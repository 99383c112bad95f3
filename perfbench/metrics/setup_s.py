"""setup_s: seconds from process start until the window opens — JAX's
start, the parameters drawn on the device, the engine built, and every
program the window calls compiled or loaded from the cache."""


def read(run):
    return run.setup_s
