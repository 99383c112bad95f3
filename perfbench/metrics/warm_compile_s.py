"""warm_compile_s: seconds JAX spent in backend compiles during set-up
(``/jax/core/compile/backend_compile_duration`` events; a program loaded
from the persistent cache counts its load)."""


def read(run):
    return run.setup_compile_s
