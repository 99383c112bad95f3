"""paged_attn_roofline: the paged-attention kernel's share of its
roofline over the traced decode steps, in percent.

The least time the chip could take is the larger of the kernel's
required operations over the bf16 peak and its required bytes over the
HBM bandwidth (``cost.paged_attn_flops`` / ``cost.paged_attn_bytes``:
the live positions of the active slots, from the ``bench.decode`` spans'
``active`` and ``context``); the share is that time over the kernel's
device time, the operations inside the decode programs that the
compiler emitted for the Pallas call.
"""
import re
import sys

from perfbench import cost, xplane

# how the kernel shows among a TPU trace's device operations:
# ``%_paged_attention.7 = bf16[16,16,128]{...} custom-call(...),
# custom_call_target="tpu_custom_call", ...`` (other custom calls of the
# decode program, such as ``AllocateBuffer``, are not the kernel)
KERNEL = re.compile(r"^%\w*paged_attention[\w.]* = .*custom_call_target="
                    r"\"tpu_custom_call\"")


def is_kernel(ev) -> bool:
    return bool(KERNEL.match(ev.name))


def read(run):
    tr = run.trace
    if tr is None or run.peaks is None:
        return None
    pairs = xplane.paired(tr.spans_named("bench.decode"),
                          tr.modules(0, "_decode"))
    if not pairs:
        return None
    ops = [e for e in tr.devices[0]["ops"] if is_kernel(e)]
    ns = 0.0
    for _, m in pairs:
        ns += sum(e.dur for e in ops if m.start <= e.start < m.end)
    if ns <= 0:
        return None
    c = run.config
    flops = sum(cost.paged_attn_flops(c, int(s.stats["context"]))
                for s, _ in pairs)
    nbytes = sum(cost.paged_attn_bytes(c, int(s.stats["active"]),
                                       int(s.stats["context"]))
                 for s, _ in pairs)
    t_flops = flops / run.peaks.bf16_flops
    t_bytes = nbytes / run.peaks.hbm_bytes_per_s
    bound = "memory" if t_bytes >= t_flops else "compute"
    print(f"[bench] paged attention: {flops} flops, {nbytes} bytes over "
          f"{ns * 1e-9:.6f}s of kernel time, {bound}-bound",
          file=sys.stderr)
    return 100.0 * max(t_flops, t_bytes) / (ns * 1e-9)
