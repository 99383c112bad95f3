"""mla_attn_roofline: the paged latent-attention kernel's share of its
roofline over the traced decode steps, in percent.

The least time is the larger of the kernel's required operations over
the bf16 peak and its required bytes over the HBM bandwidth
(``cost_mla_moe.mla_attn_flops`` / ``mla_attn_bytes``: the live positions
of the active slots, from the ``bench.decode`` spans' ``active`` and
``context``; each 576-value row counted, not the 640 lanes it is padded
to); the share is that time over the device time of the kernel's
operations inside the decode programs (``%paged_mla_attention…``). A
program without the kernel leaves the metric out.
"""
import re
import sys

from perfbench import cost_mla_moe, xplane

KERNEL = re.compile(r"^%\w*paged_mla_attention[\w.]* = .*custom_call_target="
                    r"\"tpu_custom_call\"")


def read(run):
    tr = run.trace
    if tr is None or run.peaks is None:
        return None
    pairs = xplane.paired(tr.spans_named("bench.decode"),
                          tr.modules(0, "_decode"))
    ops = [e for e in tr.devices[0]["ops"] if KERNEL.match(e.name)]
    ns = sum(e.dur for _, m in pairs for e in ops
             if m.start <= e.start < m.end)
    if ns <= 0:
        return None
    c = run.config
    flops = sum(cost_mla_moe.mla_attn_flops(c, int(s.stats["context"]))
                for s, _ in pairs)
    nbytes = sum(cost_mla_moe.mla_attn_bytes(c, int(s.stats["active"]),
                                             int(s.stats["context"]))
                 for s, _ in pairs)
    t_flops = flops / run.peaks.bf16_flops
    t_bytes = nbytes / run.peaks.hbm_bytes_per_s
    print(f"[bench] latent attention: {flops} flops, {nbytes} bytes over "
          f"{ns * 1e-9:.6f}s of kernel time, "
          f"{'memory' if t_bytes >= t_flops else 'compute'}-bound",
          file=sys.stderr)
    return 100.0 * max(t_flops, t_bytes) / (ns * 1e-9)
