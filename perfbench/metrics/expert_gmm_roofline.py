"""expert_gmm_roofline: the routed experts' grouped matmuls' share of
their roofline over the traced decode steps, in percent.

The least time is the larger of the required operations over the bf16
peak and the required bytes over the HBM bandwidth
(``cost_mla_moe.expert_gmm_flops`` / ``expert_gmm_bytes``: each active
slot's token through its experts; the weights of the experts hit, and
the rows moved). The experts hit are not known per traced step: the
window's mean of the engine's ``experts_hit`` counter stands for them
(over 32 busy slots it moves by a fraction of an expert from step to
step). The share is that time over the device time of the kernel's
operations inside the decode programs (``%expert_gmm…``). Without the
kernel or the counter the metric is left out.
"""
import re
import sys

from perfbench import cost_mla_moe, xplane
from perfbench.metrics import experts_hit as hit_reader

KERNEL = re.compile(r"^%\w*expert_gmm[\w.]* = .*custom_call_target="
                    r"\"tpu_custom_call\"")


def read(run):
    tr = run.trace
    hit = hit_reader.read(run)
    if tr is None or run.peaks is None or hit is None:
        return None
    pairs = xplane.paired(tr.spans_named("bench.decode"),
                          tr.modules(0, "_decode"))
    ops = [e for e in tr.devices[0]["ops"] if KERNEL.match(e.name)]
    ns = sum(e.dur for _, m in pairs for e in ops
             if m.start <= e.start < m.end)
    if ns <= 0:
        return None
    c = run.config
    flops = sum(cost_mla_moe.expert_gmm_flops(c, int(s.stats["active"]))
                for s, _ in pairs)
    nbytes = sum(cost_mla_moe.expert_gmm_bytes(c, int(s.stats["active"]),
                                               hit) for s, _ in pairs)
    t_flops = flops / run.peaks.bf16_flops
    t_bytes = nbytes / run.peaks.hbm_bytes_per_s
    print(f"[bench] expert gmm: {flops} flops, {nbytes:.0f} bytes "
          f"({hit:.2f} experts hit a layer) over {ns * 1e-9:.6f}s of kernel "
          f"time, {'memory' if t_bytes >= t_flops else 'compute'}-bound",
          file=sys.stderr)
    return 100.0 * max(t_flops, t_bytes) / (ns * 1e-9)
