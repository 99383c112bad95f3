"""decode_mfu: the useful operations of the traced decode steps (active
slots only: projections, MLP and head per token, attention over each
slot's context; ``cost.decode_step_flops``) over those programs' device
time times the chip's bf16 peak, in percent."""
from perfbench import cost, xplane


def read(run):
    tr = run.trace
    if tr is None or run.peaks is None:
        return None
    pairs = xplane.paired(tr.spans_named("bench.decode"),
                          tr.modules(0, "_decode"))
    if not pairs:
        return None
    flops = sum(cost.decode_step_flops(run.config, int(s.stats["active"]),
                                       int(s.stats["context"]))
                for s, _ in pairs)
    ns = sum(m.dur for _, m in pairs)
    return 100.0 * flops / (ns * 1e-9 * run.peaks.bf16_flops)
