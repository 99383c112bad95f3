"""decode_mfu_moe: the useful operations of the traced decode steps of a
latent-attention MoE (active slots only; per token the absorbed latent
attention's projections, the dense layers' MLP, the router, the routed
and shared experts the token is sent to, and the head; attention over
each slot's context; ``cost_mla_moe.decode_step_flops``) over those
programs' device time times the chip's bf16 peak, in percent. A
configuration without latent attention leaves it out."""
from perfbench import cost_mla_moe, xplane


def read(run):
    tr = run.trace
    if tr is None or run.peaks is None or not run.config.get("kv_lora_rank"):
        return None
    pairs = xplane.paired(tr.spans_named("bench.decode"),
                          tr.modules(0, "_decode"))
    if not pairs:
        return None
    flops = sum(cost_mla_moe.decode_step_flops(
        run.config, int(s.stats["active"]), int(s.stats["context"]))
        for s, _ in pairs)
    ns = sum(m.dur for _, m in pairs)
    return 100.0 * flops / (ns * 1e-9 * run.peaks.bf16_flops)
