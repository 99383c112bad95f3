"""Top-k MoE FFN: a router over E experts, shared experts, two dispatches.

Routing (``route``) is a softmax over the router's logits with the top-k
gates renormalised, or, for ``router_scoring="sigmoid"`` (DeepSeek-V3
``noaux_tc`` with one group), sigmoid scores whose top-k is picked by
scores plus a per-expert correction bias (``score_bias``) and weighed by
the unbiased scores of the picked experts, normalised and multiplied by
``routed_scaling_factor``.

* Serving (``moe_serve``, prefill and decode) drops nothing: every
  (token, expert) pair is a row, rows are sorted by expert, and the
  experts' FFNs are grouped matmuls over them (``kernels/ops.expert_gmm``)
  — work in the rows and the experts they hit, and a token's output
  independent of the other tokens in its batch.
* Training (``moe_apply``) keeps the grouped dense dispatch: tokens are
  reshaped into groups aligned with the data-parallel sharding, and the
  dispatch/combine tensors are (G, Ng, E, C) one-hots so every shape is
  static (capacity-factor token dropping). Constraining the dispatched
  activations to (batch, expert, ...) makes GSPMD place each expert's FFN
  on its 'model' shard — the EP exchange shows up as all-to-all /
  collective-permute in the HLO. Aux losses (load-balance + router
  z-loss) are returned for the train step.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import common, mlp
from repro.parallel import sharding


# the drawn noaux_tc correction bias's standard deviation
BIAS_SCALE = 0.05


def moe_init(rng, cfg: ArchConfig) -> dict:
    D, F, E = cfg.d_model, cfg.d_ff, cfg.num_experts
    dt = common.dtype_of(cfg)
    ks = jax.random.split(rng, 5)

    def expert_kernels(rng, in_dim, out_dim):
        scale = 1.0 / jnp.sqrt(jnp.float32(in_dim))
        return {"kernel": (jax.random.normal(rng, (E, in_dim, out_dim),
                                             jnp.float32) * scale).astype(dt)}

    p = {
        "router": common.dense_init(ks[0], D, E, jnp.float32),
        "wi": expert_kernels(ks[1], D, F),
        "wo": expert_kernels(ks[2], F, D),
    }
    if cfg.act == "swiglu":
        p["wg"] = expert_kernels(ks[3], D, F)
    if cfg.shared_experts:
        p["shared_mlp"] = mlp.mlp_init(ks[4], cfg,
                                       d_ff=cfg.d_ff * cfg.shared_experts)
    if cfg.router_scoring == "sigmoid":
        # the noaux_tc correction bias: learned in training, drawn here
        # so that selection and gating differ
        p["score_bias"] = BIAS_SCALE * jax.random.normal(
            jax.random.fold_in(rng, 5), (E,), jnp.float32)
    return p


def route(cfg: ArchConfig, p: dict, x: jax.Array):
    """x (..., D) -> (gates (..., K) f32, experts (..., K) int32, scores
    (..., E) f32, logits (..., E) f32)."""
    K = cfg.experts_per_token
    # float32 as published (a TPU's default f32 matmul rounds its operands
    # to bfloat16, which flips near-tied selections)
    logits = jnp.matmul(x, p["router"]["kernel"].astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    if cfg.router_scoring == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        _, idx = jax.lax.top_k(scores + p["score_bias"], K)
        gates = jnp.take_along_axis(scores, idx, -1)
    else:
        scores = jax.nn.softmax(logits, axis=-1)
        gates, idx = jax.lax.top_k(scores, K)
    gates = gates / jnp.maximum(jnp.sum(gates, -1, keepdims=True), 1e-9)
    if cfg.routed_scaling_factor != 1.0:
        gates = gates * cfg.routed_scaling_factor
    return gates, idx, scores, logits


EXPERT_KERNELS = ("wi", "wg", "wo")


def split_experts(layers: dict):
    """Scanned layer params ``{"l{i}": ...}`` -> (the same without the
    routed experts' kernels, ``{"l{i}": {"wi": (G, E, ...), ...}}``): a
    layer scan hands the grouped matmul the stacked kernels and its layer
    index, so the kernel reads them in place — a scanned slice of them
    would be copied whole (a layer's experts) before each call."""
    scanned, held = {}, {}
    for key, lp in layers.items():
        if "moe" not in lp:
            scanned[key] = lp
            continue
        rest = dict(lp["moe"])
        held[key] = {k: rest.pop(k) for k in EXPERT_KERNELS if k in rest}
        scanned[key] = dict(lp, moe=rest)
    return scanned, held


def moe_serve(cfg: ArchConfig, p: dict, x: jax.Array, live=None,
              layer=None):
    """Drop-free MoE over every token of ``x`` (..., D). ``live`` (the
    tokens' count, bool) marks tokens to route; the others get only the
    shared experts and cost no expert work. With ``layer``, the expert
    kernels in ``p`` are stacked over layers (``split_experts``) and read
    at that index. Returns (y, load): load (E,) int32 counts the live
    tokens routed to each expert."""
    from repro.kernels import ops as kops
    E, K = cfg.num_experts, cfg.experts_per_token
    D = x.shape[-1]
    xf = x.reshape(-1, D)
    gates, idx, _, _ = route(cfg, p, xf)
    if live is not None:
        idx = jnp.where(live.reshape(-1, 1), idx, E)  # past every expert
    flat = idx.reshape(-1)                                    # (N*K,)
    order = jnp.argsort(flat, stable=True)
    load = jnp.sum(jax.nn.one_hot(flat, E, dtype=jnp.int32), 0)
    xs = xf[order // K]                                       # by expert
    h = kops.expert_gmm(xs, p["wi"]["kernel"], load, layer).astype(x.dtype)
    if cfg.act == "swiglu":
        g = kops.expert_gmm(xs, p["wg"]["kernel"], load, layer)
        h = jax.nn.silu(g.astype(x.dtype)) * h
    else:
        h = common.act_fn(cfg.act)(h)
    out = kops.expert_gmm(h, p["wo"]["kernel"], load, layer)
    routed = jnp.arange(flat.shape[0]) < jnp.sum(load)
    out = jnp.where(routed[:, None], out, 0.0).astype(x.dtype)
    # combined in the activations' dtype, as the training dispatch does
    y = jnp.einsum("nkd,nk->nd",
                   out[jnp.argsort(order)].reshape(xf.shape[0], K, D),
                   gates.astype(x.dtype))
    y = y.reshape(x.shape)
    if "shared_mlp" in p:
        y = y + mlp.mlp_apply(cfg, p["shared_mlp"], x)
    return y, load


def _group_size(n_tokens_per_shard: int) -> int:
    g = 1
    while g < 1024 and n_tokens_per_shard % (g * 2) == 0:
        g *= 2
    return g


def moe_apply(cfg: ArchConfig, p: dict, x: jax.Array):
    """Training dispatch (capacity drops). x: (B, S, D) -> (y, aux) with
    aux = {'lb_loss', 'z_loss'}."""
    B, S, D = x.shape
    E, K = cfg.num_experts, cfg.experts_per_token
    N = B * S
    ctx = sharding.get_ctx()
    dp = ctx.axis_size("batch") if ctx else 1
    dp = max(dp, 1)
    Ng = _group_size(max(N // dp, 1))
    G = N // Ng
    C = max(1, int(Ng * K / E * cfg.capacity_factor))

    xg = x.reshape(G, Ng, D)
    xg = sharding.constrain(xg, "batch", None, None)

    gates, idx, probs, logits = route(cfg, p, xg)                  # (G,Ng,K)

    # slot assignment: order tokens within a group, count per expert
    emask = jax.nn.one_hot(idx, E, dtype=jnp.int32)                 # (G,Ng,K,E)
    flat = emask.reshape(G, Ng * K, E)
    pos = jnp.cumsum(flat, axis=1) - flat                           # slots before me
    pos = pos.reshape(G, Ng, K, E)
    slot = jnp.sum(pos * emask, -1)                                 # (G,Ng,K)
    slot_oh = jax.nn.one_hot(slot, C, dtype=x.dtype)                # >=C -> all-zero row

    # dispatch/combine: (G, Ng, E, C)
    disp = jnp.einsum("gnke,gnkc->gnec", emask.astype(x.dtype), slot_oh)
    comb = jnp.einsum("gnke,gnkc,gnk->gnec", emask.astype(jnp.float32),
                      slot_oh.astype(jnp.float32), gates).astype(x.dtype)

    xe = jnp.einsum("gnec,gnd->gecd", disp, xg)                     # (G,E,C,D)
    xe = sharding.constrain(xe, "batch", "expert", None, None)
    h = jnp.einsum("gecd,edf->gecf", xe, p["wi"]["kernel"])
    if cfg.act == "swiglu":
        h = jax.nn.silu(jnp.einsum("gecd,edf->gecf", xe, p["wg"]["kernel"])) * h
    else:
        h = common.act_fn(cfg.act)(h)
    h = sharding.constrain(h, "batch", "expert", None, None)
    out = jnp.einsum("gecf,efd->gecd", h, p["wo"]["kernel"])
    out = sharding.constrain(out, "batch", "expert", None, None)
    y = jnp.einsum("gecd,gnec->gnd", out, comb.astype(out.dtype))
    y = y.reshape(B, S, D)

    if "shared_mlp" in p:
        y = y + mlp.mlp_apply(cfg, p["shared_mlp"], x)

    # aux losses (fp32)
    density = jnp.mean(emask.astype(jnp.float32).sum(2), axis=(0, 1))   # (E,)
    router_mean = jnp.mean(probs, axis=(0, 1))
    lb_loss = E * jnp.sum(density / K * router_mean)
    z_loss = jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1)))
    return y, {"lb_loss": lb_loss, "z_loss": z_loss}
