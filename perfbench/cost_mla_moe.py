"""Operations and bytes the algorithm needs for a latent-attention MoE
decode step (DeepSeek-V3 layout), from a configuration file's sizes.

As in ``cost.py``: a multiply-add is 2 operations; bytes are those a
kernel has to read or write at least once; a batched decode step is
described by its ``n`` active slots and ``context``, the positions they
attend summed over those slots. Attention is counted in its absorbed
form, the one the decode step computes: a head's query is one row of
``kv_lora_rank + qk_rope_head_dim`` values scored against each cached
row of the same width, and its output a weighted sum of the rows'
``kv_lora_rank`` latents. The expert layer streams the weights of the
experts its tokens hit, so its bytes take ``experts_hit`` (distinct
experts per MoE layer), which the engine counts.
"""
from __future__ import annotations

from perfbench.cost import itemsize


def row(c: dict) -> int:
    """Values cached per token and layer: latent and rope key."""
    return c["kv_lora_rank"] + c["qk_rope_head_dim"]


def moe_layers(c: dict) -> int:
    return c["num_layers"] - c.get("first_k_dense_replace", 0)


def expert_params(c: dict) -> int:
    """One SwiGLU expert's weights (gate, up, down)."""
    return 3 * c["d_model"] * c["d_ff"]


def mla_attn_flops(c: dict, context: int) -> int:
    """Scores and weighted latents of single absorbed queries over
    ``context`` positions in all, every layer."""
    H, R = c["num_heads"], c["kv_lora_rank"]
    return 2 * c["num_layers"] * H * (row(c) + R) * int(context)


def mla_attn_bytes(c: dict, n: int, context: int) -> int:
    """Least HBM traffic of the latent kernel over one step, every layer:
    each attended row once, and each active slot's query read and output
    written (only the row's own columns count, not the lanes it is padded
    to)."""
    H, R, b = c["num_heads"], c["kv_lora_rank"], itemsize(c)
    return c["num_layers"] * (int(context) * row(c) * b
                              + int(n) * H * (row(c) + R) * b)


def expert_gmm_flops(c: dict, n: int) -> int:
    """The routed experts' matmuls of ``n`` tokens, every MoE layer."""
    return (2 * moe_layers(c) * int(n) * c["experts_per_token"]
            * expert_params(c))


def expert_gmm_bytes(c: dict, n: int, experts_hit: float) -> float:
    """Least HBM traffic of the routed experts over one step: the weights
    of the ``experts_hit`` experts each MoE layer's tokens hit, and the
    rows moved — each token-expert row read in (``d_model``), its gate and
    up projections out and its down projection in (``3 * d_ff``), and its
    result out (``d_model``)."""
    D, F, b = c["d_model"], c["d_ff"], itemsize(c)
    rows = int(n) * c["experts_per_token"] * (2 * D + 3 * F) * b
    return moe_layers(c) * (experts_hit * expert_params(c) * b + rows)


def token_flops(c: dict) -> int:
    """Operations one decode token needs outside attention over its
    context: per layer the absorbed latent attention's projections (q,
    kv_a, q_nope into the latent, the latent out to v, o), the dense
    layers' MLP or, in MoE layers, the router, the routed experts it is
    sent to and the shared experts; then the output head."""
    D, H, R = c["d_model"], c["num_heads"], c["kv_lora_rank"]
    dn, dr, dv = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                  c["v_head_dim"])
    attn = (D * H * (dn + dr) + D * row(c) + H * dn * R + H * R * dv
            + H * dv * D)
    dense = 3 * D * c["dense_d_ff"]
    moe = (D * c["num_experts"] + (c["experts_per_token"]
                                   + c.get("shared_experts", 0))
           * expert_params(c))
    n_dense = c.get("first_k_dense_replace", 0)
    return 2 * (c["num_layers"] * attn + n_dense * dense
                + moe_layers(c) * moe + D * c["vocab_size"])


def decode_step_flops(c: dict, n: int, context: int) -> int:
    """Useful operations of one batched decode step over its ``n`` active
    slots attending ``context`` positions in all."""
    return int(n) * token_flops(c) + mla_attn_flops(c, context)
