"""Operations and bytes the algorithm needs, from a configuration's sizes.

Every function takes the configuration file's dict (``configs/*.json``)
and counts what the mathematics requires, not what a compiled program
happens to do: a multiply-add is 2 operations; bytes are those a kernel
has to read or write at least once. Dense decoder-only transformers with
a gated (SwiGLU) or plain MLP.

A batched decode step is described by its active slots ``n`` and
``context``, the positions they attend summed over those slots (position
+ 1 each): the work is linear in both, so these two numbers are all a
traced call needs to carry.
"""
from __future__ import annotations

BYTES = {"bfloat16": 2, "float16": 2, "float32": 4, "int8": 1}


def head_dim(c: dict) -> int:
    return c.get("head_dim") or c["d_model"] // c["num_heads"]


def itemsize(c: dict) -> int:
    return BYTES[c.get("dtype", "bfloat16")]


def layer_matmul_params(c: dict) -> int:
    """Weights one token multiplies through in one layer: the attention
    projections and the MLP."""
    D, H, Kv, hd, F = (c["d_model"], c["num_heads"], c["num_kv_heads"],
                       head_dim(c), c["d_ff"])
    attn = D * H * hd + 2 * D * Kv * hd + H * hd * D
    mlp = (3 if c.get("act", "swiglu") == "swiglu" else 2) * D * F
    return attn + mlp


def paged_attn_flops(c: dict, context: int) -> int:
    """Attention of single query tokens over ``context`` positions in all,
    all layers: scores and the weighted sum of values."""
    return 4 * c["num_layers"] * c["num_heads"] * head_dim(c) * int(context)


def paged_attn_bytes(c: dict, n: int, context: int) -> int:
    """Least HBM traffic of the paged-attention kernel over one decode
    step, all layers: K and V of every attended position, and the query
    read and the output written for each of the ``n`` active slots."""
    H, Kv, hd, b = c["num_heads"], c["num_kv_heads"], head_dim(c), itemsize(c)
    return c["num_layers"] * (int(context) * 2 * Kv * hd * b
                              + int(n) * 2 * H * hd * b)


def decode_step_flops(c: dict, n: int, context: int) -> int:
    """Useful operations of one batched decode step over its ``n`` active
    slots: every layer's projections and MLP and the output head for each
    slot's token, and attention over ``context`` positions."""
    per_token = (2 * c["num_layers"] * layer_matmul_params(c)
                 + 2 * c["d_model"] * c["vocab_size"])
    return int(n) * per_token + paged_attn_flops(c, context)
