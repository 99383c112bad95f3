"""Shared model building blocks: dense layers, norms, RoPE, init helpers.

All modules are functional: ``*_init(rng, ...) -> params`` (nested dict of
arrays) and ``*_apply(params, x, ...) -> y``.  Kernels are flattened 2D
(in_features, out_features) so tensor-parallel sharding never hits a
non-divisible head dim (see parallel/sharding.py).
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig


def dtype_of(cfg: ArchConfig):
    return jnp.dtype(cfg.dtype)


# ---------------------------------------------------------------------------
# dense
# ---------------------------------------------------------------------------

def dense_init(rng, in_dim: int, out_dim: int, dtype, use_bias: bool = False,
               scale: Optional[float] = None) -> dict:
    scale = scale if scale is not None else 1.0 / np.sqrt(in_dim)
    p = {"kernel": (jax.random.normal(rng, (in_dim, out_dim), jnp.float32)
                    * scale).astype(dtype)}
    if use_bias:
        p["bias"] = jnp.zeros((out_dim,), dtype)
    return p


def dense(p: dict, x: jax.Array) -> jax.Array:
    y = x @ p["kernel"]
    if "bias" in p:
        y = y + p["bias"]
    return y


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

def norm_init(cfg: ArchConfig, dim: Optional[int] = None) -> dict:
    dim = dim or cfg.d_model
    if cfg.norm == "rmsnorm":
        return {"scale": jnp.ones((dim,), dtype_of(cfg))}
    if cfg.norm == "layernorm":
        return {"scale": jnp.ones((dim,), dtype_of(cfg)),
                "bias": jnp.zeros((dim,), dtype_of(cfg))}
    if cfg.norm == "ln_nonparam":
        return {}
    raise ValueError(cfg.norm)


def norm_apply(cfg: ArchConfig, p: dict, x: jax.Array) -> jax.Array:
    xf = x.astype(jnp.float32)
    if cfg.norm == "rmsnorm":
        y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True)
                                 + cfg.norm_eps)
        return (y * p["scale"].astype(jnp.float32)).astype(x.dtype)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mean), axis=-1, keepdims=True)
    y = (xf - mean) * jax.lax.rsqrt(var + cfg.norm_eps)
    if cfg.norm == "layernorm":
        y = y * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# rotary position embedding
# ---------------------------------------------------------------------------

def rope_freqs(hd: int, theta: float) -> jax.Array:
    """(hd//2,) inverse frequencies."""
    return 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., S, n_heads, hd); positions: (S,) or (..., S)."""
    hd = x.shape[-1]
    half = hd // 2
    freqs = rope_freqs(hd, theta)                        # (half,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, half)
    cos = jnp.cos(angles)[..., None, :]                  # (..., S, 1, half)
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:2 * half]
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    o1 = xf1 * cos - xf2 * sin
    o2 = xf2 * cos + xf1 * sin
    out = jnp.concatenate([o1, o2], axis=-1)
    if hd % 2:  # odd head dims pass the tail through (not used by our archs)
        out = jnp.concatenate([out, x[..., 2 * half:].astype(jnp.float32)], axis=-1)
    return out.astype(x.dtype)


def sinusoid_pos(seq_len: int, dim: int, offset: int = 0) -> jax.Array:
    """(seq_len, dim) fixed sinusoidal embeddings (whisper-style)."""
    pos = jnp.arange(offset, offset + seq_len, dtype=jnp.float32)[:, None]
    inv = jnp.exp(-jnp.arange(0, dim, 2, dtype=jnp.float32)
                  * (np.log(10000.0) / max(dim // 2 - 1, 1)))
    ang = pos * inv[None, :]
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)[:, :dim]


# ---------------------------------------------------------------------------
# activations
# ---------------------------------------------------------------------------

def act_fn(name: str):
    return {
        "silu": jax.nn.silu,
        "gelu": jax.nn.gelu,
        "relu2": lambda x: jnp.square(jax.nn.relu(x)),
    }[name]


# ---------------------------------------------------------------------------
# stacked (scanned) init
# ---------------------------------------------------------------------------

def stacked_init(rng, n: int, init_fn):
    """vmap an init over ``n`` rngs -> params with a leading stacking dim."""
    rngs = jax.random.split(rng, n)
    return jax.vmap(init_fn)(rngs)


def embed_init(rng, vocab: int, dim: int, dtype) -> dict:
    return {"embedding": (jax.random.normal(rng, (vocab, dim), jnp.float32)
                          * (1.0 / np.sqrt(dim))).astype(dtype)}
