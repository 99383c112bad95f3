"""Plain float32 forward of a dense decoder-only transformer.

Written from the published description of the family (pre-norm blocks,
rotary positions on the first/second halves of each head, grouped-query
causal softmax attention, SwiGLU MLP, final norm, tied or untied output
head), in ``jax.numpy`` at ``highest`` matmul precision, with no kernel,
cache or batching. It imports nothing of the program under test.

Its weights are drawn from the run's key in a fixed order (the key split
four ways: embedding, layers — one key per layer, split into attention
q/k/v/o and MLP wi/wo/wg — and output head), each a float32 normal times
its fan-in scale rounded once to the configuration's ``dtype``: the
weights the benchmark serves, drawn here independently of the program.
Departures from the published models, shared with the program and stated
in each configuration file: norms carry their initial (unit) scales, and
the norm epsilon is the file's ``norm_eps``.

``gaps`` runs it over each prompt with its served tokens, one layer's
weights at a time, and reads, at every served position, how far the
served token's logit lies below the reference's best. With ``lower`` set
it also runs the same forward with every dense matmul's operands rounded
to ``int8`` or ``fp8`` (per row of activations, per output column of
weights) and reads the same gap for the token that forward puts first:
the control, computing in the precision below the configuration's.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


def _quant(x, axis: int, lower: str | None):
    """Round ``x`` to ``lower`` precision with one scale per slice along
    ``axis`` (the reduced axis of its matmul), then back to float32."""
    if lower is None:
        return x
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    if lower == "int8":
        s = jnp.maximum(amax, 1e-30) / 127.0
        return jnp.clip(jnp.round(x / s), -127, 127) * s
    if lower == "fp8":
        s = jnp.maximum(amax, 1e-30) / 448.0
        return (x / s).astype(jnp.float8_e4m3fn).astype(F32) * s
    raise ValueError(f"unknown lower precision {lower!r}")


def _dense(x, w, lower):
    return _quant(x, -1, lower) @ _quant(w, 0, lower)


class Model:
    """Jitted pieces of the reference for one configuration dict."""

    def __init__(self, c: dict):
        if c["family"] != "dense" or c.get("num_experts", 0):
            raise ValueError(f"{c['name']}: not a dense transformer")
        self.c = c
        self.D, self.H = c["d_model"], c["num_heads"]
        self.Kv = c["num_kv_heads"]
        self.hd = c.get("head_dim") or self.D // self.H
        self.F, self.V = c["d_ff"], c["vocab_size"]
        self.G = c["num_layers"] // c.get("layer_group", 1)
        self.dtype = jnp.dtype(c["dtype"])
        self.layer_weights = jax.jit(self._layer_weights, static_argnums=1)
        self.embedding = jax.jit(lambda k: self._w(k, (self.V, self.D),
                                                   self.D ** -0.5))
        self.head = jax.jit(lambda k: self._w(k, (self.D, self.V),
                                              self.D ** -0.5))
        self.layer = jax.jit(self._layer, static_argnums=2)
        self.gaps = jax.jit(self._gaps, static_argnums=5)

    # -- weights ---------------------------------------------------------

    def _w(self, key, shape, scale):
        return (jax.random.normal(key, shape, F32) * scale).astype(
            self.dtype).astype(F32)

    def keys(self, key):
        """(embedding key, per-group layer keys, output-head key)."""
        ks = jax.random.split(key, 4)
        return ks[0], jax.random.split(ks[1], self.G), ks[2]

    def _layer_weights(self, group_key, l: int):
        D, H, Kv, hd, F = self.D, self.H, self.Kv, self.hd, self.F
        lk = jax.random.split(group_key, self.c.get("layer_group", 1))[l]
        ka, km = jax.random.split(lk, 4)[:2]
        q, k, v, o = jax.random.split(ka, 4)
        wi, wo, wg = jax.random.split(km, 3)
        return {"q": self._w(q, (D, H * hd), D ** -0.5),
                "k": self._w(k, (D, Kv * hd), D ** -0.5),
                "v": self._w(v, (D, Kv * hd), D ** -0.5),
                "o": self._w(o, (H * hd, D), (H * hd) ** -0.5),
                "wi": self._w(wi, (D, F), D ** -0.5),
                "wo": self._w(wo, (F, D), F ** -0.5),
                "wg": self._w(wg, (D, F), D ** -0.5)}

    # -- forward ---------------------------------------------------------

    def _norm(self, x):
        eps = self.c["norm_eps"]
        if self.c["norm"] == "rmsnorm":
            return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
        mu = jnp.mean(x, -1, keepdims=True)
        var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
        return (x - mu) * jax.lax.rsqrt(var + eps)

    def _rope(self, x, pos):
        half = self.hd // 2
        freqs = 1.0 / (self.c["rope_theta"]
                       ** (jnp.arange(0, self.hd, 2, dtype=F32) / self.hd))
        ang = pos[:, None].astype(F32) * freqs
        cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
        x1, x2 = x[..., :half], x[..., half:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)

    def _layer(self, w, x, lower):
        """One pre-norm block over one sequence ``x`` (L, D)."""
        L = x.shape[0]
        H, Kv, hd = self.H, self.Kv, self.hd
        pos = jnp.arange(L)
        h = self._norm(x)
        q = self._rope(_dense(h, w["q"], lower).reshape(L, H, hd), pos)
        k = self._rope(_dense(h, w["k"], lower).reshape(L, Kv, hd), pos)
        v = _dense(h, w["v"], lower).reshape(L, Kv, hd)
        q = q.reshape(L, Kv, H // Kv, hd)
        s = jnp.einsum("qgrh,sgh->grqs", q, k) * hd ** -0.5
        s = jnp.where(pos[None, None, :, None] >= pos[None, None, None, :],
                      s, -jnp.inf)
        a = jnp.einsum("grqs,sgh->qgrh", jax.nn.softmax(s, -1), v)
        x = x + _dense(a.reshape(L, H * hd), w["o"], lower)
        h = self._norm(x)
        if self.c["act"] == "swiglu":
            f = jax.nn.silu(_dense(h, w["wg"], lower)) * _dense(h, w["wi"],
                                                               lower)
        else:
            f = jax.nn.gelu(_dense(h, w["wi"], lower))
        return x + _dense(f, w["wo"], lower)

    def _gaps(self, head, x, served, mask, x_low, lower):
        """Per position: the reference's best logit minus its logit of the
        served token, and (with ``lower``) minus its logit of the token
        the lower-precision forward puts first."""
        logits = self._norm(x) @ head
        best = jnp.max(logits, -1)
        ref_at = jnp.take_along_axis(logits, served[:, None], -1)[:, 0]
        gap = jnp.where(mask, best - ref_at, 0.0)
        if lower is None:
            return gap, gap
        low = _dense(self._norm(x_low), head, lower)
        pick = jnp.argmax(low, -1)
        low_at = jnp.take_along_axis(logits, pick[:, None], -1)[:, 0]
        return gap, jnp.where(mask, best - low_at, 0.0)


def gaps(c: dict, key, seqs, lower: str | None = None, pad_to: int = 128):
    """Reference gaps over ``seqs``, a list of ``(prompt, served)``: the
    prompt token ids and the tokens served after it, in order.

    Returns a list of ``(served_gaps, lower_gaps)`` numpy arrays, one
    entry per served token (``lower_gaps`` is None without ``lower``)."""
    m = Model(c)
    with jax.default_matmul_precision("highest"):
        ek, lks, hk = m.keys(key)
        rows, meta = [], []
        for prompt, served in seqs:
            toks = np.concatenate([np.asarray(prompt, np.int64),
                                   np.asarray(served[:-1], np.int64)])
            meta.append((len(prompt), len(served)))
            rows.append(toks)
        L = -(-max(len(t) for t in rows) // pad_to) * pad_to
        emb = m.embedding(ek)
        xs = [emb[jnp.asarray(np.pad(t, (0, L - len(t))))] for t in rows]
        lo = list(xs) if lower else None
        tied = c["tie_embeddings"]
        head = emb.T if tied else None
        del emb
        for g in range(m.G):
            for l in range(c.get("layer_group", 1)):
                w = m.layer_weights(lks[g], l)
                xs = [m.layer(w, x, None) for x in xs]
                if lower:
                    lo = [m.layer(w, x, lower) for x in lo]
                del w
        if not tied:
            head = m.head(hk)
        out = []
        for i, (P, n) in enumerate(meta):
            served = np.zeros(L, np.int32)
            served[P - 1:P - 1 + n] = np.asarray(seqs[i][1], np.int32)
            mask = np.zeros(L, bool)
            mask[P - 1:P - 1 + n] = True
            g, gl = m.gaps(head, xs[i], jnp.asarray(served), jnp.asarray(mask),
                           lo[i] if lower else xs[i], lower)
            g, gl = np.asarray(g)[mask], np.asarray(gl)[mask]
            out.append((g, gl if lower else None))
        return out
