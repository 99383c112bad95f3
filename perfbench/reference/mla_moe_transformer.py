"""Plain float32 forward of a DeepSeek-V3-layout transformer: latent
attention, leading dense layers, then routed and shared experts.

Written from the published description (DeepSeek-V3 as Moonlight-16B-A3B
configures it), in ``jax.numpy`` at ``highest`` matmul precision, with no
kernel, cache or batching, and importing nothing of the program under
test:

- pre-norm blocks (RMSNorm), final norm, untied output head;
- MLA in its expanded form, ``q_lora_rank`` null: ``q = h W_q`` split into
  ``qk_nope_head_dim`` and rotary ``qk_rope_head_dim`` parts per head;
  ``[c, k_r] = h W_kv_a``, the latent ``c`` RMS-normed, the rotary key
  ``k_r`` shared by every head; ``[k_nope, v] = c W_kv_b`` per head; causal
  softmax over ``[q_nope, q_r] · [k_nope, k_r]`` scaled by
  ``(qk_nope_head_dim + qk_rope_head_dim) ** -0.5``;
- the first ``first_k_dense_replace`` layers: a SwiGLU MLP of
  ``dense_d_ff``; the others: ``shared_experts`` SwiGLU experts of
  ``d_ff`` as one MLP of ``shared_experts * d_ff``, plus every one of the
  ``num_experts`` routed experts computed for every token and weighed by
  its gate, zero for experts not picked;
- routing (``noaux_tc``, one group): float32 logits ``h W_r``, scores
  ``sigmoid``; the ``experts_per_token`` experts of the largest ``score +
  bias`` are picked; their gates are the unbiased scores, normalised to
  sum 1 and multiplied by ``routed_scaling_factor``.

Weights are drawn from the run's key in the program's order, each a
float32 normal times its fan-in scale, rounded once to ``dtype``: the key
split four ways (embedding; the routed layers, one key each; the output
head; the leading dense layers, one key each); a layer's key split four
ways (attention: q, kv_a, kv_b, o; FFN); a routed FFN's key split five
ways (router, wi, wo, wg, shared MLP: wi, wo, wg), its correction bias a
normal times 0.05 from the FFN key folded with 5; the router stays
float32. Departures from the published model, shared with the program
and stated in the configuration file: norms carry their initial (unit)
scales; the rotary pairs are the first and second halves of the rope
dimensions where HF DeepSeek-V3 rotates interleaved pairs (with random
weights a fixed permutation of the rope columns of ``W_q`` and
``W_kv_a``); the correction bias is drawn, not learned.

``gaps`` runs it over each prompt with its served tokens, one layer's
weights at a time (a routed layer is 2.3 GB in float32 at Moonlight's
widths), and reads the same gaps as ``dense_transformer.gaps``, with the
same ``lower`` control, at the served positions it compares: those where
the same forward in the configuration's own precision (``OWN``: each
matmul's activation and product, the residual stream, softmax and gates
rounded to bfloat16; the router float32, as the program keeps it) puts
the float32 best token first. A routed layer turns rounding into a
different pick of experts at near-ties, and the changed residual carries
it through attention to every later position and through the later
layers' routing: a sound bfloat16 forward picks other experts than the
float32 one for a large share of its tokens (the count is reported on
stderr), and at the positions where it loses the float32 best token its
gap is that of a different, equally sound routing, not a fault. A wrong
weight, gate, head or page moves the other positions as well.
"""
from __future__ import annotations

import sys

import jax
import jax.numpy as jnp
import numpy as np

from perfbench.reference.dense_transformer import _dense

F32 = jnp.float32
BIAS_SCALE = 0.05
# the configuration's own precision, the one forward besides float32 and
# the control that ``gaps`` runs
OWN = "bfloat16"


class Model:
    """Jitted pieces of the reference for one configuration dict."""

    def __init__(self, c: dict):
        if not c.get("kv_lora_rank") or not c.get("num_experts"):
            raise ValueError(f"{c['name']}: not a latent-attention MoE")
        self.c = c
        self.D, self.H = c["d_model"], c["num_heads"]
        self.R, self.dn = c["kv_lora_rank"], c["qk_nope_head_dim"]
        self.dr, self.dv = c["qk_rope_head_dim"], c["v_head_dim"]
        self.E, self.K = c["num_experts"], c["experts_per_token"]
        self.F, self.V = c["d_ff"], c["vocab_size"]
        self.n_dense = c.get("first_k_dense_replace", 0)
        self.G = c["num_layers"] - self.n_dense
        self.dtype = jnp.dtype(c["dtype"])
        self.attn_weights = jax.jit(self._attn_weights, static_argnums=1)
        self.dense_weights = jax.jit(self._dense_weights)
        self.moe_weights = jax.jit(self._moe_weights)
        self.embedding = jax.jit(lambda k: self._w(k, (self.V, self.D),
                                                   self.D ** -0.5))
        self.head = jax.jit(lambda k: self._w(k, (self.D, self.V),
                                              self.D ** -0.5))
        self.attn = jax.jit(self._attn, static_argnums=2)
        self.mlp = jax.jit(self._mlp, static_argnums=2)
        self.moe = jax.jit(self._moe, static_argnums=2)
        self.gaps = jax.jit(self._gaps, static_argnums=6)

    # -- weights ---------------------------------------------------------

    def _w(self, key, shape, scale, dtype=None):
        w = jax.random.normal(key, shape, F32) * scale
        return w if dtype is F32 else w.astype(self.dtype).astype(F32)

    def keys(self, key):
        """(embedding key, routed layer keys, head key, dense layer keys)."""
        ks = jax.random.split(key, 4)
        return (ks[0], jax.random.split(ks[1], self.G), ks[2],
                jax.random.split(ks[3], max(self.n_dense, 1)))

    @staticmethod
    def _parts(layer_key):
        """(attention key, FFN key) of a layer (a group of one layer)."""
        ka, kf = jax.random.split(jax.random.split(layer_key, 1)[0], 4)[:2]
        return ka, kf

    def _attn_weights(self, layer_key, dense: bool):
        D, H, R, dn, dr, dv = (self.D, self.H, self.R, self.dn, self.dr,
                               self.dv)
        ka = (jax.random.split(layer_key, 4)[0] if dense
              else self._parts(layer_key)[0])
        q, a, b, o = jax.random.split(ka, 4)
        return {"q": self._w(q, (D, H * (dn + dr)), D ** -0.5),
                "kv_a": self._w(a, (D, R + dr), D ** -0.5),
                "kv_b": self._w(b, (R, H * (dn + dv)), R ** -0.5),
                "o": self._w(o, (H * dv, D), (H * dv) ** -0.5)}

    def _swiglu_weights(self, key, F):
        wi, wo, wg = jax.random.split(key, 3)
        D = self.D
        return {"wi": self._w(wi, (D, F), D ** -0.5),
                "wo": self._w(wo, (F, D), F ** -0.5),
                "wg": self._w(wg, (D, F), D ** -0.5)}

    def _dense_weights(self, layer_key):
        kf = jax.random.split(layer_key, 4)[1]
        return self._swiglu_weights(kf, self.c["dense_d_ff"])

    def _moe_weights(self, layer_key):
        D, E, F = self.D, self.E, self.F
        kf = self._parts(layer_key)[1]
        ks = jax.random.split(kf, 5)

        def experts(k, n_in, n_out):
            return self._w(k, (E, n_in, n_out),
                           1.0 / jnp.sqrt(jnp.float32(n_in)))
        return {"router": self._w(ks[0], (D, E), D ** -0.5, F32),
                "bias": BIAS_SCALE * jax.random.normal(
                    jax.random.fold_in(kf, 5), (E,), F32),
                "wi": experts(ks[1], D, F), "wo": experts(ks[2], F, D),
                "wg": experts(ks[3], D, F),
                "shared": self._swiglu_weights(
                    ks[4], F * self.c["shared_experts"])}

    # -- forward ---------------------------------------------------------

    def _norm(self, x):
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                                 + self.c["norm_eps"])

    def _rope(self, x, pos):
        """x (L, n, dr): rotate the first half against the second."""
        d = x.shape[-1]
        freqs = 1.0 / (self.c["rope_theta"]
                       ** (jnp.arange(0, d, 2, dtype=F32) / d))
        ang = pos[:, None].astype(F32) * freqs
        cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
        x1, x2 = x[..., :d // 2], x[..., d // 2:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)

    def _attn(self, w, x, prec):
        """x + latent attention over one sequence ``x`` (L, D)."""
        L = x.shape[0]
        H, R, dn, dr, dv = self.H, self.R, self.dn, self.dr, self.dv
        pos = jnp.arange(L)
        h = _round(self._norm(x), prec)
        q = _mm(h, w["q"], prec).reshape(L, H, dn + dr)
        q_nope = q[..., :dn]
        q_r = _round(self._rope(q[..., dn:], pos), prec)
        a = _mm(h, w["kv_a"], prec)
        c = _round(self._norm(a[:, :R]), prec)
        k_r = _round(self._rope(a[:, None, R:], pos)[:, 0], prec)
        kv = _mm(c, w["kv_b"], prec).reshape(L, H, dn + dv)
        k_nope, v = kv[..., :dn], kv[..., dn:]
        s = (jnp.einsum("qhn,shn->hqs", q_nope, k_nope)
             + jnp.einsum("qhr,sr->hqs", q_r, k_r)) * (dn + dr) ** -0.5
        s = jnp.where(pos[None, :, None] >= pos[None, None, :], s, -jnp.inf)
        p = _round(jax.nn.softmax(s, -1), prec)
        o = _round(jnp.einsum("hqs,shv->qhv", p, v), prec)
        return _round(x + _mm(o.reshape(L, H * dv), w["o"], prec), prec)

    @staticmethod
    def _swiglu(w, h, prec):
        return _mm(_round(jax.nn.silu(_mm(h, w["wg"], prec)), prec)
                   * _mm(h, w["wi"], prec), w["wo"], prec)

    def _mlp(self, w, x, prec):
        return _round(x + self._swiglu(w, _round(self._norm(x), prec), prec),
                      prec)

    def _gates(self, w, h, prec):
        """(L, E) gates: zero but for each token's picked experts. The
        router stays float32 in the configuration's own precision."""
        logits = (h @ w["router"] if prec == OWN
                  else _dense(h, w["router"], prec))
        scores = jax.nn.sigmoid(logits)
        _, pick = jax.lax.top_k(scores + w["bias"], self.K)
        chosen = jnp.take_along_axis(scores, pick, -1)
        chosen = (chosen / jnp.sum(chosen, -1, keepdims=True)
                  * self.c["routed_scaling_factor"])
        rows = jnp.arange(h.shape[0])[:, None]
        return jnp.zeros_like(scores).at[rows, pick].set(chosen)

    def _moe(self, w, x, prec):
        """(x + shared experts + every routed expert, gate-weighted; the
        (L, E) picks)."""
        h = _round(self._norm(x), prec)
        gates = self._gates(w, h, prec)

        def expert(y, e):
            wi, wg, wo, g = e
            f = _mm(_round(jax.nn.silu(_mm(h, wg, prec)), prec)
                    * _mm(h, wi, prec), wo, prec)
            return y + _round(g[:, None] * f, prec), None

        y, _ = jax.lax.scan(expert, self._swiglu(w["shared"], h, prec),
                            (w["wi"], w["wg"], w["wo"],
                             _round(gates, prec).T))
        return _round(x + y, prec), gates > 0

    def _gaps(self, head, x, served, mask, x_own, x_low, lower):
        """Per position: whether it is compared (the forward in the
        configuration's own precision puts the reference's best token
        first), the reference's best logit minus its logit of the served
        token, and (with ``lower``) minus its logit of the token the
        lower-precision forward puts first."""
        logits = self._norm(x) @ head
        best = jnp.max(logits, -1)
        keep = mask
        if x_own is not None:
            own = _mm(_round(self._norm(x_own), OWN), head, OWN)
            keep = mask & (jnp.argmax(own, -1) == jnp.argmax(logits, -1))
        ref_at = jnp.take_along_axis(logits, served[:, None], -1)[:, 0]
        gap = best - ref_at
        if lower is None:
            return keep, gap, gap
        low = _dense(self._norm(x_low), head, lower)
        pick = jnp.argmax(low, -1)
        low_at = jnp.take_along_axis(logits, pick[:, None], -1)[:, 0]
        return keep, gap, best - low_at


def _round(x, prec):
    """``x`` as the configuration's own precision stores it (``OWN``);
    unchanged in float32 and in the control, which rounds only a matmul's
    operands."""
    return x.astype(jnp.bfloat16).astype(F32) if prec == OWN else x


def _mm(x, w, prec):
    """``x @ w``: in float32 (``prec`` None), the control's rounding of
    both operands (``int8``, ``fp8``), or the configuration's own
    precision (``OWN``): the activation and the product rounded to
    bfloat16, the weight already is."""
    if prec == OWN:
        return _round(_round(x, prec) @ w, prec)
    return _dense(x, w, prec)


def gaps(c: dict, key, seqs, lower: str | None = None, pad_to: int = 128):
    """Reference gaps over ``seqs``, a list of ``(prompt, served)``: the
    prompt token ids and the tokens served after it, in order.

    Returns a list of ``(served_gaps, lower_gaps)`` numpy arrays, one
    entry per compared served position (``lower_gaps`` is None without
    ``lower``): where the forward in the configuration's own precision
    puts the float32 reference's best token first (every position of a
    float32 configuration)."""
    m = Model(c)
    own = c["dtype"] == OWN
    with jax.default_matmul_precision("highest"):
        ek, lks, hk, dks = m.keys(key)
        rows, meta = [], []
        for prompt, served in seqs:
            toks = np.concatenate([np.asarray(prompt, np.int64),
                                   np.asarray(served[:-1], np.int64)])
            meta.append((len(prompt), len(served)))
            rows.append(toks)
        L = -(-max(len(t) for t in rows) // pad_to) * pad_to
        masks = []
        for P, n in meta:
            mk = np.zeros(L, bool)
            mk[P - 1:P - 1 + n] = True
            masks.append(jnp.asarray(mk))
        emb = m.embedding(ek)
        xs = [emb[jnp.asarray(np.pad(t, (0, L - len(t))))] for t in rows]
        del emb
        # the forwards run side by side, one layer's weights at a time:
        # float32, the configuration's own precision, the control
        runs = {"f32": xs}
        if own:
            runs["own"] = list(xs)
        if lower:
            runs["low"] = list(xs)
        precs = {"f32": None, "own": OWN, "low": lower}
        flips = total = 0

        def run(layer, w):
            nonlocal flips, total
            picks = {}
            for name, ys in runs.items():
                out = [layer(w, x, precs[name]) for x in ys]
                if isinstance(out[0], tuple):
                    picks[name] = [p for _, p in out]
                    out = [y for y, _ in out]
                runs[name] = out
            if own and picks:
                for a, b, mk in zip(picks["f32"], picks["own"], masks):
                    flips += int(jnp.sum(jnp.any(a != b, -1) & mk))
                    total += int(mk.sum())

        for j in range(m.n_dense):
            run(m.attn, m.attn_weights(dks[j], True))
            run(m.mlp, m.dense_weights(dks[j]))
        for g in range(m.G):
            run(m.attn, m.attn_weights(lks[g], False))
            run(m.moe, m.moe_weights(lks[g]))
        head = m.head(hk)
        out, kept = [], 0
        for i, (P, n) in enumerate(meta):
            served = np.zeros(L, np.int32)
            served[P - 1:P - 1 + n] = np.asarray(seqs[i][1], np.int32)
            x = runs["f32"][i]
            keep, g, gl = m.gaps(head, x, jnp.asarray(served), masks[i],
                                 runs["own"][i] if own else None,
                                 runs["low"][i] if lower else x, lower)
            keep = np.asarray(keep)
            kept += int(keep.sum())
            out.append((np.asarray(g)[keep],
                        np.asarray(gl)[keep] if lower else None))
        print(f"[bench] reference routing: {flips} of {total} token-layer "
              f"expert selections differ in the {OWN} forward; "
              f"{kept} of {sum(n for _, n in meta)} served positions "
              f"compared (its best token the float32 best)",
              file=sys.stderr, flush=True)
        return out


def logits(c: dict, key, tokens) -> np.ndarray:
    """The reference's logits (L, V) over one sequence of token ids."""
    m = Model(c)
    with jax.default_matmul_precision("highest"):
        ek, lks, hk, dks = m.keys(key)
        x = m.embedding(ek)[jnp.asarray(np.asarray(tokens, np.int64))]
        for j in range(m.n_dense):
            x = m.attn(m.attn_weights(dks[j], True), x, None)
            x = m.mlp(m.dense_weights(dks[j]), x, None)
        for g in range(m.G):
            x = m.attn(m.attn_weights(lks[g], False), x, None)
            x, _ = m.moe(m.moe_weights(lks[g]), x, None)
        return np.asarray(m._norm(x) @ m.head(hk))
