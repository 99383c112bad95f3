"""``correct`` at a size a test run holds (CPU, float32, tiny widths): the
reference draws the program's weights; a sound run passes; a run with its
timed path broken underneath fails; the lower-precision control fails.

The tiny configurations keep each configuration's structure (MHA with a
tied head; GQA 4:1 with an untied head) at ``d_model`` 64 in float32, so
the program agrees with the float32 reference to rounding, and the limit
below sits far above that and far below a wrong token.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import check, run, spec, sut, traffic
from perfbench.reference import dense_transformer as ref
from repro.models import registry

# float32 program vs float32 reference: gaps of order 1e-6; a flipped
# token moves the served logit by the spread of the top logits (~0.1)
LIMIT = 1e-3
MEAN_LIMIT = 1e-4

MIX = {"kind": "backlog", "requests": 6,
       "prompt_len": {"median": 16, "sigma": 0.6, "buckets": [8, 16, 32]},
       "output_len": {"median": 12, "sigma": 0.5, "buckets": [8, 16]}}


def tiny(name: str, dtype: str = "float32") -> dict:
    c = spec.config(spec.load(), name)
    kv = 4 if c["num_kv_heads"] == c["num_heads"] else 1
    c.update(num_layers=2, d_model=64, num_heads=4, num_kv_heads=kv,
             head_dim=16, d_ff=96, vocab_size=512, dtype=dtype)
    c["serving"].update(n_slots=4, cache_len=64)
    c["check"] = {"sample_requests": 3, "max_logit_gap": LIMIT,
                  "mean_logit_gap": MEAN_LIMIT}
    return c


def cell_run(c, seed=2 ** 31 + 3, tamper=None):
    return run.run_cell(spec.load(), "tiny", c, MIX, seed, 30.0, False,
                        tamper=tamper, t_start=time.perf_counter())


@pytest.mark.parametrize("name", ["olmo-1b", "mistral-nemo-12b-s8"])
def test_reference_draws_the_programs_weights(name):
    c = tiny(name, "bfloat16")
    key = sut.seed_key(2 ** 33 + 1)
    prog = registry.init_params(sut.arch(c), key)
    m = ref.Model(c)
    ek, lks, hk = m.keys(key)
    np.testing.assert_array_equal(
        np.asarray(m.embedding(ek)),
        np.asarray(prog["embed"]["embedding"], np.float32))
    if not c["tie_embeddings"]:
        np.testing.assert_array_equal(
            np.asarray(m.head(hk)),
            np.asarray(prog["lm_head"]["kernel"], np.float32))
    for g in range(m.G):
        w = m.layer_weights(lks[g], 0)
        lay = prog["layers"]["l0"]
        got = {"q": lay["attn"]["q"], "k": lay["attn"]["k"],
               "v": lay["attn"]["v"], "o": lay["attn"]["o"],
               "wi": lay["mlp"]["wi"], "wo": lay["mlp"]["wo"],
               "wg": lay["mlp"]["wg"]}
        for k, p in got.items():
            np.testing.assert_array_equal(
                np.asarray(w[k]), np.asarray(p["kernel"][g], np.float32))


@pytest.mark.parametrize("name", ["olmo-1b", "mistral-nemo-12b-s8"])
def test_sound_run_is_correct(name):
    out = cell_run(tiny(name))
    assert out["correct"], out["checks"]
    assert out["checks"]["max_logit_gap"]["value"] < LIMIT / 10
    assert out["checks"]["mean_logit_gap"]["value"] < MEAN_LIMIT / 10
    assert out["checks"]["bad_tokens"]["value"] == 0
    assert out["attempted"] == MIX["requests"] and out["failed"] == 0
    assert list(out)[-1] == "checks"


def token_altered(eng):
    """The decode step's next token is not its best one."""
    decode = eng._decode

    def bad(*a):
        logits, pool = decode(*a)
        return jnp.roll(logits, 1, axis=-1), pool
    eng._decode = bad


def half_batch_left_out(eng):
    """The decode step serves only the first half of its slots: the other
    half are given the first half's next tokens."""
    decode = eng._decode

    def bad(*a):
        logits, pool = decode(*a)
        half = logits.shape[0] // 2
        return jnp.concatenate([logits[:half], logits[:half]]), pool
    eng._decode = bad


def decode_state_unchanged(eng):
    """The decode step returns the KV pool it was given: no token's keys
    and values are kept."""
    decode = eng._decode

    def bad(params, tok, idx, pool, tables):
        logits, _ = decode(params, tok, idx,
                           jax.tree_util.tree_map(jnp.copy, pool), tables)
        return logits, pool
    eng._decode = bad


def insert_state_unchanged(eng):
    """Admission returns the pool unchanged: the prompt's keys and values
    never reach the request's pages."""
    insert = eng._insert

    def bad(pool, caches, row):
        insert(jax.tree_util.tree_map(jnp.copy, pool), caches, row)
        return pool
    eng._insert = bad


@pytest.mark.parametrize("fault", [token_altered, half_batch_left_out,
                                   decode_state_unchanged,
                                   insert_state_unchanged])
def test_broken_timed_path_is_not_correct(fault):
    out = cell_run(tiny("mistral-nemo-12b-s8"), tamper=fault)
    assert not out["correct"]
    assert out["checks"]["max_logit_gap"]["value"] > LIMIT
    assert out["checks"]["mean_logit_gap"]["value"] > MEAN_LIMIT


def test_lower_precision_control_is_not_correct():
    """The reference in int8 in the program's place, read at every served
    position of the program's own requests (all twelve: 152 tokens), on
    three seeds."""
    c = tiny("olmo-1b")
    c["check"]["sample_requests"] = 12
    for seed in (11, 12, 2 ** 32 + 13):
        key = sut.seed_key(seed)
        _, eng = sut.build(c, key)
        reqs = sut.make_requests(traffic.requests(
            dict(MIX, requests=12), c["vocab_size"], seed, 1.0))
        eng.run(reqs)
        v = check.verdict(c, key, reqs, seed, lower="int8")
        assert v["correct"]
        assert v["control"]["max_logit_gap"] > LIMIT
        assert v["control"]["mean_logit_gap"] > MEAN_LIMIT
        assert v["control_correct"] is False
