"""Pipeline parallelism: skewed microbatch schedule over a 'stage' mesh axis.

A compact GPipe-style schedule expressed with ``shard_map`` + ppermute:
tick t runs microbatch (t - s) on stage s, activations hop stage->stage+1
each tick.  Autodiff through ppermute (transpose = reversed permutation)
yields the backward pipeline for free, so ``jax.grad`` of a pipelined loss
works out of the box.

The production configs use FSDP+TP (see DESIGN.md section 4); this module is
the PP building block for deployments that need cross-pod stages instead of
cross-pod DP, and is exercised by tests/test_pipeline.py.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp



def pipeline(stage_fn, n_stages: int, axis_name: str = "stage"):
    """Wrap ``stage_fn(stage_params, x) -> y`` into a pipelined apply.

    Returns ``apply(stacked_params, microbatches)`` to run inside a
    ``shard_map`` that is manual over ``axis_name``:
      stacked_params: per-stage params (leading dim sharded over stages)
      microbatches:   (n_micro, mb, ...) replicated input microbatches
    Output: (n_micro, mb, ...) pipeline outputs (from the last stage).
    """

    def apply(stage_params, microbatches):
        # params arrive stacked (leading stage dim, local size 1): unstack
        stage_params = jax.tree_util.tree_map(lambda a: a[0], stage_params)
        n_micro = microbatches.shape[0]
        me = jax.lax.axis_index(axis_name)
        fwd_perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
        total = n_micro + n_stages - 1
        pad = jnp.zeros((n_stages - 1,) + microbatches.shape[1:],
                        microbatches.dtype)
        feed = jnp.concatenate([microbatches, pad], axis=0)

        def tick(carry, mb_in):
            incoming = carry                       # activation from stage-1
            x = jnp.where(me == 0, mb_in, incoming)
            y = stage_fn(stage_params, x)
            out = y                                # last stage's y is output
            sent = jax.lax.ppermute(y, axis_name, fwd_perm)
            return sent, out

        init = jax.lax.pcast(jnp.zeros_like(feed[0]), (axis_name,),
                             to="varying")
        _, outs = jax.lax.scan(tick, init, feed)
        # stage s emits microbatch m at tick m + s; collect from last stage
        idx = jnp.arange(n_micro) + (n_stages - 1)
        outs = outs[idx]
        # broadcast the last stage's outputs to every stage
        sel = (me == n_stages - 1).astype(outs.dtype)
        return jax.lax.psum(outs * sel, axis_name)

    return apply


def pipelined_loss(stage_fn, loss_fn, n_stages: int, axis_name: str = "stage"):
    """Loss over a pipelined model: mean over microbatches of ``loss_fn``.

    The loss is computed on the last stage and broadcast (pmax) so every
    stage returns the same scalar — required for jax.grad under shard_map.
    """
    apply = pipeline(stage_fn, n_stages, axis_name)

    def fn(stage_params, microbatches, targets):
        outs = apply(stage_params, microbatches)   # replicated across stages
        loss = loss_fn(outs, targets)
        # mask to the last stage before psum: keeps the value exact while
        # leaving a single live backward chain (no n_stages overcount)
        me = jax.lax.axis_index(axis_name)
        return jax.lax.psum(
            jnp.where(me == n_stages - 1, loss, 0.0), axis_name)

    return fn
