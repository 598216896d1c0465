"""GPipe-style pipeline parallelism over the ``pod`` axis.

The multi-pod mesh's ``pod`` axis defaults to data parallelism (one gradient
all-reduce per step crosses the slow inter-pod links).  For models whose
per-pod parameter shard is still too large, this module instead places
*contiguous layer blocks* on successive pods and streams microbatches through
them with ``collective_permute`` (ICI/DCN point-to-point) — the classic GPipe
fill/drain schedule, expressed in ``shard_map``.

``pipeline_apply(stage_fn, stage_params, x, mesh, axis)``:
  * ``stage_params``: pytree with leading dim = n_stages, sharded over
    ``axis`` (one stage per mesh slice);
  * ``x``: (n_micro, mb, ...) microbatched input, replicated over ``axis``;
  * result: (n_micro, mb, ...) outputs (as produced by the *last* stage,
    broadcast back).

Bubble fraction is (S-1)/(n_micro + S - 1); the dry-run's cost analysis is
how we account for it (EXPERIMENTS.md §Perf discusses when PP beats pure DP
across pods).  Equivalence with the sequential stack is tested on a 4-device
CPU mesh in tests/test_pipeline.py.
"""
from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


__all__ = ["pipeline_apply"]


def pipeline_apply(
    stage_fn: Callable[[Any, jax.Array], jax.Array],
    stage_params: Any,
    x: jax.Array,
    *,
    mesh: Mesh,
    axis: str = "pod",
) -> jax.Array:
    """Run ``x``'s microbatches through pipeline stages laid out on ``axis``.

    ``stage_fn(params_one_stage, mb) -> mb`` must preserve the microbatch
    shape (a residual-block stack does).
    """
    S = mesh.shape[axis]
    n_micro = x.shape[0]
    assert n_micro >= S, f"need >= {S} microbatches to fill the pipeline"

    p_specs = jax.tree_util.tree_map(
        lambda a: P(axis, *[None] * (a.ndim - 1)), stage_params)
    x_spec = P(*[None] * x.ndim)

    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(p_specs, x_spec), out_specs=x_spec, check_vma=False)
    def run(local_params, xs):
        # local_params leaves: (1, ...) -> squeeze the stage dim
        lp = jax.tree_util.tree_map(lambda a: a[0], local_params)
        stage = jax.lax.axis_index(axis)
        mb_shape = xs.shape[1:]
        T = n_micro + S - 1          # fill + steady + drain ticks
        perm = [(i, i + 1) for i in range(S - 1)]

        def tick(carry, t):
            buf, outs = carry
            # stage 0 ingests microbatch t (or zeros past the end)
            feed = jax.lax.dynamic_index_in_dim(
                xs, jnp.minimum(t, n_micro - 1), axis=0, keepdims=False)
            inp = jnp.where(stage == 0, feed, buf)
            out = stage_fn(lp, inp)
            # pass to the next stage; last stage's output is recorded
            buf2 = jax.lax.ppermute(out, axis, perm)
            # the last stage emitted microbatch (t - (S-1)) at tick t
            emit_idx = t - (S - 1)
            outs = jax.lax.cond(
                emit_idx >= 0,
                lambda o: jax.lax.dynamic_update_index_in_dim(
                    o, out, jnp.maximum(emit_idx, 0), axis=0),
                lambda o: o,
                outs)
            return (buf2, outs), None

        buf0 = jnp.zeros(mb_shape, xs.dtype)
        outs0 = jnp.zeros_like(xs)
        (_, outs), _ = jax.lax.scan(tick, (buf0, outs0),
                                    jnp.arange(T, dtype=jnp.int32))
        # outs is only valid on the last stage; broadcast via all_gather
        # (ppermute cannot fan out one source to many destinations)
        return jax.lax.all_gather(outs, axis)[S - 1]

    return run(stage_params, x)
