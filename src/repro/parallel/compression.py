"""Compressed gradient all-reduce — the cross-pod bandwidth optimization
(DESIGN.md §5).

An all-reduce is a reduce-scatter followed by an all-gather.  The reduce
phase must stay exact (sums of quantized values would compound error), but
the *gather* phase broadcasts finished values — safe to quantize.  So:

  1. ``psum_scatter`` the f32 gradients over the sync axes (exact;
     wire = X·(n-1)/n f32 bytes);
  2. each shard owner quantizes its shard to int8 with a shared symmetric
     scale and keeps the quantization residual as **error feedback** (added
     into the next step's gradient — the EF-SGD argument makes the scheme
     unbiased over time, validated in tests/test_compression.py);
  3. ``all_gather`` the int8 shards (wire = X/4·(n-1)/n bytes — the 4x
     phase saving) and rescale.

End-to-end wire vs f32 all-reduce: (1 + 1/4)/2 = 1.6x fewer bytes; vs bf16
all-reduce with an f32-precision reduce phase: comparable bytes but exact
accumulation.  Each leaf's leading dim must divide the axis size to scatter
— leaves that cannot fall back to a plain f32 psum (recorded per leaf).
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


__all__ = ["init_error_state", "compressed_grad_mean", "make_compressed_mean"]


def init_error_state(grads: Any) -> Any:
    return jax.tree_util.tree_map(
        lambda g: jnp.zeros(g.shape, jnp.float32), grads)


def _axis_prod(axes: tuple[str, ...]) -> int:
    n = 1
    for a in axes:
        n *= jax.lax.axis_size(a)
    return n


def _linear_axis_index(axes: tuple[str, ...]) -> jax.Array:
    idx = jnp.int32(0)
    for a in axes:
        idx = idx * jax.lax.axis_size(a) + jax.lax.axis_index(a)
    return idx


def _quantize_mean(x: jax.Array, err: jax.Array, axes: tuple[str, ...]):
    """Inside shard_map: mean of ``x`` over ``axes`` with an int8 gather
    phase + error feedback.  Returns (mean, new_err)."""
    n = _axis_prod(axes)
    xf = x.astype(jnp.float32) + err
    if n == 1:
        return xf.astype(x.dtype), jnp.zeros_like(xf)
    lead = x.shape[0] if x.ndim else 0
    if x.ndim == 0 or lead % n != 0:
        # unscatterable leaf (scalars, tiny vectors): exact f32 fallback
        mean = jax.lax.psum(xf, axes) / n
        return mean.astype(x.dtype), jnp.zeros_like(xf)

    # 1. exact reduce-scatter of the sum
    shard = jax.lax.psum_scatter(xf, axes, scatter_dimension=0,
                                 tiled=True) / n        # (lead/n, ...)
    # 2. shared scale + int8 quantization of the owned shard
    gmax = jax.lax.pmax(jnp.max(jnp.abs(shard)), axes)
    scale = jnp.maximum(gmax, 1e-12) / 127.0
    q = jnp.clip(jnp.round(shard / scale), -127, 127).astype(jnp.int8)
    resid = shard - q.astype(jnp.float32) * scale
    # 3. int8 all-gather (the compressed wire) + rescale
    gathered = jax.lax.all_gather(q, axes, axis=0, tiled=True)
    mean = gathered.astype(jnp.float32) * scale
    # error feedback: the owner of each shard re-injects its residual next
    # step (n * resid because the next reduce averages it over n again)
    shard_len = lead // n
    offset = _linear_axis_index(axes) * shard_len
    err_new = jax.lax.dynamic_update_slice_in_dim(
        jnp.zeros_like(xf), n * resid, offset, axis=0)
    return mean.astype(x.dtype), err_new


def compressed_grad_mean(grads: Any, err_state: Any,
                         axes: tuple[str, ...]) -> tuple[Any, Any]:
    """Per-leaf compressed mean over ``axes`` (call inside shard_map)."""
    out = jax.tree_util.tree_map(
        lambda g, e: _quantize_mean(g, e, axes), grads, err_state)
    means = jax.tree_util.tree_map(lambda t: t[0], out,
                                   is_leaf=lambda t: isinstance(t, tuple))
    errs = jax.tree_util.tree_map(lambda t: t[1], out,
                                  is_leaf=lambda t: isinstance(t, tuple))
    return means, errs


def make_compressed_mean(mesh: Mesh, axes: tuple[str, ...]):
    """jit-able f(grads, err) -> (mean_grads, err') over replicated leaves.

    Leaves are replicated over ``axes`` within each shard-map instance and
    differ across instances (the DP gradient situation).
    """

    def fn(grads, err):
        spec_in = jax.tree_util.tree_map(lambda _: P(*[None] * _.ndim), grads)

        @functools.partial(
            jax.shard_map, mesh=mesh,
            in_specs=(spec_in, spec_in), out_specs=(spec_in, spec_in),
            check_vma=False)
        def inner(g, e):
            return compressed_grad_mean(g, e, axes)

        return inner(grads, err)

    return fn
