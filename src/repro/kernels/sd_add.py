"""Pallas TPU kernel: batched carry-free SD-RNS modular addition.

The paper's constant-time adder as a VPU-shaped kernel: each (batch, digit)
lane computes the two-step rule (interim sum + transfer with rotated
end-around lookahead) in one fused elementwise pass — there is no loop over
digits, which *is* the carry-free property in dataflow form.

Layout: digits LSB-first on the last axis (multiple-of-128 lanes after the
ops.py padding), batch tiled on the second-to-last axis.  int8 in / int8 out.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import compat

__all__ = ["sd_add_pallas"]

_WRAP = {"pow2m1": 1, "pow2": 0, "pow2p1": -1, "plain": 0}


def _kernel(x_ref, y_ref, out_ref, *, n: int, wrap_sign: int,
            kind_is_modular: bool):
    """x,y,out: (bb, nd) int8 digit blocks; digits beyond n are zero pad."""
    x = x_ref[...].astype(jnp.int8)
    y = y_ref[...].astype(jnp.int8)
    p = x + y
    idx = jax.lax.broadcasted_iota(jnp.int32, p.shape, dimension=p.ndim - 1)

    # lookahead prev_i = p_{i-1}; position 0 sees wrap_sign * p_{n-1}
    p_shift = jnp.roll(p, 1, axis=-1)
    top = jnp.roll(p, -(n - 1), axis=-1)  # broadcasts p_{n-1} into lane 0
    prev = jnp.where(idx == 0, jnp.int8(wrap_sign) * top, p_shift)

    prev_nonneg = prev >= 0
    w = jnp.select(
        [p >= 2, p == 1, p == 0, p == -1],
        [p - 2,
         jnp.where(prev_nonneg, jnp.int8(-1), jnp.int8(1)),
         jnp.zeros_like(p),
         jnp.where(prev_nonneg, jnp.int8(-1), jnp.int8(1))],
        default=p + 2,
    ).astype(jnp.int8)
    t = jnp.select(
        [p >= 2, p == 1, p == 0, p == -1],
        [jnp.ones_like(p),
         jnp.where(prev_nonneg, jnp.int8(1), jnp.int8(0)),
         jnp.zeros_like(p),
         jnp.where(prev_nonneg, jnp.int8(0), jnp.int8(-1))],
        default=-jnp.ones_like(p),
    ).astype(jnp.int8)

    t_shift = jnp.roll(t, 1, axis=-1)
    t_top = jnp.roll(t, -(n - 1), axis=-1)
    t_in = jnp.where(idx == 0, jnp.int8(wrap_sign) * t_top, t_shift)
    # zero the pad lanes so the block stays a clean digit vector; a "plain"
    # (non-modular) add keeps its transfer-out as digit n instead of wrapping.
    live = n if kind_is_modular else n + 1
    s = jnp.where(idx < live, (w + t_in).astype(jnp.int8), jnp.int8(0))
    out_ref[...] = s


@functools.partial(jax.jit, static_argnames=("kind", "n", "bb", "interpret"))
def sd_add_pallas(
    x: jax.Array,
    y: jax.Array,
    *,
    kind: str,
    n: int,
    bb: int = 256,
    interpret: bool | None = None,
) -> jax.Array:
    """Carry-free modular SD addition.

    Args:
      x, y: (B, nd) int8 digit tensors, LSB-first, digits >= n zero;
            B % bb == 0 and nd % 128 == 0 (ops.py pads).
      kind: "pow2m1" | "pow2" | "pow2p1" (modulus family) | "plain".
      n: live digit width (modulus = 2**n ± 1 / 2**n).
    Returns:
      (B, nd) int8 digits of the modular sum, digits in {-1, 0, 1}.
    """
    interpret = compat.resolve_interpret(interpret)
    B, nd = x.shape
    assert y.shape == (B, nd)
    assert B % bb == 0, (B, bb)
    wrap_sign = _WRAP[kind]
    return pl.pallas_call(
        functools.partial(_kernel, n=n, wrap_sign=wrap_sign,
                          kind_is_modular=(kind != "plain")),
        grid=(B // bb,),
        in_specs=[
            pl.BlockSpec((bb, nd), lambda i: (i, 0)),
            pl.BlockSpec((bb, nd), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((bb, nd), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, nd), jnp.int8),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(x, y)
