"""Pallas kernel: fused SD-RNS modular matmul (the paper's Eq. 2, end to end).

This is the first kernel that does the *whole* signed-digit residue multiply
inside one kernel body, instead of composing the per-digit Python loop in
:mod:`repro.core.sdrns` out of many small jnp ops:

* **Eq. 2 partial products** — multiplying by ``2^p`` mod ``2^n - 1 / 2^n /
  2^n + 1`` is a digit-vector *rotation*: cyclic, shift-with-zero-fill, or
  negate-on-wrap respectively.  All three are one formula here — roll the
  digit axis by ``p`` and multiply the wrapped lanes by the channel's
  ``wrap_sign`` (+1 / 0 / -1) — so a single kernel body serves every channel
  of the moduli set with the sign as a prefetched per-channel scalar.
* **Carry-free adder trees** — the ``n`` digit partial products reduce with
  the end-around two-step adder (constant depth per level, no carry chains),
  then the ``K`` per-term products reduce the same way.  Total depth is
  ``1 + ceil(log2 n) + ceil(log2 K)`` carry-free levels — the structure
  behind Table I's constant SD adder delay.

Tiling: grid ``(C, M/bm, N/bn)`` — channel and both matmul dims parallel; the
K and digit axes ride whole inside the body (digit tensors are small: the
paper's channels are n <= 21 digits, and K is pre-segmented by ops.py).

Mosaic status: the body is not TPU-legal yet — it builds 5-D digit tensors
with the digit axis (n = 7 under P21) as the lane dimension and reduces them
with strided pairwise slices, which Mosaic refuses ("Only 2D gather is
supported"), and its ``(n, bm, K, bn, n)`` partial-product stack is sized
in logical rather than tiled bytes.  Both entry points therefore run only
in the Pallas interpreter and raise :class:`NotImplementedError` when asked
for Mosaic (``interpret=False``, or ``None`` on a TPU) — ``system="sdrns"``
never silently degrades to another implementation on the chip.

Bit-exactness: the reduction structure (pairwise 0::2/1::2 trees with zero
padding on odd counts) mirrors :func:`repro.core.sdrns.modular_mul` exactly,
so the output *digit vectors* — not just the decoded values — match the
digit-level reference; tests/test_sdrns_matmul.py asserts that.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import sd
from repro.core.sdrns import WRAP_SIGNS
from repro.kernels import compat

__all__ = ["sdrns_matmul_pallas", "sdrns_matvec_pallas", "WRAP_SIGNS"]


def _rotate_pp(digits: jax.Array, p: int, ws: jax.Array) -> jax.Array:
    """Digits of ``2^p * value`` mod the channel modulus (Eq. 2).

    One formula for all three kinds: roll LSB-first digits by ``p`` and scale
    the ``p`` wrapped lanes by the runtime wrap sign.
    """
    if p == 0:
        return digits
    rolled = jnp.roll(digits, p, axis=-1)
    idx = jax.lax.broadcasted_iota(jnp.int32, digits.shape, digits.ndim - 1)
    return jnp.where(idx < p, ws * rolled, rolled).astype(jnp.int8)


def _modular_add(x: jax.Array, y: jax.Array, ws: jax.Array) -> jax.Array:
    """Carry-free SD add with the end-around transfer rotated by ``ws``.

    Same math as :func:`repro.core.sdrns.modular_add`, with the wrap sign a
    runtime scalar instead of a static kind tag.
    """
    p = x.astype(jnp.int8) + y.astype(jnp.int8)
    idx = jax.lax.broadcasted_iota(jnp.int32, p.shape, p.ndim - 1)
    prev = jnp.roll(p, 1, axis=-1)
    prev = jnp.where(idx == 0, ws * prev, prev).astype(jnp.int8)
    w, t = sd.add_interim(p, prev)
    t_in = jnp.roll(t, 1, axis=-1)
    t_in = jnp.where(idx == 0, ws * t_in, t_in).astype(jnp.int8)
    return sd.combine(w, t_in)


def _tree_reduce(pp: jax.Array, axis: int, ws: jax.Array) -> jax.Array:
    """Pairwise end-around adder tree over ``axis`` (width never grows).

    Delegates to :func:`sd.pairwise_reduce` — the exact pairing of
    ``sdrns.modular_mul``'s tree, so digit vectors stay bit-identical.
    """
    return sd.pairwise_reduce(
        pp, axis, lambda x, y: _modular_add(x, y, ws))


def _require_interpret(interpret: bool | None) -> None:
    if not compat.resolve_interpret(interpret):
        raise NotImplementedError(
            "the fused SD-RNS kernel is not Mosaic-legal (5-D digit tensors "
            "with the digit axis as lanes, strided adder-tree slices); run "
            "system='sdrns' with backend='interpret' or 'ref', or serve "
            "system='rns' on TPU")


def _kernel(ws_ref, a_ref, b_ref, out_ref, *, n: int):
    """One (channel, i, j) grid step — a full SD-RNS tile product.

    ws_ref:  (C,)            int32  wrap signs (+1/0/-1), whole array in SMEM
    a_ref:   (1, bm, K, n)   int8   SD digits of A's residues
    b_ref:   (1, K, bn, n)   int8   SD digits of B's residues
    out_ref: (1, bm, bn, n)  int8   SD digits of (A @ B) mod m_c
    """
    ws = ws_ref[pl.program_id(0)].astype(jnp.int8)
    a = a_ref[0]                                     # (bm, K, n)
    b = b_ref[0]                                     # (K, bn, n)

    # Eq. 2 partial products: PP_p[m,k,j,:] = rot(a[m,k], p) * b[k,j,p].
    # The digit select is a mux (+-rot or 0), never a real multiply.
    pps = []
    for p in range(n):
        rot = _rotate_pp(a, p, ws)                   # (bm, K, n)
        yp = b[..., p]                               # (K, bn)
        pps.append(rot[:, :, None, :] * yp[None, :, :, None])
    pp = jnp.stack(pps, axis=0)                      # (n, bm, K, bn, n)

    # digit tree -> per-(m,k,j) product digits, then K tree -> output digits.
    prod = _tree_reduce(pp, 0, ws)                   # (bm, K, bn, n)
    out_ref[0] = _tree_reduce(prod, 1, ws)           # (bm, bn, n)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "interpret"))
def sdrns_matmul_pallas(
    a_dig: jax.Array,
    b_dig: jax.Array,
    wrap_signs: jax.Array,
    *,
    bm: int,
    bn: int,
    interpret: bool | None = None,
) -> jax.Array:
    """Fused SD-RNS modular matmul over digit-encoded residue channels.

    Args:
      a_dig: (C, M, K, n) int8 SD digits (LSB first) of A's residues.
      b_dig: (C, K, N, n) int8 SD digits of B's residues.
      wrap_signs: (C,) int32 end-around signs per channel.
    Returns:
      (C, M, N, n) int8 SD digits of (A @ B) mod m_c per channel.

    M % bm == 0 and N % bn == 0 (ops.py pads).  ``interpret=None``
    auto-selects the Pallas interpreter off-TPU; Mosaic is refused (see
    the module docstring).
    """
    _require_interpret(interpret)
    C, M, K, n = a_dig.shape
    _, K2, N, n2 = b_dig.shape
    assert (K, n) == (K2, n2), (a_dig.shape, b_dig.shape)
    assert M % bm == 0 and N % bn == 0, (M, N, bm, bn)

    grid = (C, M // bm, N // bn)
    return pl.pallas_call(
        functools.partial(_kernel, n=n),
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, bm, K, n), lambda c, i, j: (c, i, 0, 0)),
            pl.BlockSpec((1, K, bn, n), lambda c, i, j: (c, 0, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bm, bn, n), lambda c, i, j: (c, i, j, 0)),
        out_shape=jax.ShapeDtypeStruct((C, M, N, n), jnp.int8),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel")),
        interpret=True,
    )(wrap_signs.astype(jnp.int32), a_dig, b_dig)


@functools.partial(jax.jit, static_argnames=("bn", "interpret"))
def sdrns_matvec_pallas(
    a_dig: jax.Array,
    b_dig: jax.Array,
    wrap_signs: jax.Array,
    *,
    bn: int,
    interpret: bool | None = None,
) -> jax.Array:
    """Decode-shaped SD-RNS modular matmul: skinny M, K-resident digit planes.

    The serving decode step multiplies a handful of token activations
    (M = batch, typically <= 8 after padding) against a resident weight's
    digit planes.  Tiling the M axis buys nothing there, so this variant
    keeps the whole (padded) M block *and* the whole K segment resident per
    grid step and walks only ``(C, N/bn)`` — a matvec-style schedule: the A
    digits load once per channel and B's K-resident planes stream through
    wide ``bn`` column tiles.  The kernel body is byte-for-byte the matmul
    body (same Eq. 2 rotations, same pairwise adder trees), so output digit
    vectors stay bit-identical to :func:`sdrns_matmul_pallas` and the
    digit-level reference.

    Args:
      a_dig: (C, M, K, n) int8 SD digits with M small (ops.py pads to 8).
      b_dig: (C, K, N, n) int8 SD digits of the resident weight.
      wrap_signs: (C,) int32 end-around signs per channel.
    Returns:
      (C, M, N, n) int8 SD digits of (A @ B) mod m_c per channel.
    Interpreter only, like :func:`sdrns_matmul_pallas`.
    """
    _require_interpret(interpret)
    C, M, K, n = a_dig.shape
    _, K2, N, n2 = b_dig.shape
    assert (K, n) == (K2, n2), (a_dig.shape, b_dig.shape)
    assert N % bn == 0, (N, bn)

    grid = (C, N // bn)
    return pl.pallas_call(
        functools.partial(_kernel, n=n),
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, M, K, n), lambda c, j: (c, 0, 0, 0)),
            pl.BlockSpec((1, K, bn, n), lambda c, j: (c, 0, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, M, bn, n), lambda c, j: (c, 0, j, 0)),
        out_shape=jax.ShapeDtypeStruct((C, M, N, n), jnp.int8),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=True,
    )(wrap_signs.astype(jnp.int32), a_dig, b_dig)
