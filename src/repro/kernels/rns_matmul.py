"""Pallas TPU kernel: C-channel RNS modular matmul with lazy reduction.

This is the compute hot-spot of the paper's technique on TPU: one *wide*
integer matmul is replaced by ``C`` independent *narrow* channel matmuls
(moduli small enough that centered residues fit int8 — MXU's native integer
path), and — the redundancy insight — **no modular reduction happens inside
the K loop**.  Centered residues bound each product by ``(m//2)^2``, so an
int32 tile accumulates ``>= 2**18`` terms before it could overflow; a single
reduce-and-center runs on the last K step.  The inner loop is therefore a pure
``dot_general`` chain: MXU-only, no elementwise mod traffic.

Tiling: grid ``(C, M/bm, N/bn, K/bk)`` with the K axis innermost/sequential
("arbitrary" semantics on TPU).  The caller picks the blocks from the call's
own shape (``numerics/runners.py::_choose_blocks``): ``bn`` and ``bk`` divide
``N`` and the K segment wherever those are multiples of 128, so weight planes
reach the kernel unpadded, and each grid step streams a weight block of
1-4 MiB (whole K where it fits), because a grid step has a fixed cost that a
64 KiB block cannot hide.  At decode (M <= 512) one M block holds every row,
so each weight byte is read once; at prefill ``bm`` grows to 512 so each step
does enough MXU work to hide its weight DMA.  :func:`vmem_bytes` is the
double-buffered footprint of one step; the chooser keeps it within
:data:`VMEM_BUDGET` and the kernel asks Mosaic for :data:`VMEM_LIMIT`, above
the v5e's 16 MiB scoped default.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import compat

__all__ = ["rns_matmul_pallas", "DEFAULT_BLOCKS", "VMEM_BUDGET", "VMEM_LIMIT",
           "vmem_bytes"]

DEFAULT_BLOCKS = (128, 128, 512)  # (bm, bn, bk)
VMEM_BUDGET = 24 * 1024 * 1024    # blocks of one grid step, double-buffered
VMEM_LIMIT = 32 * 1024 * 1024     # scoped VMEM asked of Mosaic: the budget
                                  # plus room for the compiler's own scratch


def vmem_bytes(bm: int, bn: int, bk: int, itemsize: int = 1) -> int:
    """VMEM one grid step holds: both residue operand blocks and the int32
    output block double-buffered, plus the int32 product of the step."""
    return 2 * (bm * bk + bk * bn) * itemsize + 3 * bm * bn * 4


def _kernel(m_ref, a_ref, b_ref, out_ref, *, n_k: int):
    """One (channel, i, j, k) grid step.

    m_ref:  (C,)        int32   channel moduli, whole array in SMEM
    a_ref:  (1, bm, bk) int8    centered residues of A
    b_ref:  (1, bk, bn) int8    centered residues of B
    out_ref:(1, bm, bn) int32   accumulator / final centered residues
    """
    m = m_ref[pl.program_id(0)]
    k = pl.program_id(3)

    a = a_ref[0]
    b = b_ref[0]
    # MXU path: int8 x int8 -> int32.  No mod here — lazy reduction.
    part = jax.lax.dot_general(
        a, b, (((1,), (0,)), ((), ())), preferred_element_type=jnp.int32
    )

    @pl.when(k == 0)
    def _init():
        out_ref[0] = part

    @pl.when(k > 0)
    def _accum():
        out_ref[0] = out_ref[0] + part

    # Single deferred reduction: centered remainder on the last K step.
    @pl.when(k == n_k - 1)
    def _reduce():
        acc = out_ref[0]
        r = jax.lax.rem(acc, m)           # sign of dividend; |r| < m
        r = jnp.where(r < 0, r + m, r)    # canonical [0, m)
        r = jnp.where(r > m // 2, r - m, r)  # centered (matches ModuliSet.center)
        out_ref[0] = r


@functools.partial(
    jax.jit, static_argnames=("bm", "bn", "bk", "interpret")
)
def rns_matmul_pallas(
    a_res: jax.Array,
    b_res: jax.Array,
    moduli: jax.Array,
    *,
    bm: int = DEFAULT_BLOCKS[0],
    bn: int = DEFAULT_BLOCKS[1],
    bk: int = DEFAULT_BLOCKS[2],
    interpret: bool | None = None,
) -> jax.Array:
    """Channel-wise modular matmul.

    Args:
      a_res: (C, M, K) int8 centered residues.
      b_res: (C, K, N) int8 centered residues.
      moduli: (C,) int32.
    Returns:
      (C, M, N) int32 centered residues of A @ B mod m_c.

    M, N, K must be multiples of the block sizes (ops.py pads).
    ``interpret=None`` auto-selects the Pallas interpreter off-TPU.
    """
    interpret = compat.resolve_interpret(interpret)
    C, M, K = a_res.shape
    _, _, N = b_res.shape
    assert b_res.shape == (C, K, N)
    assert M % bm == 0 and N % bn == 0 and K % bk == 0, (M, N, K, bm, bn, bk)
    n_k = K // bk

    grid = (C, M // bm, N // bn, n_k)
    return pl.pallas_call(
        functools.partial(_kernel, n_k=n_k),
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, bm, bk), lambda c, i, j, k: (c, i, k)),
            pl.BlockSpec((1, bk, bn), lambda c, i, j, k: (c, k, j)),
        ],
        out_specs=pl.BlockSpec((1, bm, bn), lambda c, i, j, k: (c, i, j)),
        out_shape=jax.ShapeDtypeStruct((C, M, N), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT,
        ),
        interpret=interpret,
    )(moduli.astype(jnp.int32), a_res, b_res)
