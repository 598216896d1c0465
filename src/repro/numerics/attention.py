"""Float attention ops behind the numerics backend registry.

The flash kernels join the matmul runners on the registry axis
(``pallas`` / ``interpret`` / ``ref`` / ``cost`` — see
``numerics/registry.py``): models dispatch by *op name* and the platform
(or an explicit override) picks the implementation.  Two ops:

* ``flash_attention`` — GQA-native tiled online-softmax over the model
  layouts ``q (B, Sq, H, hd)`` / ``k, v (B, T, Kv, hd)``; the ``ref``
  backend is the materialized-score oracle (``kernels/ref.py``).
* ``flash_decode`` — the split-KV decode schedule: KV chunks run as
  *parallel* grid steps emitting online-softmax partials, merged here by
  :func:`merge_decode_partials` (a tiny (B, n_chunks, H)-sized jnp pass).

``kv_len`` is a runtime ``(B,)`` operand on both ops — decode positions and
ragged prompts share one compiled kernel (no per-position recompiles).

Block sizes are picked here (:func:`pick_block`): the preferred MXU tiles,
shrunk to the problem so tiny test shapes do not pay for padded grids.
:func:`grid_size` is exported for the dispatch guard in
``models/attention.py`` — interpret-mode emulation pays per grid step, so
oversized grids fall back to the materialized path off-TPU.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels.flash_attn import (
    DEFAULT_BLOCKS,
    flash_attention_pallas,
    flash_decode_pallas,
    flash_paged_decode_pallas,
)
from repro.kernels.ref import gqa_attention_ref
from repro.numerics import kv_pages as _kv
from repro.numerics.registry import get_impl, register_impl, resolve_backend

__all__ = [
    "flash_attention",
    "flash_decode",
    "paged_decode",
    "paged_verify",
    "merge_decode_partials",
    "pick_block",
    "grid_size",
    "paged_grid_size",
    "set_decode_block",
]


def _round_up(v: int, k: int) -> int:
    return (v + k - 1) // k * k


def pick_block(n: int, pref: int) -> int:
    """Preferred tile size, shrunk (8-aligned) when the dim is smaller."""
    return min(pref, _round_up(max(n, 1), 8))


def grid_size(B: int, H: int, Sq: int, T: int, *,
              bq: int | None = None, bk: int | None = None) -> int:
    """Grid steps the flash call would run (the interpret-cost guard)."""
    bq = bq or pick_block(Sq, DEFAULT_BLOCKS[0])
    bk = bk or pick_block(T, DEFAULT_BLOCKS[1])
    return B * H * (-(-Sq // bq)) * (-(-T // bk))


def paged_grid_size(B: int, H: int, n_pmax: int) -> int:
    """Grid steps of the paged decode kernel (one per block-table entry)."""
    return B * H * n_pmax


_DECODE_BLOCK_OVERRIDE: int | None = None


def set_decode_block(bk: int | None) -> int | None:
    """Override the dense split-KV decode chunk size (None restores auto).

    Aligning the dense chunk boundary with the paged page boundary makes
    paged-vs-dense decode *bit*-identical even when the KV prefix spans
    multiple chunks: both schedules then emit the same set of per-chunk
    partials and run the same merge.  Returns the previous override so
    callers can restore it.
    """
    global _DECODE_BLOCK_OVERRIDE
    prev = _DECODE_BLOCK_OVERRIDE
    _DECODE_BLOCK_OVERRIDE = bk
    return prev


def merge_decode_partials(o_p: jax.Array, m_p: jax.Array,
                          l_p: jax.Array) -> jax.Array:
    """Log-sum-exp merge of split-KV partials.

    o_p: (B, n_chunks, H, hd) f32;  m_p, l_p: (B, n_chunks, H) f32.
    Returns (B, H, hd) f32.  All-masked chunks carry (o=0, m=-inf, l=0)
    and weigh out naturally (their exp(m - m_max) underflows to zero).
    """
    m_max = jnp.max(m_p, axis=1, keepdims=True)          # (B, 1, H)
    w = jnp.exp(m_p - m_max)                             # (B, n_chunks, H)
    l_tot = jnp.sum(l_p * w, axis=1)                     # (B, H)
    o = jnp.einsum("bchd,bch->bhd", o_p, w)
    return o / jnp.maximum(l_tot, 1e-30)[..., None]


# ---------------------------------------------------------------------------
# Registry impls.  Shared signatures:
#   flash_attention: (q, k, v, kv_len, causal, bq, bk) -> (B, Sq, H, hd)
#   flash_decode:    (q, k, v, kv_len, bk)             -> (B, H, hd) f32
# ---------------------------------------------------------------------------


def _attn_kernel_impl(interpret: bool):
    def run(q, k, v, kv_len, causal, bq, bk):
        return flash_attention_pallas(q, k, v, kv_len, causal=causal,
                                      bq=bq, bk=bk, interpret=interpret)
    return run


def _attn_ref_impl(q, k, v, kv_len, causal, bq, bk):
    return gqa_attention_ref(q, k, v, kv_len, causal=causal)


register_impl("flash_attention", "pallas", _attn_kernel_impl(False))
register_impl("flash_attention", "interpret", _attn_kernel_impl(True))
register_impl("flash_attention", "ref", _attn_ref_impl)
register_impl("flash_attention", "cost", _attn_ref_impl)


def _decode_kernel_impl(interpret: bool):
    def run(q, k, v, kv_len, bk):
        o_p, m_p, l_p = flash_decode_pallas(q, k, v, kv_len, bk=bk,
                                            interpret=interpret)
        return merge_decode_partials(o_p, m_p, l_p)
    return run


def _decode_ref_impl(q, k, v, kv_len, bk):
    out = gqa_attention_ref(q[:, None], k, v, kv_len, causal=False)
    return out[:, 0].astype(jnp.float32)


register_impl("flash_decode", "pallas", _decode_kernel_impl(False))
register_impl("flash_decode", "interpret", _decode_kernel_impl(True))
register_impl("flash_decode", "ref", _decode_ref_impl)
register_impl("flash_decode", "cost", _decode_ref_impl)


# flash_paged_decode: (q, k_raw, v_raw, k_scale, v_scale, fmt, syndrome,
#                      tab, kv_len, page_size) -> (out (B, H, hd) f32,
#                      syn (B,) int32 | None)
# k_raw/v_raw are the unwrapped pool leaves: (P, ps, Kv, hd) cache dtype for
# dense pages, (P, ps, 1 + r, Kv, hd/vpb) uint8 planes (+ (P, ps, Kv, 1) f32
# scales) for residue pages — lane 0 the packed info byte, lanes 1..r the
# redundant witnesses, read only under ``syndrome``.  fmt is the static
# KVFormat.

def _paged_kernel_impl(interpret: bool):
    def run(q, k_raw, v_raw, k_scale, v_scale, fmt, syndrome, tab, kv_len,
            page_size):
        moduli = fmt.mset.info_moduli if fmt.is_residue else None
        red = fmt.mset.redundant_moduli if syndrome else None
        outs = flash_paged_decode_pallas(
            q, k_raw, v_raw, tab, kv_len, page_size=page_size,
            k_scale=k_scale, v_scale=v_scale, moduli=moduli,
            red_moduli=red, interpret=interpret)
        return merge_decode_partials(*outs[:3]), (outs[3] if syndrome
                                                  else None)
    return run


def _paged_ref_impl(q, k_raw, v_raw, k_scale, v_scale, fmt, syndrome, tab,
                    kv_len, page_size):
    """Oracle: gather the page list into a dense cache, dequantize, attend."""
    B, n_pmax = tab.shape

    def dense_of(raw, scale):
        pages = raw[tab]                       # (B, n_pmax, ps, ...)
        if fmt.is_residue:
            info = pages[:, :, :, 0].astype(jnp.int32)
            vals = fmt.pack.decode(info)
            pages = vals.astype(jnp.float32) * scale[tab]
        return pages.reshape(B, n_pmax * page_size, *pages.shape[3:])

    k = dense_of(k_raw, k_scale)
    v = dense_of(v_raw, v_scale)
    out = gqa_attention_ref(q[:, None], k, v, kv_len, causal=False)
    syn = None
    if syndrome:
        syn = (_ref_syndrome(k_raw, fmt, tab, kv_len, page_size)
               + _ref_syndrome(v_raw, fmt, tab, kv_len, page_size))
    return out[:, 0].astype(jnp.float32), syn


def _ref_syndrome(raw, fmt, tab, kv_len, page_size):
    """Mirror of the kernel's witness check: per-request mismatch count."""
    B, n_pmax = tab.shape
    pages = raw[tab].astype(jnp.int32)          # (B, np, ps, 1+r, Kv, hd)
    vals = fmt.pack.decode(pages[:, :, :, 0])           # (B, np, ps, Kv, hd)
    mism = jnp.zeros(vals.shape, jnp.bool_)
    for jw, m in enumerate(fmt.mset.redundant_moduli):
        mism = mism | (jnp.remainder(
            pages[:, :, :, 1 + jw] - jnp.remainder(vals, m), m) != 0)
    rows = (jnp.arange(n_pmax * page_size)
            .reshape(1, n_pmax, page_size, 1, 1))
    valid = rows < kv_len.reshape(B, 1, 1, 1, 1)
    return jnp.sum(mism & valid, axis=(1, 2, 3, 4)).astype(jnp.int32)


register_impl("flash_paged_decode", "pallas", _paged_kernel_impl(False))
register_impl("flash_paged_decode", "interpret", _paged_kernel_impl(True))
register_impl("flash_paged_decode", "ref", _paged_ref_impl)
register_impl("flash_paged_decode", "cost", _paged_ref_impl)


# ---------------------------------------------------------------------------
# Public dispatchers.
# ---------------------------------------------------------------------------


def _channel_ctx_plan(B: int):
    """``(mesh, dp_names)`` under a ``channel_shard`` ShardCtx, else None.

    Under the channel-parallel layout the residue matmuls run as shard_map
    bodies (``runners._channel_mapped``); attention is float-domain and
    carries no moduli channels, so the dispatchers wrap the flash kernels
    in the *same* mesh context — batch over ``dp``, everything else
    replicated over the tensor axes.  Each shard runs the unchanged kernel
    body with **zero collectives** (the output is already replicated over
    tp), so a whole residue-resident decode step lowers under one mesh
    and the only cross-device traffic left is the partial-CRT psum per
    residue matmul.  Bit-identical: the kernel body per shard is the
    single-device body.  ``dp_names`` is ``()`` when ``B`` is not
    divisible (the batch then rides replicated too).
    """
    from repro.parallel.sharding import get_shard_ctx

    ctx = get_shard_ctx()
    if ctx is None or not ctx.channel_shard:
        return None
    dp = ctx.resolve("dp")
    if not dp or B % ctx.axis_size(dp):
        dp = ()
    return (ctx.mesh, dp)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    kv_len: jax.Array | int | None = None,
    backend: str | None = None,
    bq: int | None = None,
    bk: int | None = None,
) -> jax.Array:
    """Exact attention, no materialized scores.  See module docstring.

    q: (B, Sq, H, hd);  k, v: (B, T, Kv, hd), H % Kv == 0.
    kv_len: runtime valid-prefix length — scalar or (B,) int32 (None = T).
    Returns (B, Sq, H, hd) in q's dtype.
    """
    B, Sq, H, hd = q.shape
    T = k.shape[1]
    bq = bq or pick_block(Sq, DEFAULT_BLOCKS[0])
    bk = bk or pick_block(T, DEFAULT_BLOCKS[1])
    if kv_len is not None:
        kv_len = jnp.broadcast_to(jnp.asarray(kv_len, jnp.int32), (B,))
    impl = get_impl("flash_attention", resolve_backend(backend))
    plan = _channel_ctx_plan(B)
    if plan is None:
        return impl(q, k, v, kv_len, causal, bq, bk)
    mesh, dp = plan
    bspec = P(dp or None, None, None, None)
    args = (q, k, v) + (() if kv_len is None else (kv_len,))
    in_specs = (bspec, bspec, bspec) + (
        () if kv_len is None else (P(dp or None),))

    def body(q_, k_, v_, *rest):
        return impl(q_, k_, v_, rest[0] if rest else None, causal, bq, bk)

    return jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                         out_specs=bspec, check_vma=False)(*args)


def flash_decode(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    kv_len: jax.Array | int,
    backend: str | None = None,
    bk: int | None = None,
) -> jax.Array:
    """One-token split-KV attention over a (possibly padded) KV cache.

    q: (B, H, hd);  k, v: (B, T, Kv, hd);  kv_len: scalar or (B,) int32.
    Returns (B, H, hd) f32 (callers cast at the boundary).
    """
    B, H, hd = q.shape
    T = k.shape[1]
    bk = bk or _DECODE_BLOCK_OVERRIDE or pick_block(T, DEFAULT_BLOCKS[1])
    kv_len = jnp.broadcast_to(jnp.asarray(kv_len, jnp.int32), (B,))
    impl = get_impl("flash_decode", resolve_backend(backend))
    plan = _channel_ctx_plan(B)
    if plan is None:
        return impl(q, k, v, kv_len, bk)
    mesh, dp = plan
    kvspec = P(dp or None, None, None, None)
    qspec = P(dp or None, None, None)

    def body(q_, k_, v_, len_):
        return impl(q_, k_, v_, len_, bk)

    return jax.shard_map(
        body, mesh=mesh, in_specs=(qspec, kvspec, kvspec, P(dp or None)),
        out_specs=qspec, check_vma=False)(q, k, v, kv_len)


def paged_decode(
    q: jax.Array,
    kv_layer: "_kv.PagedKV",
    block_tab: jax.Array,
    kv_len: jax.Array,
    *,
    page_size: int,
    backend: str | None = None,
    syndrome: bool = False,
) -> jax.Array | tuple[jax.Array, jax.Array]:
    """One-token split-KV attention over one layer's *paged* cache.

    The request's page list (``block_tab`` row) is walked by the kernel's
    scalar-prefetch index map — the chunk boundary IS the page boundary, and
    residue pages dequantize inside the KV load.

    q: (B, H, hd);  kv_layer: per-layer :class:`~repro.numerics.kv_pages.
    PagedKV` (no leading L axis);  block_tab: (B, n_pmax) int32;  kv_len:
    scalar or (B,) int32 logical prefix length.  Returns (B, H, hd) f32.

    With ``syndrome=True`` (redundant residue formats only) the same pass
    also checks every valid KV element against its stored witness residues
    while the planes are in VMEM and returns ``(out, syn)`` where ``syn``
    is the (B,) int32 count of mismatching elements — the in-kernel
    replacement for a separate ``verify_pages`` sweep on the hot path.
    """
    B = q.shape[0]
    fmt = _kv.kv_format_of(kv_layer)
    if syndrome and not (fmt.is_residue and fmt.redundant):
        raise ValueError(
            "syndrome=True requires a redundant residue KV format "
            f"(e.g. 'rns8r'); got {fmt.name!r}")
    if fmt.is_residue:
        # the whole planes leaf goes to the kernel: lane 0 is the packed
        # info byte, redundant witness lanes are read only under syndrome
        k_raw, v_raw = kv_layer.k.planes, kv_layer.v.planes
        k_scale, v_scale = kv_layer.k.scale, kv_layer.v.scale
    else:
        k_raw, v_raw = kv_layer.k, kv_layer.v
        k_scale = v_scale = None
    block_tab = jnp.asarray(block_tab, jnp.int32)
    kv_len = jnp.broadcast_to(jnp.asarray(kv_len, jnp.int32), (B,))
    impl = get_impl("flash_paged_decode", resolve_backend(backend))
    out, syn = impl(q, k_raw, v_raw, k_scale, v_scale, fmt, syndrome,
                    block_tab, kv_len, page_size)
    return (out, syn) if syndrome else out


def paged_verify(
    q: jax.Array,
    kv_layer: "_kv.PagedKV",
    block_tab: jax.Array,
    kv_len: jax.Array,
    *,
    page_size: int,
    backend: str | None = None,
) -> jax.Array:
    """Multi-token split-KV attention for the speculative verify step.

    The spec loop verifies a block of ``V = k + 1`` tokens per slot in one
    batched target step; each verify row attends causally over its own
    prefix, which is exactly :func:`paged_decode` with a *per-row* logical
    length.  The V axis is folded into the kernel's batch grid axis — row
    ``(b, j)`` becomes batch row ``b * V + j`` with its slot's block table
    repeated and ``kv_len[b, j]`` advancing by one per in-block position —
    so the same compiled flash kernel serves 1-token decode and k-token
    verify, and each folded row's online-softmax is bit-identical to the
    single-token dispatch it replaces (pinned by tests/test_spec_decode.py).

    q: (B, V, H, hd);  block_tab: (B, n_pmax);  kv_len: (B, V) int32
    per-row logical prefix lengths.  Returns (B, V, H, hd) f32.
    """
    B, V, H, hd = q.shape
    q2 = q.reshape(B * V, H, hd)
    tab2 = jnp.repeat(jnp.asarray(block_tab, jnp.int32), V, axis=0)
    len2 = jnp.asarray(kv_len, jnp.int32).reshape(B * V)
    out = paged_decode(q2, kv_layer, tab2, len2, page_size=page_size,
                       backend=backend)
    return out.reshape(B, V, H, hd)
