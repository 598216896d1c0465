"""Grouped-query attention with qk-norm, chunked long-context path, KV-cache
prefill/decode — parameterized over the arithmetic backend via
``models.linear.dense``, over the mesh via ``parallel.sharding.constrain``,
and over the *attention kernel implementation* via the numerics registry
(``repro.numerics.attention``: flash / split-KV Pallas kernels vs the
materialized-score reference).

All four projection weights (wq/wk/wv/wo) may arrive residue-resident
(repro/quant/residency.py): ``linear.dense`` detects the prepared form, so
the decode step's projections run conversion-free against precomputed digit
planes — nothing here changes shape-wise, the prepared leaves just carry
the extra channel/digit axes behind the same dict keys.

Kernel dispatch (see DESIGN.md §10):
* ``prefill_attention`` / ``decode_attention`` route through the flash
  kernels by default — prefill through the GQA-native tiled online-softmax
  kernel (no (B, H, Sq, T) score buffer in HBM), decode through the
  flash-decoding split-KV schedule with ``kv_len = pos + 1`` as a *runtime*
  operand (one compiled kernel for every decode position).
* Under an installed :class:`~repro.parallel.sharding.ShardCtx` both fall
  back to the materialized path below: its ``constrain`` annotations encode
  the TP/split-KV mesh layouts (a ``pallas_call`` would not partition), so
  the dry-run cells lower exactly as before.
* ``set_attn_impl`` pins the implementation globally ("ref" forces the
  materialized path everywhere; "pallas"/"interpret" additionally opt the
  full-sequence ``attention()`` entry point into the kernel — inference
  only, the kernels define no VJP).

Layout decisions (see DESIGN.md §5):
* KV is stored *ungrouped* in the cache ((B, T, n_kv, hd)).  The flash
  kernels map query head h onto KV head h // (H // n_kv) in their BlockSpec
  index maps; the materialized fallback computes a grouped einsum over a
  reshaped (n_kv, group) head axis — the repeated-to-H KV copy that used to
  be materialized every decode step no longer exists on either path.
* Long sequences on the fallback use an exact scan over query chunks so
  peak score memory is (B, H, Q_CHUNK, T); the flash path needs no chunking
  (score tiles live in VMEM).
* Decode on the fallback supports sequence-sharded caches: the softmax
  reductions over the T axis become all-reduces under SPMD, which is the
  TPU analogue of flash-decoding's split-KV scheme — single-device decode
  runs the actual split-KV kernel.
"""
from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from repro.models import linear
from repro.models.layers import rmsnorm, rope
from repro.numerics import attention as nxattn
from repro.numerics import kv_pages as nxkv
from repro.numerics.registry import resolve_backend
from repro.parallel.sharding import constrain, constrain_any, get_shard_ctx

__all__ = ["init_attention", "attention", "prefill_attention",
           "decode_attention", "paged_decode_attention", "KVCache",
           "init_kv_cache", "set_attn_impl"]

CHUNK_THRESHOLD = 8192   # switch to scan-over-query-chunks above this S
Q_CHUNK = 1024

# Attention-impl override: None = auto (flash via the platform-selected
# registry backend on prefill/decode; materialized path under a mesh and
# for full-sequence attention()).  "ref" pins the materialized path
# everywhere; "pallas"/"interpret" force the kernels (attention() included).
_IMPL_OVERRIDE: str | None = None

# Interpret-mode emulation executes the kernel body per grid step — tiny
# test shapes are fine, but oversized auto-dispatched grids would crawl on
# CPU, so they fall back to the materialized path unless forced.
_INTERPRET_GRID_CAP = 4096


def set_attn_impl(impl: str | None) -> str | None:
    """Pin the attention kernel implementation; returns the previous value.

    ``None`` = auto (flash on the serving paths, registry backend by
    platform); ``"ref"`` = materialized-score path everywhere;
    ``"pallas"`` / ``"interpret"`` = force the flash kernels, including for
    full-sequence ``attention()`` (inference only — no VJP).
    """
    global _IMPL_OVERRIDE
    if impl not in (None, "pallas", "interpret", "ref", "cost"):
        raise ValueError(f"unknown attention impl {impl!r}")
    prev = _IMPL_OVERRIDE
    _IMPL_OVERRIDE = impl
    return prev


def _flash_backend(B: int, H: int, Sq: int, T: int) -> str | None:
    """Registry backend for the flash path, or None -> materialized path.

    Column/TP mesh traces materialize (their ``constrain`` annotations
    encode the TP/split-KV layouts).  The ``channel_shard`` layout keeps
    the flash path: attention is float-domain and replicated over the
    tensor axes there, and the ``numerics/attention.py`` dispatchers wrap
    the kernels in the same shard_map mesh context as the residue matmuls
    — so a whole residue-resident decode step lowers under one mesh with
    only the partial-CRT psums as collectives.  "ref"/"cost" impls mean
    materialized; auto interpret dispatch respects
    :data:`_INTERPRET_GRID_CAP`.
    """
    ctx = get_shard_ctx()
    if ctx is not None and not ctx.channel_shard:
        return None
    backend = resolve_backend(_IMPL_OVERRIDE)
    if backend in ("ref", "cost"):
        return None
    if (backend == "interpret" and _IMPL_OVERRIDE is None
            and nxattn.grid_size(B, H, Sq, T) > _INTERPRET_GRID_CAP):
        return None
    return backend


def init_attention(key: jax.Array, d_model: int, n_heads: int, n_kv: int,
                   head_dim: int, *, qk_norm: bool = False,
                   dtype=jnp.float32) -> dict[str, Any]:
    ks = jax.random.split(key, 4)
    p = {
        "wq": linear.init_dense(ks[0], d_model, n_heads * head_dim, dtype),
        "wk": linear.init_dense(ks[1], d_model, n_kv * head_dim, dtype),
        "wv": linear.init_dense(ks[2], d_model, n_kv * head_dim, dtype),
        "wo": linear.init_dense(ks[3], n_heads * head_dim, d_model, dtype),
    }
    if qk_norm:
        p["q_norm"] = {"scale": jnp.ones((head_dim,), jnp.float32)}
        p["k_norm"] = {"scale": jnp.ones((head_dim,), jnp.float32)}
    return p


class KVCache(NamedTuple):
    k: jax.Array  # (B, S_max, n_kv, hd)
    v: jax.Array  # (B, S_max, n_kv, hd)


def init_kv_cache(batch: int, s_max: int, n_kv: int, head_dim: int,
                  dtype=jnp.bfloat16) -> KVCache:
    shape = (batch, s_max, n_kv, head_dim)
    return KVCache(jnp.zeros(shape, dtype), jnp.zeros(shape, dtype))


def _project_qkv(params, x, *, n_heads, n_kv, head_dim, qk_norm, positions,
                 rope_theta, dense_kw, apply_rope=True):
    B, S, _ = x.shape
    with jax.named_scope("attn.qkv"):
        q = linear.dense(params["wq"], x, **dense_kw).reshape(
            B, S, n_heads, head_dim)
        k = linear.dense(params["wk"], x, **dense_kw).reshape(
            B, S, n_kv, head_dim)
        v = linear.dense(params["wv"], x, **dense_kw).reshape(
            B, S, n_kv, head_dim)
        if qk_norm:
            q = rmsnorm(params["q_norm"], q)
            k = rmsnorm(params["k_norm"], k)
    if apply_rope:
        with jax.named_scope("attn.rope"):
            q = rope(q, positions, theta=rope_theta)
            k = rope(k, positions, theta=rope_theta)
    q = constrain(q, "dp", None, "tp", None)
    return q, k, v


def _core(q, k, v, *, causal: bool, q_pos, kv_pos, kv_mask=None,
          cache_mode: bool = False):
    """q: (B, Sq, H, hd); k, v: (B, T, n_kv, hd).  Exact softmax attention
    with *materialized* scores — the mesh/ref fallback of the flash path.

    Grouped-query heads run as a grouped einsum over a reshaped
    (n_kv, group) head axis — the KV tensors are never repeated to H heads
    (the old ``jnp.repeat`` materialized a full H-headed copy of the KV
    cache on every decode step).  Scores still carry a single merged head
    dim (reshape, not copy) so they shard cleanly over the tensor axis for
    every assigned kv_heads value.

    ``cache_mode``: k/v come from a *sequence-sharded* KV cache (decode) —
    keep T sharded over tp and let the softmax reductions all-reduce (the
    SPMD form of flash-decoding's split-KV).  Otherwise prefer heads over
    tp, falling back to the query-chunk dim when heads do not divide.
    """
    B, Sq, H, hd = q.shape
    T, Kv = k.shape[1], k.shape[2]
    G = H // Kv
    if cache_mode:
        k = constrain(k, "dp", "tp", None, None)
        v = constrain(v, "dp", "tp", None, None)
    else:
        k = constrain_any(k, ("dp", None, "tp", None),
                          ("dp", "tp", None, None))
        v = constrain_any(v, ("dp", None, "tp", None),
                          ("dp", "tp", None, None))
    qg = q.reshape(B, Sq, Kv, G, hd)
    scores = jnp.einsum("bqkgd,btkd->bkgqt", qg, k.astype(q.dtype),
                        preferred_element_type=jnp.float32)
    scores = scores.reshape(B, H, Sq, T)
    if cache_mode:
        scores = constrain(scores, "dp", None, None, "tp")
    else:
        scores = constrain_any(scores,
                               ("dp", "tp", None, None),
                               ("dp", None, "tp", None),
                               ("dp", None, None, "tp"))
    scores = scores / jnp.sqrt(jnp.float32(hd))
    mask = None
    if causal:
        mask = kv_pos[None, :] <= q_pos[:, None]          # (Sq, T)
        mask = mask[None, None]
    if kv_mask is not None:                               # (B, T) valid keys
        km = kv_mask[:, None, None, :]
        mask = km if mask is None else (mask & km)
    if mask is not None:
        scores = jnp.where(mask, scores, jnp.float32(-1e30))
    probs = jax.nn.softmax(scores, axis=-1)
    # P.V in f32 like the flash kernels: rounding P to a bf16 cache dtype
    # here but not there made the mesh and single-device steps disagree
    pg = probs.reshape(B, Kv, G, Sq, T)
    out = jnp.einsum("bkgqt,btkd->bqkgd", pg, v.astype(jnp.float32))
    out = out.reshape(B, Sq, H, hd).astype(q.dtype)
    if not cache_mode:
        out = constrain_any(out, ("dp", None, "tp", None),
                            ("dp", "tp", None, None))
    return out.reshape(B, Sq, H * hd)


def _chunked(q, k, v, *, causal, pos1d, n_heads, head_dim):
    """Exact attention via scan over Q_CHUNK query blocks (long prefill)."""
    B, S = q.shape[0], q.shape[1]
    n_chunks = S // Q_CHUNK
    qc = q.reshape(B, n_chunks, Q_CHUNK, n_heads, head_dim).swapaxes(0, 1)
    pc = pos1d.reshape(n_chunks, Q_CHUNK)

    def body(_, inp):
        qb, pb = inp
        ob = _core(qb, k, v, causal=causal, q_pos=pb, kv_pos=pos1d)
        return None, ob

    _, outs = jax.lax.scan(body, None, (qc, pc))
    return outs.swapaxes(0, 1).reshape(B, S, n_heads * head_dim)


def _full_seq(q, k, v, *, causal, pos1d, n_heads, head_dim,
              flash_ok: bool = True):
    """Full-sequence attention: flash kernel when eligible, else the
    materialized `_core`/`_chunked` fallback.  q rows are assumed to sit at
    positions 0..Sq-1 against KV rows 0..T-1 on the flash path (true for
    every in-repo caller; callers with exotic position maps pass
    ``flash_ok=False``)."""
    B, S = q.shape[0], q.shape[1]
    T = k.shape[1]
    backend = _flash_backend(B, n_heads, S, T) if flash_ok else None
    if backend is not None:
        out = nxattn.flash_attention(q, k.astype(q.dtype), v.astype(q.dtype),
                                     causal=causal, backend=backend)
        return out.reshape(B, S, n_heads * head_dim)
    kv_pos = jnp.arange(T, dtype=jnp.int32)
    if S <= CHUNK_THRESHOLD or S % Q_CHUNK != 0:
        return _core(q, k, v, causal=causal, q_pos=pos1d, kv_pos=kv_pos)
    return _chunked(q, k, v, causal=causal, pos1d=pos1d,
                    n_heads=n_heads, head_dim=head_dim)


def attention(
    params: dict[str, Any],
    x: jax.Array,
    *,
    n_heads: int,
    n_kv: int,
    head_dim: int,
    causal: bool = True,
    qk_norm: bool = False,
    rope_theta: float = 1e4,
    positions: jax.Array | None = None,
    dense_kw: dict[str, Any] | None = None,
    apply_rope: bool = True,
    kv_override: tuple[jax.Array, jax.Array] | None = None,
) -> jax.Array:
    """Self-attention over a full sequence (training / encoder / prefill).

    ``kv_override`` supplies external (k, v) for cross-attention — projections
    for them are the caller's job (see models/encdec.py).

    Differentiable by default: the flash kernels (no VJP) are used here only
    under an explicit ``set_attn_impl("pallas"/"interpret")`` opt-in.
    """
    dense_kw = dense_kw or {}
    B, S, _ = x.shape
    if positions is None:
        positions = jnp.arange(S, dtype=jnp.int32)
    q, k, v = _project_qkv(params, x, n_heads=n_heads, n_kv=n_kv,
                           head_dim=head_dim, qk_norm=qk_norm,
                           positions=positions, rope_theta=rope_theta,
                           dense_kw=dense_kw, apply_rope=apply_rope)
    if kv_override is not None:
        k, v = kv_override
    pos1d = positions if positions.ndim == 1 else positions[0]
    # training path: kernels only on explicit opt-in (they define no VJP)
    flash_ok = _IMPL_OVERRIDE in ("pallas", "interpret")
    out = _full_seq(q, k, v, causal=causal, pos1d=pos1d, n_heads=n_heads,
                    head_dim=head_dim, flash_ok=flash_ok)
    return linear.dense(params["wo"], out, **dense_kw)


def prefill_attention(params, x, s_max: int, *, cache_dtype=jnp.bfloat16,
                      **kw):
    """Like ``attention`` but also *produces* this layer's KV cache slice,
    zero-padded to ``s_max`` positions.  Building the cache from the scan
    outputs (rather than updating a zero-initialized argument) keeps exactly
    one cache buffer live — the xs/ys double-buffer was the dominant memory
    term of the 32k prefill cells.

    Inference-only, so the flash kernel is the default compute path (no
    (B, H, S, S) score buffer); the materialized fallback runs under a mesh
    or a ``set_attn_impl("ref")`` pin.
    """
    dense_kw = kw.get("dense_kw") or {}
    B, S, _ = x.shape
    positions = jnp.arange(S, dtype=jnp.int32)
    n_heads, n_kv, head_dim = kw["n_heads"], kw["n_kv"], kw["head_dim"]
    q, k, v = _project_qkv(
        params, x, n_heads=n_heads, n_kv=n_kv, head_dim=head_dim,
        qk_norm=kw.get("qk_norm", False), positions=positions,
        rope_theta=kw.get("rope_theta", 1e4), dense_kw=dense_kw,
        apply_rope=kw.get("apply_rope", True),
    )
    with jax.named_scope("kv.layer"):
        pad = [(0, 0), (0, s_max - S), (0, 0), (0, 0)]
        cache = KVCache(jnp.pad(k.astype(cache_dtype), pad),
                        jnp.pad(v.astype(cache_dtype), pad))
    causal = kw.get("causal", True)
    with jax.named_scope("attn.core"):
        out = _full_seq(q, k, v, causal=causal, pos1d=positions,
                        n_heads=n_heads, head_dim=head_dim)
    with jax.named_scope("attn.out"):
        return linear.dense(params["wo"], out, **dense_kw), cache


def decode_attention(
    params: dict[str, Any],
    x: jax.Array,
    cache: KVCache,
    pos: jax.Array,
    *,
    n_heads: int,
    n_kv: int,
    head_dim: int,
    qk_norm: bool = False,
    rope_theta: float = 1e4,
    dense_kw: dict[str, Any] | None = None,
    apply_rope: bool = True,
) -> tuple[jax.Array, KVCache]:
    """One decode step.  x: (B, 1, D); pos: scalar int32 (uniform batch).

    Single-device decode runs the flash-decoding split-KV kernel over the
    ungrouped cache with ``kv_len = pos + 1`` as a runtime operand — no
    repeated KV copy, no (B, H, 1, T) score buffer, no recompile per
    position.  Under a mesh the materialized ``cache_mode`` path keeps the
    sequence-sharded layout (softmax reductions all-reduce over tp).
    """
    dense_kw = dense_kw or {}
    B = x.shape[0]
    positions = jnp.full((B, 1), pos, jnp.int32)
    q, k, v = _project_qkv(params, x, n_heads=n_heads, n_kv=n_kv,
                           head_dim=head_dim, qk_norm=qk_norm,
                           positions=positions, rope_theta=rope_theta,
                           dense_kw=dense_kw, apply_rope=apply_rope)
    cache = KVCache(
        jax.lax.dynamic_update_slice(cache.k, k.astype(cache.k.dtype),
                                     (0, pos, 0, 0)),
        jax.lax.dynamic_update_slice(cache.v, v.astype(cache.v.dtype),
                                     (0, pos, 0, 0)),
    )
    T = cache.k.shape[1]
    backend = _flash_backend(B, n_heads, 1, T)
    if backend is not None:
        o = nxattn.flash_decode(q[:, 0], cache.k, cache.v, kv_len=pos + 1,
                                backend=backend)
        out = o.astype(q.dtype).reshape(B, 1, n_heads * head_dim)
    else:
        kv_pos = jnp.arange(T, dtype=jnp.int32)
        kv_mask = (kv_pos <= pos)[None, :].astype(bool)
        kv_mask = jnp.broadcast_to(kv_mask, (B, T))
        out = _core(q, cache.k, cache.v, causal=False,
                    q_pos=jnp.full((1,), pos, jnp.int32), kv_pos=kv_pos,
                    kv_mask=kv_mask, cache_mode=True)
    return linear.dense(params["wo"], out, **dense_kw), cache


def _paged_backend(B: int, H: int, n_pmax: int) -> str:
    """Registry backend for the paged decode op (always the registry — the
    "ref" impl gathers the page list into a dense cache and materializes, so
    there is no separate `_core` fallback to route to)."""
    if get_shard_ctx() is not None:
        return "ref"   # engines gate paged off under a mesh; be safe anyway
    backend = resolve_backend(_IMPL_OVERRIDE)
    if (backend == "interpret" and _IMPL_OVERRIDE is None
            and nxattn.paged_grid_size(B, H, n_pmax) > _INTERPRET_GRID_CAP):
        return "ref"
    return backend


def paged_decode_attention(
    params: dict[str, Any],
    x: jax.Array,
    kv_layer: "nxkv.PagedKV",
    block_tab: jax.Array,
    pos: jax.Array,
    *,
    page_size: int,
    n_heads: int,
    n_kv: int,
    head_dim: int,
    qk_norm: bool = False,
    rope_theta: float = 1e4,
    dense_kw: dict[str, Any] | None = None,
    apply_rope: bool = True,
    cache_dtype=jnp.bfloat16,
    with_syndrome: bool = False,
):
    """One decode step over one layer's *paged* KV pool.

    x: (B, 1, D);  pos: **(B,) int32 per-slot positions** — under continuous
    batching each slot sits at its own depth, so positions, the append
    target, and ``kv_len`` are all per-slot runtime vectors (the dense path's
    scalar ``pos`` is the uniform special case).  The new token's K/V are
    quantized/cast into page ``block_tab[b, pos // ps]`` offset ``pos % ps``;
    attention walks the slot's page list inside the kernel.  ``cache_dtype``
    matches the dense prefill cache so decode-appended residue pages hold
    byte-identical content to prefill-scattered ones (prefix reuse relies on
    page bytes being a pure function of the token prefix).

    ``with_syndrome=True`` (redundant residue formats) also returns the
    layer's in-kernel KV syndrome count: ``(out, kv_layer, syn (B,))``.
    """
    dense_kw = dense_kw or {}
    B = x.shape[0]
    pos = jnp.asarray(pos, jnp.int32)
    positions = pos[:, None]
    q, k, v = _project_qkv(params, x, n_heads=n_heads, n_kv=n_kv,
                           head_dim=head_dim, qk_norm=qk_norm,
                           positions=positions, rope_theta=rope_theta,
                           dense_kw=dense_kw, apply_rope=apply_rope)
    n_pmax = block_tab.shape[1]
    with jax.named_scope("kv.layer"):
        page_idx = jnp.clip(pos // page_size, 0, n_pmax - 1)
        pages = jnp.take_along_axis(block_tab, page_idx[:, None],
                                    axis=1)[:, 0]
        offs = pos % page_size
        kv_layer = nxkv.append_token(kv_layer,
                                     k[:, 0].astype(cache_dtype),
                                     v[:, 0].astype(cache_dtype), pages, offs)
    backend = _paged_backend(B, n_heads, n_pmax)
    with jax.named_scope("attn.core"):
        o = nxattn.paged_decode(q[:, 0], kv_layer, block_tab,
                                kv_len=pos + 1, page_size=page_size,
                                backend=backend, syndrome=with_syndrome)
    if with_syndrome:
        o, syn = o
    with jax.named_scope("attn.out"):
        out = o.astype(q.dtype).reshape(B, 1, n_heads * head_dim)
        out = linear.dense(params["wo"], out, **dense_kw)
    if with_syndrome:
        return out, kv_layer, syn
    return out, kv_layer


def paged_verify_attention(
    params: dict[str, Any],
    x: jax.Array,
    kv_layer: "nxkv.PagedKV",
    block_tab: jax.Array,
    positions: jax.Array,
    *,
    page_size: int,
    n_heads: int,
    n_kv: int,
    head_dim: int,
    qk_norm: bool = False,
    rope_theta: float = 1e4,
    dense_kw: dict[str, Any] | None = None,
    apply_rope: bool = True,
    cache_dtype=jnp.bfloat16,
) -> tuple[jax.Array, "nxkv.PagedKV"]:
    """Speculative verify step over one layer's *paged* KV pool.

    x: (B, V, D) — each slot feeds its current last token plus ``V - 1``
    drafted tokens at per-slot per-row ``positions (B, V)``.  All V rows'
    K/V append to the slot's pages in one scatter (the same fancy-indexed
    ``append_token``, now with (B, V) page/offset grids), then every row
    attends causally over its own prefix via :func:`nxattn.paged_verify`
    — the single-token flash kernel with the V axis folded into its batch
    grid and ``kv_len`` advancing per row.  Row ``j``'s output is
    bit-identical to a sequential decode that had emitted rows ``< j``:
    within the block, row ``j`` only ever attends to rows the acceptance
    rule has already pinned (a mismatch at ``i < j`` rejects row ``j``
    itself), so speculative reads always see the bytes a plain decode
    would have written.

    Positions past the block-table capacity append to the dump page
    (page 0) instead of clipping into the slot's last page: speculative
    tails may legally overshoot the allocation; clipping would corrupt
    live rows.
    """
    dense_kw = dense_kw or {}
    B, V, _ = x.shape
    positions = jnp.asarray(positions, jnp.int32)
    q, k, v = _project_qkv(params, x, n_heads=n_heads, n_kv=n_kv,
                           head_dim=head_dim, qk_norm=qk_norm,
                           positions=positions, rope_theta=rope_theta,
                           dense_kw=dense_kw, apply_rope=apply_rope)
    n_pmax = block_tab.shape[1]
    with jax.named_scope("kv.layer"):
        page_idx = positions // page_size
        pages = jnp.take_along_axis(block_tab,
                                    jnp.clip(page_idx, 0, n_pmax - 1), axis=1)
        pages = jnp.where(page_idx < n_pmax, pages, 0)   # overshoot -> dump
        offs = positions % page_size
        kv_layer = nxkv.append_token(kv_layer, k.astype(cache_dtype),
                                     v.astype(cache_dtype), pages, offs)
    backend = _paged_backend(B * V, n_heads, n_pmax)
    with jax.named_scope("attn.core"):
        o = nxattn.paged_verify(q, kv_layer, block_tab,
                                kv_len=positions + 1, page_size=page_size,
                                backend=backend)
    with jax.named_scope("attn.out"):
        out = o.astype(q.dtype).reshape(B, V, n_heads * head_dim)
        return linear.dense(params["wo"], out, **dense_kw), kv_layer
