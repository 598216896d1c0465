"""Pallas TPU kernels: flash attention (online-softmax, tiled) — GQA-native
prefill/full-sequence kernel plus a flash-decoding split-KV schedule.

Beyond-paper optimization for the serving/training attention hot-spot: the
baseline attention materializes (B, H, Sq, T) f32 scores in HBM (measured at
~10% of granite-20b's training traffic and the whole of the long-context
prefill wall); these kernels keep every score tile in VMEM and carry the
online-softmax statistics (running max m, normalizer l, weighted accumulator)
in f32 scratch — HBM traffic drops to Q/K/V/O only.

Layout and GQA
--------------
Callers pass the model's native layouts — q ``(B, Sq, H, hd)``, k/v
``(B, T, Kv, hd)`` (exactly the KV-cache layout).  Every block handed to
Mosaic has its last two dimensions either (8, 128)-aligned or whole:

* prefill runs head-major — q/k/v are transposed to ``(B, H|Kv, S, hd)``
  so a block is one head's ``(bq|bk, hd)`` tile; query head ``h`` reads
  KV head ``h // (H // Kv)`` through the BlockSpec index map, so the
  grouped cache is never repeated to the full head count;
* decode reads a KV chunk as one ``(rows, Kv*hd)`` slab (a free reshape
  of the cache / page pool) and walks the KV heads inside the body with
  static lane slices; queries arrive grouped as ``(Kv, g, hd)`` so each
  KV head's query group is a leading-axis index.  One grid step therefore
  loads each KV byte once for all query heads.

Runtime ``kv_len``
------------------
The number of valid KV positions is a **runtime operand** — a ``(B,)`` int32
array in SMEM — never a static.  Every decode position therefore reuses one
compiled kernel (the old static ``kv_len`` recompiled per token), and ragged
per-batch prompt lengths mask correctly inside one batch.

Tiling
------
``flash_attention_pallas``: grid ``(B, H, ceil(Sq/bq), ceil(T/bk))`` with the
KV axis innermost/sequential ("arbitrary") so the scratch carry is valid.
``flash_decode_pallas``: grid ``(B, ceil(T/bk))`` with the KV-chunk axis
*parallel* — each chunk emits (o, m, l) online-softmax partials for every
head and a tiny merge pass (plain jnp, see ``numerics/attention.py``)
log-sum-exp-combines them; this is the TPU form of flash-decoding's
split-KV scheme.  Partials are laid out ``(B, n_chunks, H, hd|1)`` so the
lane dimension of every output block is ``hd`` or whole.

Blocks need not divide the sequence dims: out-of-bounds tiles are padded by
the runtime (NaN in interpret mode, clamped reads under Mosaic), so every
tile is sanitized against its true extent before it enters the accumulation.

Exactness: this is *exact* attention (same math as the reference, different
summation order); tests sweep GQA ratios / causal / ragged ``kv_len``
against ``ref.py``.  The probabilities are never rounded to the cache
dtype: P·V runs in f32, as in the materialized path
(``models/attention._core``), so the two differ by summation order only —
rounding the unnormalized online-softmax P to bf16 while the materialized
path rounds the normalized one made them disagree by ~6e-4 relative.

Mesh contract
-------------
The kernels are shard_map-safe: they reference no mesh axes, so the
dispatchers in ``numerics/attention.py`` may run them *inside* a shard_map
body (the ``channel_shard`` decode schedule does — batch over dp, heads and
KV replicated, zero collectives) and the per-shard body is byte-for-byte
the single-device kernel.  ``compat.resolve_interpret`` keys on the
platform, not the mesh, so interpret-mode auto-selection is unchanged
inside a mapped body.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.moduli import PackedFormat, modinv
from repro.kernels import compat

__all__ = ["flash_attention_pallas", "flash_decode_pallas",
           "flash_paged_decode_pallas", "DEFAULT_BLOCKS"]

DEFAULT_BLOCKS = (256, 512)   # (bq, bk)
_NEG_INF = -1e30


def _attn_kernel(kvlen_ref, q_ref, k_ref, v_ref, o_ref, acc, m, lsum, *,
                 n_k: int, causal: bool, scale: float, bq: int, bk: int,
                 sq: int):
    """One (b, h, qi, ki) grid step.

    kvlen_ref: (B,) int32 in SMEM;  q_ref: (1, 1, bq, hd);
    k_ref/v_ref: (1, 1, bk, hd) — the KV head was selected by the BlockSpec
    index map;  o_ref: (1, 1, bq, hd).
    acc: (bq, hd) f32 scratch;  m, lsum: (bq, 1) f32 scratch.
    """
    b = pl.program_id(0)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m[...] = jnp.full_like(m, _NEG_INF)
        lsum[...] = jnp.zeros_like(lsum)
        acc[...] = jnp.zeros_like(acc)

    kv_len = kvlen_ref[b]
    q_rows = pl.program_id(2) * bq + jax.lax.broadcasted_iota(
        jnp.int32, (bq, 1), 0)
    k_rows = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bk, 1), 0)
    # sanitize padded tails: OOB tiles hold NaN (interpret) or clamped reads
    # (Mosaic); zeroed rows keep the matmuls finite and are masked below
    qb = jnp.where(q_rows < sq, q_ref[0, 0], 0.0)
    kb = jnp.where(k_rows < kv_len, k_ref[0, 0], 0.0)
    vb = jnp.where(k_rows < kv_len, v_ref[0, 0], 0.0)

    s = jax.lax.dot_general(
        qb, kb, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale      # (bq, bk)

    mask = k_rows.T < kv_len                             # (1, bk)
    if causal:
        mask = mask & (k_rows.T <= q_rows)               # (bq, bk)
    mask = jnp.broadcast_to(mask, (bq, bk))

    m_prev = m[...]                                      # (bq, 1)
    m_new = jnp.maximum(m_prev, jnp.max(jnp.where(mask, s, _NEG_INF),
                                        axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)                      # (bq, 1)
    p = jnp.where(mask, jnp.exp(s - m_new), 0.0)         # (bq, bk)
    lsum[...] = lsum[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    pv = jax.lax.dot_general(
        p, vb.astype(jnp.float32), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)              # (bq, hd)
    acc[...] = acc[...] * alpha + pv
    m[...] = m_new

    @pl.when(ki == n_k - 1)
    def _final():
        o_ref[0, 0] = (acc[...] / jnp.maximum(lsum[...], 1e-30)).astype(
            o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "bq", "bk",
                                             "interpret"))
def flash_attention_pallas(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    kv_len: jax.Array | None = None,
    *,
    causal: bool = True,
    bq: int = DEFAULT_BLOCKS[0],
    bk: int = DEFAULT_BLOCKS[1],
    interpret: bool | None = None,
) -> jax.Array:
    """Exact attention without materialized scores, GQA-native.

    Args:
      q: (B, Sq, H, hd);  k, v: (B, T, Kv, hd) with H % Kv == 0 — the
        model/cache layouts, heads ungrouped.
      kv_len: (B,) int32 *runtime* count of valid KV positions per batch row
        (<= T; the padded tail is masked).  ``None`` means all T are valid.
    Returns:
      (B, Sq, H, hd) in q's dtype.
    """
    interpret = compat.resolve_interpret(interpret)
    B, Sq, H, hd = q.shape
    _, T, Kv, _ = k.shape
    assert H % Kv == 0, (H, Kv)
    g = H // Kv
    if kv_len is None:
        kv_len = jnp.full((B,), T, jnp.int32)
    else:
        kv_len = jnp.broadcast_to(jnp.asarray(kv_len, jnp.int32), (B,))
    n_q = -(-Sq // bq)
    n_k = -(-T // bk)

    grid = (B, H, n_q, n_k)
    out = pl.pallas_call(
        functools.partial(_attn_kernel, n_k=n_k, causal=causal,
                          scale=1.0 / (hd ** 0.5), bq=bq, bk=bk, sq=Sq),
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, bq, hd), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, hd),
                         lambda b, h, i, j: (b, h // g, j, 0)),
            pl.BlockSpec((1, 1, bk, hd),
                         lambda b, h, i, j: (b, h // g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, hd),
                               lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, hd), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(kv_len, jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2),
      jnp.swapaxes(v, 1, 2))
    return jnp.swapaxes(out, 1, 2)


def _group_partial(qg, kb, vb, valid, scale):
    """Online-softmax partial of one KV head's query group over one chunk.

    qg: (g, hd) queries;  kb, vb: (rows, hd) sanitized KV rows;  valid:
    (1, rows) bool, lane-major (Mosaic cannot transpose a mask).  Returns
    ``(o (g, hd) f32, m (g, 1), l (g, 1))``.  An all-masked chunk gives
    m = -inf-ish and p = 0 everywhere -> l = 0, o = 0; the merge pass
    weighs it out (its exp(m_c - m_max) underflows).
    """
    dt = jnp.promote_types(qg.dtype, kb.dtype)
    s = jax.lax.dot_general(
        qg.astype(dt), kb.astype(dt), (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale      # (g, rows)
    s = jnp.where(valid, s, _NEG_INF)
    m_c = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.where(valid, jnp.exp(s - m_c), 0.0)
    l_c = jnp.sum(p, axis=-1, keepdims=True)
    o_c = jax.lax.dot_general(
        p, vb.astype(jnp.float32), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)              # (g, hd)
    return o_c, m_c, l_c


def _chunk_rows(chunk, rows: int, axis: int) -> jax.Array:
    """Logical KV row ids of a chunk as a column (axis=0) or row (axis=1)."""
    shape = (rows, 1) if axis == 0 else (1, rows)
    return chunk * rows + jax.lax.broadcasted_iota(jnp.int32, shape, axis)


def _decode_kernel(kvlen_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, *,
                   bk: int, scale: float, n_kv: int, hd: int):
    """One (b, ki) grid step of the split-KV decode schedule.

    Each KV chunk is independent (*parallel* grid axis — no scratch carry):
    it emits its own online-softmax partial (o, m, l) for every head and the
    merge pass combines them.  kvlen_ref: (B,) int32 in SMEM;  q_ref:
    (1, Kv, g, hd);  k_ref/v_ref: (1, bk, Kv*hd);  o_ref: (1, 1, Kv, g, hd);
    m_ref/l_ref: (1, 1, Kv, g, 1).
    """
    b = pl.program_id(0)
    ki = pl.program_id(1)
    kv_len = kvlen_ref[b]
    valid = _chunk_rows(ki, bk, 0) < kv_len              # (bk, 1)
    valid_t = _chunk_rows(ki, bk, 1) < kv_len            # (1, bk)
    for h in range(n_kv):
        lanes = slice(h * hd, (h + 1) * hd)
        kb = jnp.where(valid, k_ref[0, :, lanes], 0.0)
        vb = jnp.where(valid, v_ref[0, :, lanes], 0.0)
        o_c, m_c, l_c = _group_partial(q_ref[0, h], kb, vb, valid_t, scale)
        o_ref[0, 0, h] = o_c
        m_ref[0, 0, h] = m_c
        l_ref[0, 0, h] = l_c


def _split_partials(o, m, l, H):
    """(B, n, Kv, g, hd|1) kernel outputs -> merge layout (B, n, H, ...)."""
    B, n = o.shape[:2]
    return (o.reshape(B, n, H, o.shape[-1]), m.reshape(B, n, H),
            l.reshape(B, n, H))


def _partial_specs(H: int, n_kv: int, hd: int, index_map):
    g = H // n_kv
    return [pl.BlockSpec((1, 1, n_kv, g, hd), index_map),
            pl.BlockSpec((1, 1, n_kv, g, 1), index_map),
            pl.BlockSpec((1, 1, n_kv, g, 1), index_map)]


def _partial_shapes(B: int, n: int, H: int, n_kv: int, hd: int):
    g = H // n_kv
    return [jax.ShapeDtypeStruct((B, n, n_kv, g, hd), jnp.float32),
            jax.ShapeDtypeStruct((B, n, n_kv, g, 1), jnp.float32),
            jax.ShapeDtypeStruct((B, n, n_kv, g, 1), jnp.float32)]


@functools.partial(jax.jit, static_argnames=("bk", "interpret"))
def flash_decode_pallas(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    kv_len: jax.Array,
    *,
    bk: int = DEFAULT_BLOCKS[1],
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Split-KV decode partials: per-chunk online-softmax (o, m, l).

    Args:
      q: (B, H, hd) — the single decode token's queries;
      k, v: (B, T, Kv, hd) — the KV cache, heads ungrouped;
      kv_len: (B,) int32 runtime valid-prefix length (<= T).
    Returns:
      ``(o_part (B, n_chunks, H, hd) f32, m_part (B, n_chunks, H) f32,
      l_part (B, n_chunks, H) f32)`` — merge with
      :func:`repro.numerics.attention.merge_decode_partials`.
    """
    interpret = compat.resolve_interpret(interpret)
    B, H, hd = q.shape
    _, T, Kv, _ = k.shape
    assert H % Kv == 0, (H, Kv)
    g = H // Kv
    kv_len = jnp.broadcast_to(jnp.asarray(kv_len, jnp.int32), (B,))
    n_k = -(-T // bk)

    def out_map(b, j):
        return (b, j, 0, 0, 0)

    o, m, l = pl.pallas_call(
        functools.partial(_decode_kernel, bk=bk, scale=1.0 / (hd ** 0.5),
                          n_kv=Kv, hd=hd),
        grid=(B, n_k),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, Kv, g, hd), lambda b, j: (b, 0, 0, 0)),
            pl.BlockSpec((1, bk, Kv * hd), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, bk, Kv * hd), lambda b, j: (b, j, 0)),
        ],
        out_specs=_partial_specs(H, Kv, hd, out_map),
        out_shape=_partial_shapes(B, n_k, H, Kv, hd),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(kv_len, q.reshape(B, Kv, g, hd), k.reshape(B, T, Kv * hd),
      v.reshape(B, T, Kv * hd))
    return _split_partials(o, m, l, H)


def _unpack_crt(byte: jax.Array, moduli: tuple[int, int]) -> jax.Array:
    """Bit-packed centered 2-channel residues -> int32 values, in-register.

    ``byte`` is int32-widened uint8 of shape (rows, hd/vpb).  Each byte holds
    ``vpb`` lanes of ``b0+b1`` bits: channel-0 residue in the low ``b0`` bits,
    channel-1 in the next ``b1``, both two's-complement.  CRT fold with the
    power-of-two modulus as the anchor: X = r1 + m1 * center((r0 - r1) *
    inv(m1 mod m0, m0) mod m0).  Exact over [-M/2, M/2).
    """
    fmt = PackedFormat.for_moduli(moduli)
    (b0, b1), vpb = fmt.widths, fmt.values_per_byte
    m0, m1 = moduli
    w = b0 + b1
    if vpb > 1:
        lanes = jnp.stack(
            [(byte >> (i * w)) & ((1 << w) - 1) for i in range(vpb)], axis=-1)
        lane = lanes.reshape(byte.shape[0], byte.shape[1] * vpb)
    else:
        lane = byte
    f0 = lane & ((1 << b0) - 1)
    f1 = (lane >> b0) & ((1 << b1) - 1)
    r0 = f0 - ((f0 >> (b0 - 1)) << b0)           # sign-extend both fields
    r1 = f1 - ((f1 >> (b1 - 1)) << b1)
    inv = modinv(m1 % m0, m0)
    t = jax.lax.rem((r0 - r1) * inv, jnp.int32(m0))
    t = jnp.where(t < 0, t + m0, t)              # canonical residue mod m0
    t = jnp.where(t > (m0 - 1) // 2, t - m0, t)  # centered
    return r1 + m1 * t


def _paged_decode_kernel(tab_ref, kvlen_ref, q_ref, k_ref, v_ref, *rest,
                         ps: int, scale: float, n_kv: int, hd: int,
                         moduli: tuple[int, int] | None,
                         red_moduli: tuple[int, ...] | None):
    """One (b, j) grid step: page ``tab[b, j]`` of the split-KV schedule.

    The scalar-prefetched block table already steered the BlockSpec index
    maps at page ``tab[b, j]``, so the kernel body only sees this request's
    j-th page — one ``(ps, lanes)`` slab holding every KV head; masking is
    against the *logical* row ``j*ps + slot`` exactly like the dense chunk
    kernel.  With ``moduli`` set, k/v arrive as packed uint8 residue planes
    (lane group 0 = the packed info bytes of all heads, groups 1..r the
    witness residues) plus an f32 per-(slot, head) scale slab and are
    dequantized in-register before the dot products.

    With ``red_moduli`` the kernel emits a fourth reduction output: the
    count of valid (row, head, hd) elements on this page whose stored
    witness residues disagree with the packed info byte it just decoded —
    KV integrity is checked *while the planes are in VMEM*, for free on the
    decode hot path.  Each page step counts each of its elements once.
    """
    if moduli is None:
        o_ref, m_ref, l_ref = rest
    elif red_moduli is None:
        ks_ref, vs_ref, o_ref, m_ref, l_ref = rest
    else:
        ks_ref, vs_ref, o_ref, m_ref, l_ref, syn_ref = rest
    b = pl.program_id(0)
    j = pl.program_id(1)
    kv_len = kvlen_ref[b]
    valid = _chunk_rows(j, ps, 0) < kv_len               # (ps, 1)
    valid_t = _chunk_rows(j, ps, 1) < kv_len             # (1, ps)
    hdp = hd if moduli is None else hd // PackedFormat.for_moduli(
        moduli).values_per_byte
    group = n_kv * hdp                        # lanes of one plane lane group

    def lanes(lane_group, h):
        lo = lane_group * group + h * hdp
        return slice(lo, lo + hdp)

    def bad(x_int, w_ref, h):
        mism = jnp.zeros(x_int.shape, jnp.bool_)
        for jw, m in enumerate(red_moduli):
            wit = w_ref[0, :, lanes(1 + jw, h)].astype(jnp.int32)
            mism = mism | (jnp.remainder(
                wit - jnp.remainder(x_int, m), m) != 0)
        cnt = jnp.sum((mism & valid).astype(jnp.int32), axis=1,
                      keepdims=True)
        return jnp.sum(cnt, axis=0, keepdims=True)       # (1, 1)

    syn = jnp.zeros((1, 1), jnp.int32)
    for h in range(n_kv):
        if moduli is None:
            kb = k_ref[0, :, lanes(0, h)]
            vb = v_ref[0, :, lanes(0, h)]
        else:
            k_int = _unpack_crt(k_ref[0, :, lanes(0, h)].astype(jnp.int32),
                                moduli)
            v_int = _unpack_crt(v_ref[0, :, lanes(0, h)].astype(jnp.int32),
                                moduli)
            if red_moduli is not None:
                syn = syn + bad(k_int, k_ref, h) + bad(v_int, v_ref, h)
            kb = k_int.astype(jnp.float32) * ks_ref[0, :, h:h + 1]
            vb = v_int.astype(jnp.float32) * vs_ref[0, :, h:h + 1]
        kb = jnp.where(valid, kb, 0.0)
        vb = jnp.where(valid, vb, 0.0)
        o_c, m_c, l_c = _group_partial(q_ref[0, h], kb, vb, valid_t, scale)
        o_ref[0, 0, h] = o_c
        m_ref[0, 0, h] = m_c
        l_ref[0, 0, h] = l_c
    if red_moduli is not None:
        syn_ref[0, 0] = syn


@functools.partial(jax.jit, static_argnames=("page_size", "moduli",
                                             "red_moduli", "interpret"))
def flash_paged_decode_pallas(
    q: jax.Array,
    k_pages: jax.Array,
    v_pages: jax.Array,
    block_tab: jax.Array,
    kv_len: jax.Array,
    *,
    page_size: int,
    k_scale: jax.Array | None = None,
    v_scale: jax.Array | None = None,
    moduli: tuple[int, int] | None = None,
    red_moduli: tuple[int, ...] | None = None,
    interpret: bool | None = None,
) -> tuple[jax.Array, ...]:
    """Split-KV decode over a *paged* cache: chunk boundary == page boundary.

    The per-request page list is a **scalar-prefetch** operand: the grid's
    chunk axis walks ``block_tab[b]`` and the BlockSpec index map fetches
    page ``tab[b, j]`` of the pool, so the dense ``T`` axis never exists on
    device.  With ``moduli`` the pages are bit-packed residue planes and
    dequantization fuses into the KV load.

    Args:
      q: (B, H, hd) decode-token queries.
      k_pages, v_pages: (P, ps, Kv, hd) pool (cache dtype), or with
        ``moduli`` set the residue planes (P, ps, 1 + r, Kv, hd/vpb) uint8
        (lane 0 the packed info byte, lanes 1..r redundant witnesses) plus
        ``k_scale``/``v_scale`` (P, ps, Kv, 1) f32.  Pools are passed whole
        — the kernel picks its lanes, so no per-step slice is copied.
      block_tab: (B, n_pmax) int32 page ids per request; entries past the
        live prefix may point anywhere (masked by ``kv_len``).
      kv_len: (B,) int32 valid-prefix length (<= n_pmax * page_size).
      red_moduli: the witness moduli — the kernel then also accumulates a
        per-(b, page) syndrome count from the witness lanes.
    Returns:
      ``(o (B, n_pmax, H, hd), m (B, n_pmax, H), l (B, n_pmax, H))`` f32
      partials for :func:`repro.numerics.attention.merge_decode_partials`;
      with ``red_moduli`` a fourth ``syn (B,)`` int32 counting witness
      mismatches on the request's valid rows.
    """
    interpret = compat.resolve_interpret(interpret)
    B, H, hd = q.shape
    P, ps = k_pages.shape[:2]
    assert ps == page_size, (ps, page_size)
    Kv = k_pages.shape[-2]
    assert H % Kv == 0, (H, Kv)
    g = H // Kv
    block_tab = jnp.asarray(block_tab, jnp.int32)
    kv_len = jnp.broadcast_to(jnp.asarray(kv_len, jnp.int32), (B,))
    n_pmax = block_tab.shape[1]
    # one page = one (ps, lanes) slab: a free reshape of the pool
    row = k_pages[0, 0].size
    if moduli is not None:
        assert k_scale is not None and v_scale is not None
        assert red_moduli is None or k_pages.shape[2] == 1 + len(
            red_moduli), (k_pages.shape, red_moduli)
    else:
        assert red_moduli is None

    def page_map(b, j, tab, kvl):
        return (tab[b, j], 0, 0)

    in_specs = [
        pl.BlockSpec((1, Kv, g, hd), lambda b, j, tab, kvl: (b, 0, 0, 0)),
        pl.BlockSpec((1, ps, row), page_map),
        pl.BlockSpec((1, ps, row), page_map),
    ]
    operands = [q.reshape(B, Kv, g, hd), k_pages.reshape(P, ps, row),
                v_pages.reshape(P, ps, row)]
    if moduli is not None:
        in_specs += [pl.BlockSpec((1, ps, Kv), page_map)] * 2
        operands += [k_scale.reshape(P, ps, Kv), v_scale.reshape(P, ps, Kv)]

    def out_map(b, j, tab, kvl):
        return (b, j, 0, 0, 0)

    out_specs = _partial_specs(H, Kv, hd, out_map)
    out_shape = _partial_shapes(B, n_pmax, H, Kv, hd)
    if red_moduli is not None:
        out_specs.append(pl.BlockSpec(
            (1, 1, 1, 1), lambda b, j, tab, kvl: (b, j, 0, 0)))
        out_shape.append(jax.ShapeDtypeStruct((B, n_pmax, 1, 1), jnp.int32))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, n_pmax),
        in_specs=in_specs,
        out_specs=out_specs,
    )
    outs = pl.pallas_call(
        functools.partial(_paged_decode_kernel, ps=ps,
                          scale=1.0 / (hd ** 0.5), n_kv=Kv, hd=hd,
                          moduli=moduli, red_moduli=red_moduli),
        grid_spec=grid_spec,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(block_tab, kv_len, *operands)
    parts = _split_partials(*outs[:3], H)
    if red_moduli is None:
        return parts
    return (*parts, outs[3].sum(axis=(1, 2, 3)))
