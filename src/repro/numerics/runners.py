"""Internal kernel runners behind the typed numerics API.

These are the shared execution paths every public surface lands on — the
typed ``repro.numerics`` dispatch (``matmul``/``einsum``/``add``) and the
deprecated ``kernels/ops.py`` entry points alike — which is what keeps
digit outputs bit-identical across API generations:

* :func:`rns_run`   — activation forward-conversion + K-segmentation +
  channel-wise modular matmul over pre-encoded residue planes;
* :func:`sdrns_run` — the signed-digit sibling (fused Eq. 2 kernel), with
  decode shapes (M <= :data:`DECODE_M`) auto-routed to the matvec schedule;
* :func:`sd_add_run` — batched carry-free SD addition (pad/tile plumbing
  around the VPU kernel).

Plane encoders (:func:`encode_rns_planes`, :func:`encode_sd_planes`) are
elementwise, so encode-then-slice equals slice-then-encode — the property
that keeps residue-resident weights bit-identical to convert-per-call.

Kernel implementations are registered here against the backend registry
(``numerics/registry.py``): pallas / interpret / ref / cost per op.

Mesh composition
----------------
:func:`tp_shard_plan` turns the installed
:class:`~repro.parallel.sharding.ShardCtx` into a *static*, tagged
shard-map plan; with a plan, :func:`rns_run` / :func:`sdrns_run` wrap
their whole body in ``jax.shard_map``.  Two schedules:

* ``("col", ...)`` — the default layout: activations row-sharded over
  ``dp``, pre-encoded planes column-sharded over ``tp`` on the output
  dim, output ``(dp, tp)``-sharded.  Column slices of the integer matmul
  are independent, so each shard runs the unchanged per-shard Pallas
  kernel with **zero collectives** and the result is bit-identical to
  the single-device path.
* ``("chan", ...)`` — the ``channel_shard`` layout: planes split over
  ``tp`` on the moduli-channel C axis.  Each shard matmuls only its
  locally resident channels, projects the per-channel outputs to
  value-domain CRT partials (``ModuliSet.partial_decode``) and the
  shards fold with **one** ``psum`` + one final ``mod M``
  (``fold_partials`` / redundancy-aware ``corrected_fold``) — no device
  ever materializes the full channel axis, and the decode is
  bit-identical to the gathered single-device path.

The plan is passed down as a jit static (``numerics/api.py``), never
read inside a traced body — a context installed after a trace was
cached can therefore never be silently ignored.  When ``channel_shard``
is requested but the psum path cannot engage (C not divisible by the
tensor axis, or a set past the int32 partial-CRT bound), the planner
warns and counts the event (:func:`fallback_gather_count` — surfaced as
``EngineStats.fallback_gathers``) instead of silently running the slow
replicated/gathered layout.
"""
from __future__ import annotations

import functools
import warnings

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core import sd, sdrns
from repro.core.moduli import ModuliSet
from repro.kernels.rns_matmul import (
    VMEM_BUDGET,
    rns_matmul_pallas,
    vmem_bytes,
)
from repro.kernels.sd_add import sd_add_pallas
from repro.kernels.sdrns_matmul import (
    WRAP_SIGNS,
    sdrns_matmul_pallas,
    sdrns_matvec_pallas,
)
from repro.numerics.registry import get_impl, register_impl

__all__ = [
    "DECODE_M",
    "segment_count",
    "encode_rns_planes",
    "encode_sd_planes",
    "rns_run",
    "sdrns_run",
    "sd_add_run",
    "tp_shard_plan",
    "fallback_gather_count",
]


# ---------------------------------------------------------------------------
# Mesh composition: static shard-map plans for the matmul/matvec runners.
# ---------------------------------------------------------------------------

# Times the channel_shard layout was requested but the partial-CRT psum
# path could not engage (the plan fell back to the replicated/gathered
# layout).  Counted per *plan resolution* — the planner runs outside jit on
# every public matmul/einsum call, so a mis-sharded mesh is visible instead
# of quietly slow.  Surfaced as ``EngineStats.fallback_gathers``.
_FALLBACK_GATHERS = 0


def fallback_gather_count() -> int:
    """Process-lifetime count of channel_shard psum-path fallbacks."""
    return _FALLBACK_GATHERS


def _fallback(reason: str) -> None:
    global _FALLBACK_GATHERS
    _FALLBACK_GATHERS += 1
    warnings.warn(
        "channel_shard layout fell back to the replicated/gathered decode "
        f"path: {reason}", UserWarning, stacklevel=4)


def tp_shard_plan(M: int, N: int, *, mset: ModuliSet | None = None):
    """Shard-map plan from the installed ShardCtx, or ``None``.

    Plans are tagged hashable tuples — jit *statics*, so traces key on
    them:

    * ``("col", mesh, dp_names, tp_names)`` — default layout: plane
      columns over ``tp`` on the output dim (needs ``N % tp_size == 0``).
    * ``("chan", mesh, dp_names, tp_names)`` — ``channel_shard`` layout:
      moduli channels over ``tp``; the runner takes the partial-CRT psum
      schedule.  Needs the moduli metadata (``mset=``), ``C % tp_size ==
      0`` and :attr:`ModuliSet.supports_partial_decode`; when any of
      those fail the planner *warns* and bumps
      :func:`fallback_gather_count` (the layout silently degrading to a
      cross-channel gather is exactly the failure mode this PR removes).

    ``None`` = single-device path.  ``dp_names`` is ``()`` when ``M`` is
    not divisible (activation rows then run replicated inside the map).
    """
    from repro.parallel.sharding import get_shard_ctx

    ctx = get_shard_ctx()
    if ctx is None:
        return None
    tp = ctx.resolve("tp")
    tp_size = ctx.axis_size(tp) if tp else 1
    if not tp or tp_size <= 1:
        return None
    dp = ctx.resolve("dp")
    if not dp or M % ctx.axis_size(dp):
        dp = ()
    if ctx.channel_shard:
        if mset is None:
            _fallback("no moduli metadata reached the planner (legacy "
                      "entry point passes no mset)")
            return None
        if mset.num_channels % tp_size:
            _fallback(f"C={mset.num_channels} channels do not divide the "
                      f"tensor axis ({tp_size} devices)")
            return None
        if not mset.supports_partial_decode:
            _fallback(f"moduli set {mset.moduli} exceeds the int32 "
                      "partial-CRT bound (sequential MRC decode required)")
            return None
        return ("chan", ctx.mesh, dp, tp)
    if N % tp_size:
        return None
    return ("col", ctx.mesh, dp, tp)


def _shard_mapped(body, shard, *, sd_planes: bool):
    """Wrap a 2-operand runner body in a ``("col", ...)`` plan's shard_map."""
    _, mesh, dp, tp = shard
    b_spec = P(None, None, tp, None) if sd_planes else P(None, None, tp)
    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(dp or None, None), b_spec),
        out_specs=P(dp or None, tp),
        check_vma=False)


def _channel_mapped(body, shard, *, sd_planes: bool):
    """Wrap a channel-parallel body in a ``("chan", ...)`` plan's shard_map.

    Planes sharded over ``tp`` on the leading C axis, output replicated
    over ``tp`` (the body's psum makes every shard's fold identical).
    """
    _, mesh, dp, tp = shard
    tp_entry = tp if len(tp) > 1 else tp[0]
    b_spec = (P(tp_entry, None, None, None) if sd_planes
              else P(tp_entry, None, None))
    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(dp or None, None), b_spec),
        out_specs=P(dp or None, None),
        check_vma=False)


def _channel_ids(tp, C_loc: int) -> jax.Array:
    """Global channel ids of this shard's C-slice (inside a shard_map body).

    The linearized shard index over the (possibly tuple) tp axes follows
    PartitionSpec's major-to-minor tuple-axis split order, so block ``i``
    of the C axis lands on linear index ``i``.
    """
    idx = jax.lax.axis_index(tp[0])
    for name in tp[1:]:
        idx = idx * jax.lax.axis_size(name) + jax.lax.axis_index(name)
    return idx * C_loc + jnp.arange(C_loc, dtype=jnp.int32)


def _round_up(v: int, k: int) -> int:
    return (v + k - 1) // k * k


def segment_count(K: int, max_abs_a: int, max_abs_b: int,
                  mset: ModuliSet) -> int:
    """Segments needed so each exact partial result fits (-M/2, M/2)."""
    if max_abs_a == 0 or max_abs_b == 0:
        return 1
    per_term = max_abs_a * max_abs_b
    cap = mset.half_range // per_term
    if cap < 1:
        raise ValueError(
            f"operand bound {per_term} exceeds dynamic range of {mset.moduli}"
        )
    segs = (K + cap - 1) // cap
    return max(segs, 1)


# ---------------------------------------------------------------------------
# rns — int8 residue planes, lazy reduction, MXU tiling.
# ---------------------------------------------------------------------------


# Largest weight block (bytes) one grid step streams: 1-4 MiB hides the
# fixed cost of a grid step (about 0.37 us on a v5e) behind the block's DMA.
_WEIGHT_BLOCK_BYTES = 4 * 1024 * 1024
# Rows of one grid step once M outgrows one block (admission prefill): enough
# MXU work per step to hide the weight block's DMA.
_MAX_BM = 512


def _tiles(dim: int) -> list[int]:
    """Block sizes for one lane-aligned axis: the multiples of 128 that divide
    ``dim`` rounded up to 128, so an aligned axis is never padded."""
    padded = _round_up(dim, 128)
    return [t for t in range(128, padded + 1, 128) if padded % t == 0]


def _choose_blocks(M: int, N: int, K: int,
                   itemsize: int = 1) -> tuple[int, int, int]:
    """``(bm, bn, bk)`` of one ``rns_matmul`` call over a K segment of
    ``K``, from the call's own shape.

    ``bk`` and ``bn`` divide ``K`` and ``N`` (each rounded up to 128), so
    aligned weight planes reach the kernel as they are.  Among the pairs
    whose weight block fits :data:`_WEIGHT_BLOCK_BYTES` and whose grid step
    fits the kernel's VMEM budget, the whole K segment wins first (one
    reduction, no accumulator traffic), then the widest column block.  Up to
    :data:`_MAX_BM` rows ride one M block, so each weight byte is read once.
    """
    bm = _round_up(M, 8) if M <= _MAX_BM else _MAX_BM
    bk, bn = max((bk, bn) for bk in _tiles(K) for bn in _tiles(N)
                 if bk * bn * itemsize <= _WEIGHT_BLOCK_BYTES
                 and vmem_bytes(bm, bn, bk, itemsize) <= VMEM_BUDGET)
    return bm, bn, bk


register_impl(
    "rns_matmul", "pallas",
    lambda a, b, mset, bm, bn, bk: rns_matmul_pallas(
        a, b, jnp.asarray(mset.moduli, jnp.int32),
        bm=bm, bn=bn, bk=bk, interpret=False))
register_impl(
    "rns_matmul", "interpret",
    lambda a, b, mset, bm, bn, bk: rns_matmul_pallas(
        a, b, jnp.asarray(mset.moduli, jnp.int32),
        bm=bm, bn=bn, bk=bk, interpret=True))


def _rns_matmul_ref_impl(a, b, mset, bm, bn, bk):
    from repro.kernels.ref import rns_matmul_ref

    return rns_matmul_ref(a, b, mset)


register_impl("rns_matmul", "ref", _rns_matmul_ref_impl)


# Array-parameterized sibling of "rns_matmul": the moduli arrive as a
# runtime (C_loc,) operand instead of static ModuliSet metadata.  Needed by
# the channel-parallel shard_map body, where the locally resident channels
# are selected by a *traced* ``axis_index`` — the Pallas kernel already
# takes its moduli as a runtime operand, so pallas/interpret are the same
# kernel; ref/cost mirror its lazy-reduction semantics (one int32
# accumulation, one centered reduction) against the moduli array.
register_impl(
    "rns_matmul_planes", "pallas",
    lambda a, b, moduli, bm, bn, bk: rns_matmul_pallas(
        a, b, moduli, bm=bm, bn=bn, bk=bk, interpret=False))
register_impl(
    "rns_matmul_planes", "interpret",
    lambda a, b, moduli, bm, bn, bk: rns_matmul_pallas(
        a, b, moduli, bm=bm, bn=bn, bk=bk, interpret=True))


def _rns_matmul_planes_ref_impl(a, b, moduli, bm, bn, bk):
    acc = jnp.einsum("cmk,ckn->cmn",
                     a.astype(jnp.int32), b.astype(jnp.int32))
    m = moduli.reshape(-1, 1, 1)
    r = jnp.remainder(acc, m)
    return jnp.where(r > m // 2, r - m, r)


register_impl("rns_matmul_planes", "ref", _rns_matmul_planes_ref_impl)
register_impl("rns_matmul_planes", "cost", _rns_matmul_planes_ref_impl)


def _res_dtype(mset: ModuliSet):
    # centered residues reach m // 2, which int8 holds only up to m = 255
    return jnp.int8 if max(mset.moduli) <= 255 else jnp.int32


def encode_rns_planes(w: jax.Array, mset: ModuliSet) -> jax.Array:
    """Integer values (..., K, N) -> centered residue planes (..., C, K, N).

    The channel axis lands *after* any leading (layer-stack) axes so the
    planes slice cleanly under ``jax.lax.scan`` over stacked layers.  int8
    when every centered residue fits (the MXU-path rule of the rns kernel).
    """
    res = mset.to_residues(w.astype(jnp.int32))          # (C, ..., K, N)
    return jnp.moveaxis(res, 0, -3).astype(_res_dtype(mset))


def encode_packed_planes(w: jax.Array, mset: ModuliSet) -> jax.Array:
    """Integer values (..., K, N) -> bit-packed planes (..., 1 + r, K, N/vpb).

    The ``rns_pack`` storage layout (KV pages): both centered residues of a
    packable 2-channel set share byte lanes (``ModuliSet.packed()``); the
    channel axis keeps the scan-sliceable ResidueTensor contract.  Redundant
    sets append ``r`` unpacked witness lanes (canonical residues mod the
    redundant moduli, uint8) after the packed lane — the storage behind the
    fault-tolerant KV page format (``kv_pages.verify_pages``).
    """
    fmt = mset.packed()
    lane0 = fmt.encode(w)
    if mset.redundant == 0:
        return lane0[..., None, :, :]
    if fmt.values_per_byte != 1:
        raise ValueError(
            "redundant rns_pack needs one value per byte, got "
            f"vpb={fmt.values_per_byte} for {mset.moduli}")
    w32 = w.astype(jnp.int32)
    red = [jnp.remainder(w32, m).astype(jnp.uint8)
           for m in mset.redundant_moduli]
    return jnp.stack([lane0, *red], axis=-3)


def rns_run(a, b_res, *, mset, max_abs_a, max_abs_b, backend, shard=None,
            verify=None):
    """Shared runner: activation conversion + segmentation + kernel dispatch.

    ``b_res``: (C, K, N) pre-encoded centered residue planes.  Every public
    surface (typed ``numerics.matmul`` and the deprecated entry points)
    lands here, so outputs are bit-identical by construction.

    ``shard``: a :func:`tp_shard_plan` — maps this whole body over the
    mesh (rows over dp, plane columns over tp; per-shard kernels, no
    collectives).  Column slices of the exact integer matmul commute with
    the kernel, so sharded output == single-device output bit-for-bit.

    ``verify``: redundant moduli sets carry their witness channels through
    the matmul for free (channels are independent), and the per-segment
    decode runs :meth:`ModuliSet.corrected_decode` — base-extension
    syndrome compare, escalating to single-channel reconstruction under a
    ``lax.cond`` only when a fault is present.  A corrupted weight plane
    channel therefore never reaches the value domain: the step's output is
    bit-identical to the fault-free run.  ``None`` (default) enables the
    check exactly when ``mset.redundant >= 2``; ``False`` forces the raw
    info-channel decode (the bench baseline for the check's overhead).
    """
    if shard is not None:
        if shard[0] == "chan":
            body = functools.partial(
                _rns_channel_body, mset=mset, max_abs_a=max_abs_a,
                max_abs_b=max_abs_b, backend=backend, verify=verify,
                tp=shard[3])
            return _channel_mapped(body, shard, sd_planes=False)(a, b_res)
        body = functools.partial(rns_run, mset=mset, max_abs_a=max_abs_a,
                                 max_abs_b=max_abs_b, backend=backend,
                                 verify=verify)
        return _shard_mapped(body, shard, sd_planes=False)(a, b_res)
    impl = get_impl("rns_matmul", backend)
    if verify is None:
        verify = mset.redundant >= 2
    decode = mset.corrected_decode if (verify and mset.redundant) \
        else mset.from_residues
    M, K = a.shape
    _, K2, N = b_res.shape
    assert K == K2, (a.shape, b_res.shape)

    a_res = mset.to_residues(a.astype(jnp.int32)).astype(_res_dtype(mset))

    blocks, segments = _segments(a_res, b_res, mset=mset, max_abs_a=max_abs_a,
                                 max_abs_b=max_abs_b)
    total = jnp.zeros((M, N), jnp.int32)
    for a_p, b_p in segments:
        out_res = impl(a_p, b_p, mset, *blocks)
        total = total + decode(out_res[:, :M, :N])
    return total


def _pad_to(x: jax.Array, shape: tuple[int, ...]) -> jax.Array:
    """``x`` zero-padded at the end of each axis to ``shape`` (``x`` itself
    where it has that shape already)."""
    if x.shape == shape:
        return x
    return jnp.pad(x, [(0, t - d) for d, t in zip(x.shape, shape)])


def _segments(a_res, b_res, *, mset, max_abs_a, max_abs_b):
    """K segments of the residue operands, shaped for the ``rns_matmul``
    kernel: ``((bm, bn, bk), [(a_seg, b_seg), ...])``.

    Segments keep each exact partial product inside the moduli range; the
    tiles come from :func:`_choose_blocks`.  An operand is padded only where
    its shape is not a multiple of the tiles; padding a weight operand copies
    its planes on every call, so it is counted (``plane_pad``, trace time).
    """
    from repro.quant import residency

    C, M, K = a_res.shape
    N = b_res.shape[-1]
    segs = segment_count(K, max_abs_a, max_abs_b, mset)
    seg_len = _round_up((K + segs - 1) // segs, 128)
    segs = (K + seg_len - 1) // seg_len

    bm, bn, bk = _choose_blocks(M, N, seg_len, a_res.dtype.itemsize)
    Mp, Np = _round_up(M, bm), _round_up(N, bn)
    Kp = _round_up(seg_len, bk)
    segments = []
    for s in range(segs):
        lo, hi = s * seg_len, min((s + 1) * seg_len, K)
        b_s = b_res[:, lo:hi, :]
        if b_s.shape != (C, Kp, Np):
            residency.record("plane_pad")
        segments.append((_pad_to(a_res[:, :, lo:hi], (C, Mp, Kp)),
                         _pad_to(b_s, (C, Kp, Np))))
    return (bm, bn, bk), segments


def _rns_channel_body(a, b_res, *, mset, max_abs_a, max_abs_b, backend,
                      verify, tp):
    """Channel-parallel rns schedule (inside a ``("chan", ...)`` shard_map).

    ``b_res``: the *local* ``(C_loc, K, N)`` plane slice.  Each shard
    matmuls only its resident channels, projects the per-channel outputs
    to value-domain CRT partials (witness channels contribute their
    canonical residues via one-hot rows instead), and all per-segment rows
    cross the mesh in **one** stacked ``psum``.  The fold
    (:meth:`ModuliSet.fold_partials` / redundancy-aware
    :meth:`~ModuliSet.corrected_fold`) runs per segment — segment partials
    are separate exact products, so folding their sum would be wrong —
    and is bit-identical to the gathered single-device decode.
    """
    impl = get_impl("rns_matmul_planes", backend)
    if verify is None:
        verify = mset.redundant >= 2
    witness = bool(verify) and mset.redundant >= 2
    M, K = a.shape
    C_loc, K2, N = b_res.shape
    assert K == K2, (a.shape, b_res.shape)

    cid = _channel_ids(tp, C_loc)
    moduli = jnp.take(jnp.asarray(mset.moduli, jnp.int32), cid)
    # Forward conversion needs every channel's residues of the activations;
    # it is elementwise (cheap, collective-free), so convert all C and keep
    # the local slice by traced gather.
    a_all = mset.to_residues(a.astype(jnp.int32))        # (C, M, K)
    a_res = jnp.take(a_all, cid, axis=0).astype(_res_dtype(mset))

    blocks, segments = _segments(a_res, b_res, mset=mset, max_abs_a=max_abs_a,
                                 max_abs_b=max_abs_b)
    parts = []
    for a_p, b_p in segments:
        out_res = impl(a_p, b_p, moduli, *blocks)[:, :M, :N]
        rows = mset.partial_decode(out_res, cid)[None]   # (1, M, N)
        if witness:
            rows = jnp.concatenate(
                [rows, mset.partial_witnesses(out_res, cid)], axis=0)
        parts.append(rows)

    buf = jax.lax.psum(jnp.stack(parts, axis=0), tp)     # (segs, 1+r, M, N)
    total = jnp.zeros((M, N), jnp.int32)
    for s in range(len(parts)):
        if witness:
            total = total + mset.corrected_fold(buf[s, 0], buf[s, 1:])
        else:
            total = total + mset.fold_partials(buf[s, 0])
    return total


# ---------------------------------------------------------------------------
# sdrns — fused signed-digit residue matmul (Eq. 2 in one kernel).
# ---------------------------------------------------------------------------


def _sdrns_digit_width(mset: ModuliSet) -> int:
    from repro.numerics.tensor import _digit_width

    return _digit_width(mset)


def _choose_digit_blocks(M: int, N: int) -> tuple[int, int]:
    """Small tiles: the digit axis multiplies VMEM footprint by n^2."""
    bm = 32 if M >= 32 else _round_up(M, 8)
    bn = 32 if N >= 32 else _round_up(N, 8)
    return bm, bn


# Decode threshold: at or below this M the sd path switches to the
# matvec-style schedule (whole M block + K segment resident, grid (C, N/bn)).
DECODE_M = 8


def _choose_decode_blocks(M: int, N: int) -> tuple[int, int]:
    """Decode-shaped tiles: skinny M (padded to sublanes), wide N columns.

    With bm <= 8 the n^2-scaled partial-product stack shrinks 4x vs the
    matmul tiles, which buys lane-width (128) column tiles at the same VMEM
    budget — fewer grid steps over N for the single-token step.
    """
    bm = _round_up(M, 8)
    bn = 128 if N >= 128 else _round_up(N, 8)
    return bm, bn


# Per-grid-step budget for the kernel's partial-product stack (int8 bytes);
# a few MiB leaves VMEM room for operands and double buffering.
_PP_BUDGET_BYTES = 4 * 1024 * 1024


def _wrap_signs(mset: ModuliSet) -> jax.Array:
    return jnp.asarray([WRAP_SIGNS[k] for k, _ in mset.kinds], jnp.int32)


register_impl(
    "sdrns_matmul", "pallas",
    lambda ad, bd, mset, bm, bn: sdrns_matmul_pallas(
        ad, bd, _wrap_signs(mset), bm=bm, bn=bn, interpret=False))
register_impl(
    "sdrns_matmul", "interpret",
    lambda ad, bd, mset, bm, bn: sdrns_matmul_pallas(
        ad, bd, _wrap_signs(mset), bm=bm, bn=bn, interpret=True))


def _sdrns_matmul_ref_impl(ad, bd, mset, bm, bn):
    from repro.kernels.ref import sdrns_matmul_ref

    return sdrns_matmul_ref(ad, bd, mset)


register_impl("sdrns_matmul", "ref", _sdrns_matmul_ref_impl)

# Decode-shaped variant: same kernel body, matvec schedule (bm rides whole).
register_impl(
    "sdrns_matvec", "pallas",
    lambda ad, bd, mset, bm, bn: sdrns_matvec_pallas(
        ad, bd, _wrap_signs(mset), bn=bn, interpret=False))
register_impl(
    "sdrns_matvec", "interpret",
    lambda ad, bd, mset, bm, bn: sdrns_matvec_pallas(
        ad, bd, _wrap_signs(mset), bn=bn, interpret=True))
register_impl("sdrns_matvec", "ref", _sdrns_matmul_ref_impl)


def _sdrns_matmul_cost_impl(ad, bd, mset, bm, bn):
    """Dry-run cost oracle for the fused SD kernel.

    The exact digit-level ref materializes an O(M*K*N*n^2) partial-product
    stack — meaningless cost numbers and unlowerable at production shapes.
    This backend computes the same *decoded* result with the kernel's
    useful-work envelope (C channel-wise int32 matmuls + digit recode):
    digit planes -> residues -> matmul -> centered residues -> digits.
    Decoded values are exact; the digit *vectors* are canonical rather than
    kernel-identical, so this backend exists for compile/cost analysis
    (launch/dryrun.py), not for bit-exactness tests.
    """
    a_res = sd.to_int(ad)                                # (C, M, K) int32
    b_res = sd.to_int(bd)
    acc = jnp.einsum("cmk,ckn->cmn", a_res, b_res)
    return sd.from_int(mset.center(acc), bd.shape[-1])


register_impl("rns_matmul", "cost", _rns_matmul_ref_impl)
register_impl("sdrns_matmul", "cost", _sdrns_matmul_cost_impl)
register_impl("sdrns_matvec", "cost", _sdrns_matmul_cost_impl)


# Array-parameterized siblings for the channel-parallel shard_map body:
# moduli and wrap signs arrive as runtime (C_loc,) operands (gathered by a
# traced ``axis_index``).  pallas/interpret are the unchanged fused kernels
# — they already take wrap_signs as a runtime operand.  ref/cost compute
# the same *decoded* residues against the moduli array (digit vectors are
# canonical rather than kernel-identical, same contract as the cost
# backend above — the channel body decodes immediately, so the decoded
# values stay exact).
register_impl(
    "sdrns_matmul_planes", "pallas",
    lambda ad, bd, moduli, ws, bm, bn: sdrns_matmul_pallas(
        ad, bd, ws, bm=bm, bn=bn, interpret=False))
register_impl(
    "sdrns_matmul_planes", "interpret",
    lambda ad, bd, moduli, ws, bm, bn: sdrns_matmul_pallas(
        ad, bd, ws, bm=bm, bn=bn, interpret=True))
register_impl(
    "sdrns_matvec_planes", "pallas",
    lambda ad, bd, moduli, ws, bm, bn: sdrns_matvec_pallas(
        ad, bd, ws, bn=bn, interpret=False))
register_impl(
    "sdrns_matvec_planes", "interpret",
    lambda ad, bd, moduli, ws, bm, bn: sdrns_matvec_pallas(
        ad, bd, ws, bn=bn, interpret=True))


def _sdrns_planes_cost_impl(ad, bd, moduli, ws, bm, bn):
    acc = jnp.einsum("cmk,ckn->cmn", sd.to_int(ad), sd.to_int(bd))
    m = moduli.reshape(-1, 1, 1)
    r = jnp.remainder(acc, m)
    return sd.from_int(jnp.where(r > m // 2, r - m, r), bd.shape[-1])


register_impl("sdrns_matmul_planes", "ref", _sdrns_planes_cost_impl)
register_impl("sdrns_matmul_planes", "cost", _sdrns_planes_cost_impl)
register_impl("sdrns_matvec_planes", "ref", _sdrns_planes_cost_impl)
register_impl("sdrns_matvec_planes", "cost", _sdrns_planes_cost_impl)


def encode_sd_planes(w: jax.Array, mset: ModuliSet) -> jax.Array:
    """Integer values (..., K, N) -> SD digit planes (..., C, K, N, n) int8.

    The quantize-once / convert-once half of the serving lifecycle: centered
    residues per channel, each encoded as an n-digit SD vector.  Channel and
    digit axes land around the matmul dims so stacked-layer leaves slice
    cleanly under ``jax.lax.scan``.
    """
    n = _sdrns_digit_width(mset)
    res = mset.to_residues(w.astype(jnp.int32), centered=True)  # (C, ..., K, N)
    return sd.from_int(jnp.moveaxis(res, 0, -3), n)


def sdrns_run(a, b_dig, *, mset, max_abs_a, max_abs_b, backend,
              force_matvec=False, shard=None):
    """Shared runner over pre-encoded B digit planes.

    Routes decode shapes (M <= DECODE_M, or ``force_matvec`` — the
    ``sd_matvec`` layout tag) to the matvec schedule; every public surface
    lands here with identical segmentation and tiling, so digit outputs are
    bit-identical across them.

    ``shard``: a :func:`tp_shard_plan` — shard_maps this body over the
    mesh (see :func:`rns_run`); the matvec schedule composes the same way
    (its grid is (C, N/bn), so column-sharding N just shortens the grid).
    """
    if shard is not None:
        if shard[0] == "chan":
            body = functools.partial(
                _sdrns_channel_body, mset=mset, max_abs_a=max_abs_a,
                max_abs_b=max_abs_b, backend=backend,
                force_matvec=force_matvec, tp=shard[3])
            return _channel_mapped(body, shard, sd_planes=True)(a, b_dig)
        body = functools.partial(sdrns_run, mset=mset, max_abs_a=max_abs_a,
                                 max_abs_b=max_abs_b, backend=backend,
                                 force_matvec=force_matvec)
        return _shard_mapped(body, shard, sd_planes=True)(a, b_dig)
    n = _sdrns_digit_width(mset)
    M, K = a.shape
    C, K2, N, n2 = b_dig.shape
    assert (K, n) == (K2, n2), (a.shape, b_dig.shape)

    if force_matvec or M <= DECODE_M:
        op = "sdrns_matvec"
        bm, bn = _choose_decode_blocks(M, N)
    else:
        op = "sdrns_matmul"
        bm, bn = _choose_digit_blocks(M, N)
    impl = get_impl(op, backend)

    segs = segment_count(K, max_abs_a, max_abs_b, mset)
    seg_len = (K + segs - 1) // segs
    # VMEM bound: the kernel materializes an (n, bm, k, bn, n) int8 PP
    # stack per grid step, so the dynamic-range segmentation alone is not a
    # memory bound — cap the K slice to keep that stack within budget.
    k_cap = max(_PP_BUDGET_BYTES // (n * n * bm * bn), 1)
    seg_len = min(seg_len, k_cap)
    segs = (K + seg_len - 1) // seg_len

    Mp, Np = _round_up(M, bm), _round_up(N, bn)

    total = jnp.zeros((M, N), jnp.int32)
    for s in range(segs):
        lo = s * seg_len
        hi = min(lo + seg_len, K)
        a_s = a[:, lo:hi].astype(jnp.int32)
        # centered residues -> SD digit planes (zero rows/cols pad to tiles;
        # the zero digit vector is the zero residue, so padding is inert)
        a_res = mset.to_residues(a_s, centered=True)        # (C, M, ks)
        ad = jnp.zeros((C, Mp, hi - lo, n), jnp.int8)
        ad = ad.at[:, :M].set(sd.from_int(a_res, n))
        bd = jnp.zeros((C, hi - lo, Np, n), jnp.int8)
        bd = bd.at[:, :, :N].set(b_dig[:, lo:hi])
        out_dig = impl(ad, bd, mset, bm, bn)                # (C, Mp, Np, n)
        total = total + sdrns.sdrns_decode(out_dig[:, :M, :N], mset)
    return total


def _sdrns_channel_body(a, b_dig, *, mset, max_abs_a, max_abs_b, backend,
                        force_matvec, tp):
    """Channel-parallel sdrns schedule (inside a ``("chan", ...)`` shard_map).

    Mirrors :func:`_rns_channel_body` over the local ``(C_loc, K, N, n)``
    digit planes: the fused kernel runs per resident channel, the output
    digit vectors decode locally to a residue representative
    (``sd.to_int`` — :meth:`ModuliSet.partial_decode` canonicalizes, so
    the representative choice cannot change the fold), and one stacked
    psum + per-segment ``fold_partials`` replaces the cross-channel
    gather.  sdrns carries no witness channels (the fault-tolerant path is
    rns), so there is no corrected fold here.
    """
    n = _sdrns_digit_width(mset)
    M, K = a.shape
    C_loc, K2, N, n2 = b_dig.shape
    assert (K, n) == (K2, n2), (a.shape, b_dig.shape)

    if force_matvec or M <= DECODE_M:
        op = "sdrns_matvec_planes"
        bm, bn = _choose_decode_blocks(M, N)
    else:
        op = "sdrns_matmul_planes"
        bm, bn = _choose_digit_blocks(M, N)
    impl = get_impl(op, backend)

    cid = _channel_ids(tp, C_loc)
    moduli = jnp.take(jnp.asarray(mset.moduli, jnp.int32), cid)
    ws = jnp.take(_wrap_signs(mset), cid)

    segs = segment_count(K, max_abs_a, max_abs_b, mset)
    seg_len = (K + segs - 1) // segs
    k_cap = max(_PP_BUDGET_BYTES // (n * n * bm * bn), 1)
    seg_len = min(seg_len, k_cap)
    segs = (K + seg_len - 1) // seg_len

    Mp, Np = _round_up(M, bm), _round_up(N, bn)

    parts = []
    for s in range(segs):
        lo = s * seg_len
        hi = min(lo + seg_len, K)
        a_s = a[:, lo:hi].astype(jnp.int32)
        a_res = mset.to_residues(a_s, centered=True)     # (C, M, ks)
        a_res = jnp.take(a_res, cid, axis=0)
        ad = jnp.zeros((C_loc, Mp, hi - lo, n), jnp.int8)
        ad = ad.at[:, :M].set(sd.from_int(a_res, n))
        bd = jnp.zeros((C_loc, hi - lo, Np, n), jnp.int8)
        bd = bd.at[:, :, :N].set(b_dig[:, lo:hi])
        out_dig = impl(ad, bd, moduli, ws, bm, bn)       # (C_loc, Mp, Np, n)
        vals = sd.to_int(out_dig[:, :M, :N])             # residue reps
        parts.append(mset.partial_decode(vals, cid))

    buf = jax.lax.psum(jnp.stack(parts, axis=0), tp)     # (segs, M, N)
    total = jnp.zeros((M, N), jnp.int32)
    for s in range(segs):
        total = total + mset.fold_partials(buf[s])
    return total


# ---------------------------------------------------------------------------
# sd_add — batched carry-free SD addition.
# ---------------------------------------------------------------------------


def sd_add_run(x: jax.Array, y: jax.Array, *, kind: str,
               interpret: bool | None = None) -> jax.Array:
    """Batched carry-free SD addition via the Pallas kernel.

    x, y: (..., n) int8 digit tensors (LSB first).  Returns same shape
    ((..., n+1) for kind="plain").
    """
    n = x.shape[-1]
    lead = x.shape[:-1]
    B = int(np.prod(lead)) if lead else 1
    out_n = n + 1 if kind == "plain" else n
    nd = _round_up(max(out_n, 128), 128)
    bb = 256 if B >= 256 else _round_up(B, 8)
    Bp = _round_up(B, bb)

    xp = jnp.zeros((Bp, nd), jnp.int8).at[:B, :n].set(x.reshape(B, n))
    yp = jnp.zeros((Bp, nd), jnp.int8).at[:B, :n].set(y.reshape(B, n))
    out = sd_add_pallas(xp, yp, kind=kind, n=n, bb=bb, interpret=interpret)
    return out[:B, :out_n].reshape(*lead, out_n)
