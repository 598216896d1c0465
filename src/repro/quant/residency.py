"""Residue-resident weight preparation — quantize once, convert once, serve many.

The serving lifecycle of a quantized weight under the (SD-)RNS systems has
three stages the paper amortizes once but a naive implementation repeats on
every matmul call:

1. **quantize** — float weight -> symmetric int codes + per-output-channel
   scale;
2. **forward-convert** — int codes -> centered residue planes (rns) or SD
   digit planes (sdrns);
3. **serve** — every prefill/decode matmul consumes the planes directly.

:func:`prepare_weight` performs stages 1–2 eagerly through
:func:`repro.numerics.encode`, producing a typed
:class:`~repro.numerics.ResidueTensor` whose leaves (planes + scale) ride
``jax.lax.scan``, checkpointing and jit signatures unchanged, and whose
static metadata (moduli set, layout, qbits, magnitude bound) lets
``models.linear.dense`` and ``models.moe.moe`` dispatch with a plain
``isinstance`` check — no dict-key sniffing.  :func:`prepare_dense` is the
``{"w": float} -> {"w": ResidueTensor}`` form the parameter-tree walk in
``models/api.py`` applies.

Prepared parameters are inference-only: the float weight is dropped (that
is the memory/bandwidth point), so there is nothing to backpropagate into.
Training keeps the unprepared form with its straight-through estimator.

Trace counters
--------------
``record``/``counters`` count, *at trace time*, how often the per-call
weight-encode path runs vs the resident path.  ``models.linear`` and
``models.moe`` record ``weight_quantize``/``weight_forward_convert`` when a
matmul re-derives its weight planes and ``weight_reuse`` when it consumes
resident ones — so a test can trace a decode step and assert the hot path
performs zero weight conversions (tests/test_residency.py).
"""
from __future__ import annotations

import collections
import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro import numerics as nx
from repro.core.moduli import P21, ModuliSet
from repro.numerics import ResidueTensor
from repro.parallel import sharding
from repro.quant.quant import quantize_with_scale, symmetric_scale

__all__ = [
    "SYSTEM_LAYOUT",
    "prepare_weight",
    "prepare_dense",
    "prepared_kind",
    "dequantize_weight",
    "record",
    "reset_counters",
    "counters",
]

# model-level number system -> ResidueTensor layout tag (and back)
SYSTEM_LAYOUT = {"rns": "rns", "sdrns": "sd"}
_LAYOUT_SYSTEM = {"rns": "rns", "sd": "sdrns", "sd_matvec": "sdrns"}


# ---------------------------------------------------------------------------
# Trace-time conversion counters.
# ---------------------------------------------------------------------------

_COUNTS: collections.Counter = collections.Counter()


def record(event: str) -> None:
    """Count one trace-time occurrence of ``event`` (see module docstring)."""
    _COUNTS[event] += 1


def reset_counters() -> None:
    _COUNTS.clear()


def counters() -> dict[str, int]:
    """Snapshot of the per-event trace counts since the last reset."""
    return dict(_COUNTS)


# ---------------------------------------------------------------------------
# Prepared parameter form.
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("spec",))
def _encode_scaled(w: jax.Array, scale: jax.Array,
                   spec: nx.EncodeSpec) -> ResidueTensor:
    """Quantize + forward-convert as one fused program per weight shape.

    Eager encoding would materialize the int32 codes and all C int32
    residue channels of a whole layer stack before narrowing them to int8
    — several GB at published widths.  The scale arrives computed outside
    (eagerly, as the per-call path computes it), so the codes are
    bit-identical to :func:`repro.quant.quant.quantize_symmetric`.
    """
    q = quantize_with_scale(w, scale, spec.qbits)
    return nx.encode(q, spec, scale=scale)


def prepare_weight(
    w: jax.Array,
    *,
    system: str,
    bits: int = 4,
    mset: ModuliSet = P21,
    roles: Any | None = None,
) -> ResidueTensor:
    """Float weight (..., K, N) -> residue-resident :class:`ResidueTensor`.

    Quantization matches the per-call path exactly: symmetric, per output
    channel (reduction over the K axis, ``axis=-2`` — identical to the
    ``axis=0`` the 2-D hot path uses, but stack-safe).  The resulting digit
    or residue planes are therefore bit-identical to what the unprepared
    path derives on every call, which is what makes the swap transparent.

    Leading axes of ``w`` (layer stacks, expert stacks) are preserved.

    Sharding: when a :class:`~repro.parallel.sharding.ShardCtx` is
    installed, the prepared planes/scale leaves are placed onto their
    role-derived ``NamedSharding``\\ s.  ``roles`` are value roles for the
    represented ``(*stack, K, N)`` shape; the default is the generic dense
    rule (stack replicated, FSDP on K, TP on N).  Model-level preparation
    (``models/api.py::prepare_params``) instead applies the *name-based*
    rules tree-wide after the walk (passing ``roles=False`` here to skip
    the per-weight placement), so per-weight roles matter only for direct
    callers.  Sharding is bit-transparent: placement never changes plane
    values, only their device layout.
    """
    if system not in SYSTEM_LAYOUT:
        raise ValueError(
            f"prepare_weight: system must be 'rns' or 'sdrns', got {system!r}"
        )
    if isinstance(w, ResidueTensor):
        # idempotent only when the existing residency matches the request —
        # silently keeping planes prepared under other metadata would
        # surface much later (or never) as wrong arithmetic
        if (_LAYOUT_SYSTEM[w.layout] != system or w.qbits != bits
                or w.mset.moduli != mset.moduli):
            raise ValueError(
                f"weight already residue-resident as (system="
                f"{_LAYOUT_SYSTEM[w.layout]!r}, bits={w.qbits}, moduli="
                f"{w.mset.moduli}) — cannot re-prepare for (system="
                f"{system!r}, bits={bits}, moduli={mset.moduli}); the "
                "float weight was dropped at prepare time"
            )
        return w
    if w.ndim < 2:
        raise ValueError(f"dense weight must be at least 2-D, got {w.shape}")
    spec = nx.EncodeSpec(layout=SYSTEM_LAYOUT[system], mset=mset, qbits=bits)
    w = w.astype(jnp.float32)
    scale = symmetric_scale(w, bits, axis=spec.quant_axis)
    t = _encode_scaled(w, scale, spec)
    ctx = sharding.get_shard_ctx()
    if ctx is not None and roles is not False:
        if roles is None:  # generic dense rule: FSDP on K, TP on N
            roles = [None] * (w.ndim - 2) + ["dp", "tp"]
        t = sharding.shard_residue_tensor(t, roles, ctx)
    return t


def prepare_dense(
    params: dict[str, jax.Array],
    *,
    system: str,
    bits: int = 4,
    mset: ModuliSet = P21,
    roles: Any | None = None,
) -> dict[str, Any]:
    """``{"w": float}`` -> ``{"w": ResidueTensor}`` for ``system``."""
    return {"w": prepare_weight(params["w"], system=system, bits=bits,
                                mset=mset, roles=roles)}


def prepared_kind(params: Any) -> str | None:
    """Which system a parameter node is resident for, or ``None``.

    Accepts a ``{"w": ResidueTensor}`` dense dict or a bare tensor.
    """
    w = params.get("w") if isinstance(params, dict) else params
    if isinstance(w, ResidueTensor):
        return _LAYOUT_SYSTEM[w.layout]
    return None


def dequantize_weight(params: dict[str, Any] | ResidueTensor) -> jax.Array:
    """Reconstruct the float weight a prepared node encodes.

    Exact reverse conversion of the planes times the quantization scale —
    the closest float form available once the original weight is dropped;
    used for diagnostics and for comparing against the unprepared path.
    """
    w = params["w"] if isinstance(params, dict) else params
    if not isinstance(w, ResidueTensor):
        raise TypeError(f"expected a prepared node, got {type(w)}")
    return nx.decode(w)
