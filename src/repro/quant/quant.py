"""Symmetric integer quantization feeding the RNS arithmetic backend.

The RNS backend computes *exact* integer matmuls; quantization is the bridge
from floats into the integer ring.  Magnitude bounds chosen here are what let
``repro.numerics.segment_count`` prove the exact result fits the moduli set's
dynamic range — the quantizer and the number system are co-designed
(paper §II: "applications that require frequent arithmetic operations within
a defined numerical range").
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["quantize_symmetric", "dequantize", "qmax_for_bits"]


def qmax_for_bits(bits: int) -> int:
    """Symmetric range: int4 -> 7, int8 -> 127 (we exclude -2^(b-1) so that
    centered-residue bounds are symmetric)."""
    return (1 << (bits - 1)) - 1


def quantize_symmetric(
    x: jax.Array, bits: int, *, axis: int | tuple[int, ...] | None = None
) -> tuple[jax.Array, jax.Array]:
    """Quantize to signed integers with a power-agnostic symmetric scale.

    Args:
      x: float tensor.
      bits: target bit width (values in [-qmax, qmax]).
      axis: reduction axis/axes for the scale (None = per-tensor scale;
        e.g. axis=0 on a (d_in, d_out) weight = per-output-channel scales).
    Returns:
      (q, scale): q int32 in [-qmax, qmax]; scale broadcastable to x so that
      ``q * scale ~= x``.
    """
    scale = symmetric_scale(x, bits, axis=axis)
    return quantize_with_scale(x, scale, bits), scale.astype(jnp.float32)


def symmetric_scale(x: jax.Array, bits: int, *,
                    axis: int | tuple[int, ...] | None = None) -> jax.Array:
    """The scale of :func:`quantize_symmetric`, in ``x``'s dtype."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=axis is not None)
    return jnp.maximum(amax, 1e-8) / qmax_for_bits(bits)


def quantize_with_scale(x: jax.Array, scale: jax.Array,
                        bits: int) -> jax.Array:
    """Integer codes of ``x`` under a given symmetric ``scale``."""
    qmax = qmax_for_bits(bits)
    return jnp.clip(jnp.round(x / scale), -qmax, qmax).astype(jnp.int32)


def dequantize(q: jax.Array, scale: jax.Array) -> jax.Array:
    return q.astype(jnp.float32) * scale
