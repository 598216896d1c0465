"""Arithmetic of the plain reference that every family shares, written from
the semantics a configuration file states, and importing nothing of the
program.

What a configuration states, and so what a family's reference computes
with these pieces:

* the residual stream and every matmul output in ``compute_dtype``
  (bfloat16 at served sizes), RMSNorm's variance in float32, RoPE in
  float32;
* a matmul either on ``compute_dtype`` inputs with float32 accumulation
  (``system`` bns),
  or on symmetric integer codes (``system`` rns, ``rns_bits`` = 4): one
  scale per token of the activation and one per output column of the
  weight, an exact integer product, and the two scales applied after;
* cache rows as the KV pages store them: rounded to bfloat16, and for
  ``rns8r`` pages then held as 8-bit codes with one scale per row
  (``kv_qmax`` = 119);
* softmax and P.V in float32, logits in float32.

``Precision`` also describes the control: the same model one step lower
(3-bit codes for 4-bit ones, fp8 e4m3 inputs for bfloat16 ones).

Weights are data: a family makes them from the seed (``root_key``), hands
them to the program, and makes them again for the reference after the
window.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

BF16, F32 = jnp.bfloat16, jnp.float32
HI = jax.lax.Precision.HIGHEST


def root_key(seed: int) -> jax.Array:
    """A PRNG key for any whole-number seed (more bits than 32 included)."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


@dataclasses.dataclass(frozen=True)
class Precision:
    matmul: str            # "float" | "fp8" | "int"
    bits: int = 0          # integer code width for matmul == "int"
    kv_qmax: int = 0       # page code bound; 0 = bfloat16 pages
    compute: str = "bfloat16"


def stated(config: dict) -> Precision:
    """The precision a configuration file states."""
    kv = int(config["kv_qmax"])
    compute = config["compute_dtype"]
    if config["system"] == "rns":
        return Precision("int", int(config["rns_bits"]), kv, compute)
    if config["system"] == "bns":
        return Precision("float", 0, kv, compute)
    raise ValueError(f"no reference for system {config['system']!r}")


def control(config: dict) -> Precision:
    """One step below the stated precision: the control."""
    p = stated(config)
    if p.matmul == "int":
        return dataclasses.replace(p, bits=p.bits - 1)
    return dataclasses.replace(p, matmul="fp8")


def rmsnorm(x, scale, eps):
    var = jnp.mean(jnp.square(x.astype(F32)), axis=-1, keepdims=True)
    inv = jax.lax.rsqrt(var + eps).astype(x.dtype)
    return x * inv * scale.astype(x.dtype)


def quantize(x, bits: int, axis: int):
    """Symmetric codes in [-qmax, qmax] and the scale, along ``axis``."""
    qmax = (1 << (bits - 1)) - 1
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.maximum(amax, 1e-8) / qmax
    return jnp.clip(jnp.round(x / scale), -qmax, qmax), scale


def _fp8(x, axis: int):
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    s = jnp.maximum(amax, 1e-8) / 448.0
    return (x / s).astype(jnp.float8_e4m3fn).astype(BF16), s


def matmul(x, w, p: Precision):
    """(..., K) @ (K, N) float32 -> (..., N) float32."""
    if p.matmul == "float":
        c = jnp.dtype(p.compute)
        return jnp.matmul(x.astype(c), w.astype(c),
                          preferred_element_type=F32, precision=HI)
    if p.matmul == "fp8":
        xq, sx = _fp8(x.astype(F32), -1)
        wq, sw = _fp8(w, 0)
        return jnp.matmul(xq, wq, preferred_element_type=F32) * sx * sw
    qx, sx = quantize(x.astype(F32), p.bits, -1)
    qw, sw = quantize(w, p.bits, 0)
    acc = jnp.matmul(qx.astype(jnp.int8), qw.astype(jnp.int8),
                     preferred_element_type=jnp.int32)
    return acc.astype(F32) * sx * sw


def rope(x, positions, theta):
    """Rotate-half RoPE; x (S, heads, hd) bfloat16."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=F32) / half)
    ang = positions[:, None].astype(F32) * freqs
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    x1, x2 = x[..., :half].astype(F32), x[..., half:].astype(F32)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


def page_values(x, qmax: int):
    """What a page stores for ``x`` (S, groups, width): ``x`` rounded to
    bfloat16, and with ``qmax`` set its symmetric codes per (token, group)
    times their scale."""
    x = x.astype(BF16).astype(F32)
    if not qmax:
        return x
    amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    s = jnp.maximum(amax, 1e-8) / qmax
    return jnp.clip(jnp.round(x / s), -qmax, qmax) * s


@functools.lru_cache(maxsize=None)
def crt_table(moduli: tuple[int, int]) -> np.ndarray:
    """Value of every packed byte of a (odd, power-of-two) residue pair:
    the low field holds a residue mod ``moduli[0]``, the high field one mod
    ``moduli[1]``, each a two's-complement field of just enough bits."""
    m0, m1 = moduli
    b0, b1 = (m0 - 1).bit_length(), (m1 - 1).bit_length()
    table = np.zeros(256, np.int32)
    span = m0 * m1
    for byte in range(256):
        f0, f1 = byte & ((1 << b0) - 1), (byte >> b0) & ((1 << b1) - 1)
        f0 -= (f0 >> (b0 - 1)) << b0      # sign of the field
        f1 -= (f1 >> (b1 - 1)) << b1
        hits = [v for v in range(-(span // 2), span - span // 2)
                if (v - f0) % m0 == 0 and (v - f1) % m1 == 0]
        table[byte] = hits[0]
    return table


def decode_pages(planes: np.ndarray, scale: np.ndarray,
                 moduli: tuple[int, int]) -> np.ndarray:
    """Stored values of residue pages: lane 0 of ``planes``
    (..., lanes, groups, width) uint8 through the CRT table of the pair
    ``moduli``, times ``scale`` (..., groups, 1)."""
    table = crt_table(tuple(moduli))
    return table[planes[..., 0, :, :]].astype(np.float32) * scale
