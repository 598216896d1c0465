"""Plain reference of a served dense decoder, written from the semantics a
configuration file states, and importing nothing of the program.

What a configuration states, and so what this computes:

* the residual stream and every matmul output in ``compute_dtype``
  (bfloat16 at served sizes), RMSNorm's variance in float32, RoPE in
  float32;
* a matmul either on ``compute_dtype`` inputs with float32 accumulation
  (``system`` bns),
  or on symmetric integer codes (``system`` rns, ``rns_bits`` = 4): one
  scale per token of the activation and one per output column of the
  weight, an exact integer product, and the two scales applied after;
* attention over the prompt (the prefill) on bfloat16 keys and values, and
  attention of every later position (a decode step) on the keys and values
  as the KV pages store them: rounded to bfloat16, and for ``rns8r`` pages
  then held as 8-bit codes with one scale per token and head
  (``kv_qmax`` = 119);
* softmax and P.V in float32, logits in float32.

``Precision`` also describes the control: the same model one step lower
(3-bit codes for 4-bit ones, fp8 e4m3 inputs for bfloat16 ones).
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from harness.weights import Dims

BF16, F32 = jnp.bfloat16, jnp.float32
HI = jax.lax.Precision.HIGHEST


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


def dense(x, w, p: Precision):
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
    """What a page stores for ``x`` (S, kv_heads, hd): ``x`` rounded to
    bfloat16, and with ``qmax`` set its symmetric codes per (token, head)
    times their scale."""
    x = x.astype(BF16).astype(F32)
    if not qmax:
        return x
    amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    s = jnp.maximum(amax, 1e-8) / qmax
    return jnp.clip(jnp.round(x / s), -qmax, qmax) * s


def attend(q, k, v):
    """Causal GQA attention; q (S, H, hd), k and v (S, Kv, hd)."""
    S, H, hd = q.shape
    kv = k.shape[1]
    qg = q.astype(F32).reshape(S, kv, H // kv, hd)
    s = jnp.einsum("qkgd,tkd->kgqt", qg, k.astype(F32), precision=HI)
    s = s / jnp.sqrt(F32(hd))
    mask = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
    s = jnp.where(mask, s, F32(-1e30))
    pr = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("kgqt,tkd->qkgd", pr, v.astype(F32), precision=HI)
    return o.reshape(S, H * hd)


@functools.partial(jax.jit, static_argnames=("d", "p"))
def forward(w: dict, tokens, n_prompt, *, d: Dims, p: Precision):
    """Teacher-forced pass over one request (``tokens`` = prompt and served
    tokens, padded).  Rows before ``n_prompt`` attend as the prefill does,
    the rest as decode steps do.  Returns the logits (S, vocab) float32 and
    each layer's page values of K and V, (L, S, kv_heads, hd)."""
    S = tokens.shape[0]
    c = jnp.dtype(p.compute)
    pos = jnp.arange(S, dtype=jnp.int32)
    prefill_row = (pos < n_prompt)[:, None]
    x = w["embed"].astype(c)[tokens]

    def proj(h, wt, heads=None):
        y = dense(h, wt, p).astype(c)
        return y if heads is None else y.reshape(S, heads, d.head_dim)

    def layer(x, lw):
        hn = rmsnorm(x, lw["attn_norm"], d.norm_eps)
        q = proj(hn, lw["wq"], d.heads)
        k = proj(hn, lw["wk"], d.kv_heads)
        v = proj(hn, lw["wv"], d.kv_heads)
        q, k = rope(q, pos, d.rope_theta), rope(k, pos, d.rope_theta)
        kp, vp = page_values(k, p.kv_qmax), page_values(v, p.kv_qmax)
        o = jnp.where(prefill_row, attend(q, k, v), attend(q, kp, vp))
        x = x + proj(o.astype(c), lw["wo"])
        hn = rmsnorm(x, lw["mlp_norm"], d.norm_eps)
        g, u = proj(hn, lw["w_gate"]), proj(hn, lw["w_up"])
        h = jax.nn.silu(g.astype(F32)).astype(c) * u
        x = x + proj(h, lw["w_down"])
        return x, (kp, vp)

    names = ("attn_norm", "wq", "wk", "wv", "wo", "mlp_norm", "w_gate",
             "w_up", "w_down")
    x, (kp, vp) = jax.lax.scan(layer, x, {n: w[n] for n in names})
    xf = rmsnorm(x, w["final_norm"], d.norm_eps)
    logits = dense(xf, w["embed"].T, p)
    return logits, kp, vp


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
    (..., lanes, kv_heads, hd) uint8 through the CRT table of the pair
    ``moduli``, times ``scale`` (..., kv_heads, 1)."""
    table = crt_table(tuple(moduli))
    return table[planes[..., 0, :, :]].astype(np.float32) * scale
