"""The dense pre-norm decoder with tied embeddings: grouped-query attention
with RoPE and a SwiGLU MLP in every layer, the logits from the embedding
table (the program's ``family == "dense"`` with ``mlp_type == "swiglu"``
and no qk-norm).

Its reference attends over the prompt (the prefill) on bfloat16 keys and
values, and from every later position (a decode step) on the keys and
values as the KV pages store them.  Its cache rows are K and V
(``parts`` 2), one row per KV head (``groups``) of ``head_dim``.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from harness.arch.reference import (F32, HI, Precision, control,  # noqa: F401
                                    decode_pages, matmul, page_values,
                                    rmsnorm, root_key, rope, stated)


@dataclasses.dataclass(frozen=True)
class Dims:
    layers: int
    d_model: int
    d_ff: int
    heads: int
    kv_heads: int
    head_dim: int
    vocab: int
    rope_theta: float
    norm_eps: float

    @property
    def q_width(self) -> int:
        return self.heads * self.head_dim

    @property
    def kv_width(self) -> int:
        return self.kv_heads * self.head_dim


def dims_of(config: dict) -> Dims:
    """Model shapes from a configuration file (Hugging Face key names)."""
    return Dims(layers=int(config["num_hidden_layers"]),
                d_model=int(config["hidden_size"]),
                d_ff=int(config["intermediate_size"]),
                heads=int(config["num_attention_heads"]),
                kv_heads=int(config["num_key_value_heads"]),
                head_dim=int(config["head_dim"]),
                vocab=int(config["vocab_size"]),
                rope_theta=float(config["rope_theta"]),
                norm_eps=float(config["rms_norm_eps"]))


# Width keys of a configuration file and the program's ArchConfig field
# that must hold the same number.
_WIDTHS = {"hidden_size": "d_model", "intermediate_size": "d_ff",
           "num_attention_heads": "n_heads", "num_key_value_heads": "n_kv",
           "head_dim": "hd", "vocab_size": "vocab",
           "num_hidden_layers": "n_layers",
           "tie_word_embeddings": "tie_embeddings",
           "compute_dtype": "compute_dtype"}


def program_config(config: dict):
    """The program's ArchConfig for a configuration file, with its depth
    and RoPE base; raises ``ValueError`` where the file and the program
    disagree."""
    from repro.configs import get_config

    cfg = dataclasses.replace(get_config(config["arch"]),
                              n_layers=int(config["num_hidden_layers"]),
                              rope_theta=float(config["rope_theta"]))
    bad = {k: (config[k], getattr(cfg, f)) for k, f in _WIDTHS.items()
           if config[k] != getattr(cfg, f)}
    if cfg.family != "dense" or cfg.mlp_type != "swiglu" or cfg.qk_norm:
        bad["block"] = ("dense swiglu, no qk-norm",
                        (cfg.family, cfg.mlp_type, cfg.qk_norm))
    if bad:
        raise ValueError(f"configuration file and program disagree: {bad}")
    return cfg


# name -> (shape, std); layer tensors carry a leading L axis
def _shapes(d: Dims):
    def proj(din, dout):
        return ((d.layers, din, dout), (2.0 / (din + dout)) ** 0.5)
    return {
        "wq": proj(d.d_model, d.q_width),
        "wk": proj(d.d_model, d.kv_width),
        "wv": proj(d.d_model, d.kv_width),
        "wo": proj(d.q_width, d.d_model),
        "w_gate": proj(d.d_model, d.d_ff),
        "w_up": proj(d.d_model, d.d_ff),
        "w_down": proj(d.d_ff, d.d_model),
    }


@functools.partial(jax.jit, static_argnums=(1,))
def _make(key, d: Dims):
    shapes = _shapes(d)
    keys = jax.random.split(key, len(shapes) + 1)
    w = {"embed": jax.random.normal(keys[0], (d.vocab, d.d_model),
                                    jnp.float32) * 0.02}
    for k, (name, (shape, std)) in zip(keys[1:], shapes.items()):
        w[name] = jax.random.normal(k, shape, jnp.float32) * std
    w["attn_norm"] = jnp.ones((d.layers, d.d_model), jnp.float32)
    w["mlp_norm"] = jnp.ones((d.layers, d.d_model), jnp.float32)
    w["final_norm"] = jnp.ones((d.d_model,), jnp.float32)
    return w


def make_weights(seed: int, d: Dims) -> dict:
    """Float32 weights of run ``seed``, made on the device in one call,
    with the usual initialisation of a pre-norm decoder (embedding
    N(0, 0.02^2), each projection N(0, 2 / (d_in + d_out)), norm scales 1),
    so activations have the scale of a freshly initialised model."""
    return _make(jax.random.fold_in(root_key(seed), 0), d)


def program_params(w: dict) -> dict:
    """The same arrays in the program's parameter tree (``init_lm``'s
    layout for a dense tied-embedding decoder)."""
    return {
        "embed": {"table": w["embed"]},
        "layers": {
            "attn_norm": {"scale": w["attn_norm"]},
            "attn": {n: {"w": w[n]} for n in ("wq", "wk", "wv", "wo")},
            "mlp_norm": {"scale": w["mlp_norm"]},
            "mlp": {n: {"w": w[n]} for n in ("w_gate", "w_up", "w_down")},
        },
        "final_norm": {"scale": w["final_norm"]},
    }


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
def _forward(w: dict, tokens, n_prompt, *, d: Dims, p: Precision):
    S = tokens.shape[0]
    c = jnp.dtype(p.compute)
    pos = jnp.arange(S, dtype=jnp.int32)
    prefill_row = (pos < n_prompt)[:, None]
    x = w["embed"].astype(c)[tokens]

    def proj(h, wt, heads=None):
        y = matmul(h, wt, p).astype(c)
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
    logits = matmul(xf, w["embed"].T, p)
    return logits, kp, vp


def forward(w: dict, tokens, n_prompt, *, d: Dims, p: Precision):
    """Teacher-forced pass over one request (``tokens`` = prompt and served
    tokens, padded).  Rows before ``n_prompt`` attend as the prefill does,
    the rest as decode steps do.  Returns the logits (S, vocab) float32 and
    each layer's page values of K and V, (L, 2, S, kv_heads, hd)."""
    logits, kp, vp = _forward(w, tokens, n_prompt, d=d, p=p)
    return logits, jnp.stack([kp, vp], axis=1)


def served_rows(engine, pages: list[int], n_pos: int, config: dict):
    """The first ``n_pos`` KV rows of one request, every layer, as the
    engine's pool holds them: (L, 2, n_pos, kv_heads, hd) float32 on the
    host."""
    ps = engine.page_size
    ids = jnp.asarray(pages[: -(-n_pos // ps)], jnp.int32)
    out = []
    for t in (engine.pool.kv.k, engine.pool.kv.v):
        if config["kv_format"] == "bf16":
            vals = np.asarray(jnp.take(t, ids, axis=1).astype(jnp.float32))
        else:
            planes = np.asarray(jnp.take(t.planes, ids, axis=1))
            scale = np.asarray(jnp.take(t.scale, ids, axis=1))
            vals = decode_pages(planes, scale,
                                tuple(config["kv_moduli"][:2]))
        L, n_pages = vals.shape[:2]
        out.append(vals.reshape(L, n_pages * ps, *vals.shape[3:])[:, :n_pos])
    return np.stack(out, axis=1)


def _projections(d: Dims):
    """``(K, N)`` of each weight matmul of a layer: q, k, v, o, gate, up,
    down."""
    return [s[1:] for s, _ in _shapes(d).values()]


def matmul_calls(run):
    """``(M, K, N, repeats)`` of the residue matmuls of the run's traced
    interval: the seven projections of each layer on every row a step is
    given (the batch for a decode step, batch x bucket for a prefill) and
    the tied logits matmul on one row per slot."""
    d = run.dims
    calls = []
    for rows, logit_rows, count in run.steps():
        calls += [(rows, K, N, d.layers * count)
                  for K, N in _projections(d)]
        calls.append((logit_rows, d.d_model, d.vocab, count))
    return calls


def paged_decode(run):
    """``(operations per cached position, bytes of the query and output
    rows, layers)`` of one slot's paged decode step: QK and PV over every
    query head, the query read and the output written in bfloat16 and
    float32."""
    d = run.dims
    return 4.0 * d.heads * d.head_dim, d.q_width * 6, d.layers


def _layer_weights(d: Dims) -> int:
    return d.layers * sum(K * N for K, N in _projections(d))


def prompt_flops(run, n: int) -> float:
    """Useful FLOPs of a prompt of ``n`` tokens: every projection on each
    token, causal attention over the prompt, the logits of its last
    position."""
    d = run.dims
    att = 4.0 * d.layers * d.heads * d.head_dim
    return (2.0 * _layer_weights(d) * n + att * n * (n + 1) / 2
            + 2.0 * d.vocab * d.d_model)


def token_flops(run, n: int) -> float:
    """Useful FLOPs of a generated token whose step attends over ``n``
    positions: every projection, the logits, the attention."""
    d = run.dims
    att = 4.0 * d.layers * d.heads * d.head_dim
    return 2.0 * (_layer_weights(d) + d.vocab * d.d_model) + att * n
