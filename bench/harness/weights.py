"""Weights are data: made from the seed by the harness, handed to the
program, and made again by the reference after the window.

The distributions follow the usual initialisation of a pre-norm decoder
(embedding N(0, 0.02^2), each projection N(0, 2 / (d_in + d_out)), norm
scales 1), so activations have the scale of a freshly initialised model.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np


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


def root_key(seed: int) -> jax.Array:
    """A PRNG key for any whole-number seed (more bits than 32 included)."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


# (name, shape function, std function); layer tensors carry a leading L axis
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
    """Float32 weights of run ``seed``, made on the device in one call."""
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


def matmul_params(d: Dims) -> int:
    """Weights that take part in a matmul per token: the layers' seven
    projections and the (tied) logits matrix."""
    per_layer = sum(int(np.prod(s[1:])) for s, _ in _shapes(d).values())
    return d.layers * per_layer + d.vocab * d.d_model
