"""Feed-forward blocks (SwiGLU / GELU) over the switchable arithmetic backend.

Weights may be residue-resident (repro/quant/residency.py): the gate/up/down
dicts then hold precomputed digit or residue planes instead of a float
``"w"``, and ``linear.dense`` serves them conversion-free.  The activation
nonlinearity stays in float either way — only the matmuls change domain.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.models import linear

__all__ = ["init_swiglu", "swiglu", "init_gelu_mlp", "gelu_mlp"]


def init_swiglu(key: jax.Array, d_model: int, d_ff: int,
                dtype=jnp.float32) -> dict[str, Any]:
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "w_gate": linear.init_dense(k1, d_model, d_ff, dtype),
        "w_up": linear.init_dense(k2, d_model, d_ff, dtype),
        "w_down": linear.init_dense(k3, d_ff, d_model, dtype),
    }


def swiglu(params: dict[str, Any], x: jax.Array,
           dense_kw: dict[str, Any] | None = None) -> jax.Array:
    dense_kw = dense_kw or {}
    with jax.named_scope("mlp.gate_up"):
        g = linear.dense(params["w_gate"], x, **dense_kw)
        u = linear.dense(params["w_up"], x, **dense_kw)
        h = jax.nn.silu(g.astype(jnp.float32)).astype(u.dtype) * u
    with jax.named_scope("mlp.down"):
        return linear.dense(params["w_down"], h, **dense_kw)


def init_gelu_mlp(key: jax.Array, d_model: int, d_ff: int,
                  dtype=jnp.float32) -> dict[str, Any]:
    k1, k2 = jax.random.split(key, 2)
    return {
        "w_up": linear.init_dense(k1, d_model, d_ff, dtype),
        "w_down": linear.init_dense(k2, d_ff, d_model, dtype),
    }


def gelu_mlp(params: dict[str, Any], x: jax.Array,
             dense_kw: dict[str, Any] | None = None) -> jax.Array:
    dense_kw = dense_kw or {}
    h = linear.dense(params["w_up"], x, **dense_kw)
    h = jax.nn.gelu(h.astype(jnp.float32)).astype(h.dtype)
    return linear.dense(params["w_down"], h, **dense_kw)
