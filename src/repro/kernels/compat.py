"""Interpret-mode policy shared by every Pallas kernel.

The repo targets one installation (jax/jaxlib 0.9.0 with libtpu 0.0.34,
``requirements-ci.txt``), so nothing here bridges API versions: kernels
build ``pltpu.CompilerParams`` directly (a dataclass — an unknown field
raises) and parallel code calls ``jax.shard_map`` / ``jax.lax.axis_size``.
What is left is one decision every kernel would otherwise repeat: Pallas
kernels run in the interpreter off-TPU (:func:`resolve_interpret`).
See DESIGN.md §6.
"""
from __future__ import annotations

import jax

__all__ = ["platform", "resolve_interpret"]


def platform() -> str:
    """The default JAX backend platform ("cpu" | "gpu" | "tpu")."""
    return jax.default_backend()


def resolve_interpret(interpret: bool | None) -> bool:
    """Resolve an ``interpret`` kwarg default.

    ``None`` means "decide by platform": Mosaic lowering only exists on TPU,
    so everywhere else the Pallas interpreter runs the same kernel body.
    Explicit booleans are honored unchanged.
    """
    if interpret is None:
        return platform() != "tpu"
    return bool(interpret)
