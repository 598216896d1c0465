"""Faults planted in the attention kernels under the timed path, to show
that the check catches them (``bench/control.py --fault``, and the tests).

Each fault replaces the program's registered implementation of one kernel
for this process's backend with a wrapper that breaks what it computes;
the engine then compiles and serves through the wrapper exactly as it
would through the kernel:

* ``kv_head``: the paged decode kernel reads every query group's keys and
  values from the next KV head (witness lanes move with their codes, so
  the in-kernel syndrome stays silent);
* ``last_page``: the paged decode kernel leaves out the last page each
  slot's length covers;
* ``slots``: ``kv_head``, in the even slots of the batch only;
* ``prefill_kv_head``: the flash prefill kernel reads the next KV head.
"""
from __future__ import annotations

import jax.numpy as jnp

FAULTS = ("kv_head", "last_page", "slots", "prefill_kv_head")


def _next_head(x):
    """Roll the KV-head axis, second from last in every pool and cache
    leaf: dense pages (P, ps, Kv, hd), residue planes (P, ps, 1 + r, Kv,
    hd / vpb), page scales (P, ps, Kv, 1), prefill K/V (B, T, Kv, hd)."""
    return None if x is None else jnp.roll(x, 1, axis=-2)


def plant(name: str):
    """Install fault ``name``; returns a function that removes it."""
    from repro.numerics.registry import get_impl, register_impl, \
        resolve_backend

    backend = resolve_backend(None)
    op = "flash_attention" if name.startswith("prefill") else \
        "flash_paged_decode"
    impl = get_impl(op, backend)

    def paged(q, k, v, ks, vs, fmt, syndrome, tab, kv_len, ps):
        if name == "last_page":
            kv_len = jnp.where(kv_len > ps, (kv_len - 1) // ps * ps, kv_len)
            return impl(q, k, v, ks, vs, fmt, syndrome, tab, kv_len, ps)
        bad, syn = impl(q, _next_head(k), _next_head(v), _next_head(ks),
                        _next_head(vs), fmt, syndrome, tab, kv_len, ps)
        if name == "slots":
            good, syn = impl(q, k, v, ks, vs, fmt, syndrome, tab, kv_len, ps)
            even = (jnp.arange(q.shape[0]) % 2 == 0)[:, None, None]
            bad = jnp.where(even, bad, good)
        return bad, syn

    def prefill(q, k, v, kv_len, causal, bq, bk):
        return impl(q, _next_head(k), _next_head(v), kv_len, causal, bq, bk)

    if name not in FAULTS:
        raise ValueError(f"unknown fault {name!r}; expected {FAULTS}")
    register_impl(op, backend, prefill if op == "flash_attention" else paged)
    return lambda: register_impl(op, backend, impl)
