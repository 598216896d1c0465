"""Tiles of the rns_matmul kernel, chosen from each call's own shape.

``numerics/runners.py::_choose_blocks`` picks ``(bm, bn, bk)`` per call: ``bk``
and ``bn`` divide the K segment and N where those are multiples of 128 (so
weight planes reach the kernel unpadded), each grid step streams a weight
block of at least 1 MiB where the shape allows, and the double-buffered step
stays within the kernel's VMEM budget.  The compile rehearsals of the same
shapes for a described v5e live in ``tests/test_tpu_compile.py``.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from repro.configs import get_config
from repro.core.moduli import P21
from repro.kernels.rns_matmul import VMEM_BUDGET, vmem_bytes
from repro.models import linear
from repro.models.api import build_model
from repro.numerics.runners import (
    _choose_blocks,
    encode_rns_planes,
    rns_run,
    segment_count,
)
from repro.quant import residency
from repro.serving.kv_pool import KVPagePool

D_MODEL, D_FF, VOCAB, Q, KV_W = 4096, 11008, 64000, 4096, 512
DECODE_M, PREFILL_M, VERIFY_M = 16, 16 * 256, 16 * 5
MIB = 1024 * 1024

# (K, N) of every weight matmul of a yi-6b layer, then the tied logits
PROJECTIONS = {"q": (D_MODEL, Q), "k": (D_MODEL, KV_W), "v": (D_MODEL, KV_W),
               "o": (Q, D_MODEL), "gate": (D_MODEL, D_FF),
               "up": (D_MODEL, D_FF), "down": (D_FF, D_MODEL)}
SHAPES = (
    [(f"decode_{n}", DECODE_M, K, N) for n, (K, N) in PROJECTIONS.items()]
    + [("decode_logits", DECODE_M, D_MODEL, VOCAB)]
    + [(f"prefill_{n}", PREFILL_M, K, N) for n, (K, N) in PROJECTIONS.items()]
    + [(f"verify_{n}", VERIFY_M, K, N) for n, (K, N) in PROJECTIONS.items()]
    + [("unaligned", DECODE_M, 300, 100)]
)


@pytest.mark.parametrize("name,M,K,N", SHAPES, ids=[s[0] for s in SHAPES])
def test_tile_rule(name, M, K, N):
    # 4-bit codes: one K segment at every yi-6b width
    assert segment_count(K, 7, 7, P21) == 1
    bm, bn, bk = _choose_blocks(M, N, K)
    assert bm % 8 == 0 and bn % 128 == 0 and bk % 128 == 0
    if K % 128 == 0 and N % 128 == 0:
        assert K % bk == 0 and N % bn == 0
    assert vmem_bytes(bm, bn, bk) <= VMEM_BUDGET
    Kp, Np = -(-K // 128) * 128, -(-N // 128) * 128
    assert bk * bn >= min(MIB, Kp * Np)
    assert bk * bn <= 4 * MIB
    if M <= 512:
        assert bm >= M                            # one M block: weights read once


def _operands(M, K, N, seed=0):
    rng = np.random.default_rng(seed)
    a = rng.integers(-7, 8, size=(M, K)).astype(np.int32)
    w = rng.integers(-7, 8, size=(K, N)).astype(np.int32)
    return a, w


@pytest.mark.parametrize("M,K,N", [(16, 43 * 128, 5 * 128),
                                   (5, 43 * 128, 5 * 128), (16, 300, 100)])
@pytest.mark.parametrize("body", ["single", "planes"])
def test_rns_run_bit_identical(body, M, K, N):
    """The interpret kernel under the rule's tiles equals the ``ref`` backend
    and the integer product bit for bit, in the single-device body and the
    channel-parallel ``rns_matmul_planes`` body.  K = 43 x 128, N = 5 x 128
    take one whole-K, whole-N grid step per channel and pad no plane; an
    unaligned K and N pad the planes, and the counter says so."""
    aligned = K % 128 == 0 and N % 128 == 0
    if aligned:
        assert _choose_blocks(M, N, K) == (-(-M // 8) * 8, N, K)
    rng = np.random.default_rng(0)
    a = rng.integers(-7, 8, size=(M, K)).astype(np.int32)
    w = rng.integers(-7, 8, size=(K, N)).astype(np.int32)
    planes = encode_rns_planes(jnp.asarray(w), P21)
    kw = dict(mset=P21, max_abs_a=7, max_abs_b=7)
    shard = None
    if body == "planes":
        mesh = Mesh(np.array(jax.devices()[:1]), ("model",))
        shard = ("chan", mesh, (), ("model",))
    residency.reset_counters()
    got = rns_run(jnp.asarray(a), planes, backend="interpret", shard=shard,
                  **kw)
    assert (residency.counters().get("plane_pad", 0) > 0) == (not aligned)
    want = rns_run(jnp.asarray(a), planes, backend="ref", **kw)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(got), a @ w)


@pytest.fixture(scope="module")
def yi6b_one_layer():
    """yi-6b at published widths (vocab included), one layer, residue-
    resident weights as shapes only: tracing allocates nothing."""
    cfg = dataclasses.replace(get_config("yi-6b"), n_layers=1)
    model = build_model(cfg, system="rns", rns_bits=4, rns_impl="interpret")
    params = jax.eval_shape(
        lambda: model.prepare_params(model.init(jax.random.PRNGKey(0))))
    return cfg, model, params


def test_plane_pad_zero_in_yi6b_programs(yi6b_one_layer):
    """The rns decode step and the admission prefill, traced at yi-6b
    widths, pad no weight plane: every matmul's tiles divide its shape."""
    cfg, model, params = yi6b_one_layer
    B, page, bucket = DECODE_M, 64, 256
    pool = KVPagePool(1, 1 + B * 3, page, cfg.n_kv, cfg.hd, fmt="rns8r")
    kv = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), pool.kv)

    residency.reset_counters()
    jax.make_jaxpr(lambda p, t, kv, tab, pos: model.decode_paged(
        p, t, kv, tab, pos, page_size=page, with_syndrome=True))(
        params, jnp.zeros((B, 1), jnp.int32), kv,
        jnp.zeros((B, 3), jnp.int32), jnp.zeros((B,), jnp.int32))
    decode = residency.counters()
    assert decode.get("weight_reuse", 0) == 8     # 7 projections + logits
    assert decode.get("plane_pad", 0) == 0

    residency.reset_counters()
    jax.make_jaxpr(lambda p, t, at: model.prefill(
        p, {"tokens": t}, s_max=bucket, logits_at=at))(
        params, jnp.zeros((B, bucket), jnp.int32), jnp.zeros((B,), jnp.int32))
    prefill = residency.counters()
    assert prefill.get("weight_reuse", 0) == 8
    assert prefill.get("plane_pad", 0) == 0


def test_plane_pad_counts_unaligned_dense():
    """The same counter through the model's dense layer, at an N that is
    not a multiple of 128."""
    params = linear.init_dense(jax.random.PRNGKey(0), 256, 100)
    prep = residency.prepare_dense(params, system="rns", bits=4)
    residency.reset_counters()
    jax.make_jaxpr(lambda x: linear.dense(prep, x, system="rns",
                                          impl="interpret"))(
        jnp.zeros((DECODE_M, 256), jnp.float32))
    assert residency.counters().get("plane_pad", 0) == 1
