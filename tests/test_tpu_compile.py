"""Compile rehearsals: the main-path Pallas kernels at yi-6b widths, compiled
for a *described* (not attached) TPU v5e chip.

Interpret mode runs any block shape, so it cannot see what Mosaic refuses:
blocks whose last two dimensions are neither (8, 128)-aligned nor whole,
rank-1 blocks, transposed masks, fast-memory overuse.  Each test lowers and
compiles one kernel at the published widths (d_model 4096, 32 query / 4 KV
heads of 128, d_ff 11008) through the TPU compiler that ships with libtpu,
and checks that the kernel survived as a ``tpu_custom_call``.

The topology is described inside a module fixture, never at import time:
only one process may hold libtpu, and pytest-xdist workers import every
test module.  The persistent compilation cache is off around the compiles
(an entry written for a described chip cannot be read back without one).
"""
from __future__ import annotations

import dataclasses
import os
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.core.moduli import KV8, KV8R2
from repro.kernels.flash_attn import (
    flash_attention_pallas,
    flash_decode_pallas,
    flash_paged_decode_pallas,
)
from repro.kernels.rns_matmul import rns_matmul_pallas
from repro.kernels.sdrns_matmul import sdrns_matmul_pallas, sdrns_matvec_pallas
from repro.models.api import build_model
from repro.models.attention import set_attn_impl
from repro.numerics.runners import _choose_blocks
from repro.serving.kv_pool import KVPagePool

D_MODEL, D_FF, H, KV, HD, VOCAB = 4096, 11008, 32, 4, 128, 64000
BATCH, PAGE, N_PMAX = 8, 64, 9


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "no compiler"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding) for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


# Every weight matmul shape of yi-6b's two serving programs: decode at batch
# 16 (q/o, k/v, gate/up, down, the tied logits at vocab 64000) and at 8,
# admission prefill at 16 x bucket 256, speculative verify at 16 x (4 + 1);
# and one shape that is not lane-aligned.
RNS_SHAPES = (
    [(BATCH, D_MODEL, D_MODEL), (BATCH, D_FF, D_MODEL)]
    + [(M, K, N) for M in (16, 4096, 80)
       for K, N in ((D_MODEL, D_MODEL), (D_MODEL, KV * HD), (D_MODEL, D_FF),
                    (D_FF, D_MODEL))]
    + [(16, D_MODEL, VOCAB), (16, 300, 100)])


@pytest.mark.parametrize("M,K,N", RNS_SHAPES)
def test_rns_matmul_compiles(one_chip, M, K, N):
    """With the tiles the runner's rule picks: aligned planes unpadded."""
    bm, bn, bk = _choose_blocks(M, N, K)
    Mp, Np, Kp = (-(-d // t) * t for d, t in ((M, bm), (N, bn), (K, bk)))
    if K % 128 == 0 and N % 128 == 0:
        assert (Kp, Np) == (K, N)
    _compile(lambda a, b, m: rns_matmul_pallas(a, b, m, bm=bm, bn=bn, bk=bk,
                                               interpret=False),
             one_chip, ((3, Mp, Kp), jnp.int8), ((3, Kp, Np), jnp.int8),
             ((3,), jnp.int32))


def test_flash_attention_compiles(one_chip):
    S = 512
    _compile(lambda q, k, v, n: flash_attention_pallas(q, k, v, n,
                                                       interpret=False),
             one_chip, ((1, S, H, HD), jnp.bfloat16),
             ((1, S, KV, HD), jnp.bfloat16), ((1, S, KV, HD), jnp.bfloat16),
             ((1,), jnp.int32))


def test_flash_decode_compiles(one_chip):
    T = 1024
    _compile(lambda q, k, v, n: flash_decode_pallas(q, k, v, n, bk=512,
                                                    interpret=False),
             one_chip, ((BATCH, H, HD), jnp.bfloat16),
             ((BATCH, T, KV, HD), jnp.bfloat16),
             ((BATCH, T, KV, HD), jnp.bfloat16), ((BATCH,), jnp.int32))


@pytest.mark.parametrize("fmt,syndrome", [(None, False), ("rns8", False),
                                          ("rns8r", False), ("rns8r", True)])
def test_paged_decode_compiles(one_chip, fmt, syndrome):
    P = 1 + BATCH * N_PMAX
    common = [((BATCH, H, HD), jnp.bfloat16)]
    tab = [((BATCH, N_PMAX), jnp.int32), ((BATCH,), jnp.int32)]
    if fmt is None:
        pool = ((P, PAGE, KV, HD), jnp.bfloat16)
        _compile(lambda q, k, v, t, n: flash_paged_decode_pallas(
                     q, k, v, t, n, page_size=PAGE, interpret=False),
                 one_chip, *common, pool, pool, *tab)
        return
    mset = {"rns8": KV8, "rns8r": KV8R2}[fmt]
    planes = ((P, PAGE, 1 + mset.redundant, KV, HD), jnp.uint8)
    scale = ((P, PAGE, KV, 1), jnp.float32)
    red = mset.redundant_moduli if syndrome else None
    _compile(lambda q, k, v, t, n, ks, vs: flash_paged_decode_pallas(
                 q, k, v, t, n, page_size=PAGE, k_scale=ks, v_scale=vs,
                 moduli=mset.info_moduli, red_moduli=red, interpret=False),
             one_chip, *common, planes, planes, *tab, scale, scale)


def _bench_kernel_names() -> list[str]:
    """``TRACE`` of each ``bench/kernels/*.py``: the instruction name the
    benchmark's device-trace reduction finds each kernel by."""
    bench = Path(__file__).resolve().parents[1] / "bench" / "kernels"
    names = []
    for f in sorted(bench.glob("*.py")):
        m = re.search(r'^TRACE = "([^"]+)"', f.read_text(), re.M)
        if m:
            names.append(m.group(1))
    return names


def test_decode_step_keeps_kernel_names(one_chip):
    """The main-path decode step (rns weights, rns8r pages with in-kernel
    syndromes, sub-layer device scopes), compiled at yi-6b widths for one
    layer, holds an instruction named after every kernel the benchmark
    reads: scopes change op metadata, never these names."""
    names = _bench_kernel_names()
    assert len(names) >= 2
    cfg = dataclasses.replace(get_config("yi-6b"), n_layers=1, vocab=4096)
    model = build_model(cfg, system="rns", rns_bits=4, rns_impl="pallas")
    params = jax.eval_shape(
        lambda: model.prepare_params(model.init(jax.random.PRNGKey(0))))
    pool = KVPagePool(1, 1 + BATCH * 3, PAGE, cfg.n_kv, cfg.hd, fmt="rns8r")

    def spec(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    def step(params, tok, kv, tab, pos):
        return model.decode_paged(params, tok, kv, tab, pos, page_size=PAGE,
                                  with_syndrome=True)

    prev = set_attn_impl("pallas")
    try:
        text = jax.jit(step).lower(
            jax.tree_util.tree_map(spec, params),
            spec(jnp.zeros((BATCH, 1), jnp.int32)),
            jax.tree_util.tree_map(spec, pool.kv),
            spec(jnp.zeros((BATCH, 3), jnp.int32)),
            spec(jnp.zeros((BATCH,), jnp.int32))).compile().as_text()
    finally:
        set_attn_impl(prev)
    for name in names:
        assert re.search(rf"%{re.escape(name)}(\.\d+)? = ", text), name
    assert "/mlp.down/" in text and "/attn.core/" in text


@pytest.mark.parametrize("kernel", [sdrns_matmul_pallas, sdrns_matvec_pallas])
def test_sdrns_kernels_refuse_mosaic(kernel):
    """The fused SD-RNS kernel is interpreter-only: asking for Mosaic is a
    clear error, never a silent fallback."""
    n = 7
    a = jnp.zeros((3, 8, 128, n), jnp.int8)
    b = jnp.zeros((3, 128, 128, n), jnp.int8)
    ws = jnp.zeros((3,), jnp.int32)
    kw = {"bn": 128} if kernel is sdrns_matvec_pallas else {"bm": 8,
                                                            "bn": 128}
    with pytest.raises(NotImplementedError, match="not Mosaic-legal"):
        kernel(a, b, ws, interpret=False, **kw)
