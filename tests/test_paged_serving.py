"""Paged, residue-domain KV cache + continuous-batching serving (PR 6).

Pins, bottom-up: the packed residue codec (exact over the full centered
range), the page quantizer's error bound, the host page pool's state machine
(refcounts, prefix sharing, eviction, exhaustion), the paged flash-decode
kernel against a dense reference on ragged page-unaligned lengths, paged
*bit*-identity with the dense engine for bf16 pages, residue-page tolerance,
continuous batching (mid-decode admission, ragged budgets, prefix reuse,
prefill skips), and the >= 2x KV-bytes cut of rns4 pages.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.moduli import KV4, KV8
from repro.models.api import build_model
from repro.numerics import kv_pages as kvp
from repro.numerics.attention import paged_decode, set_decode_block
from repro.serving.engine import ServingEngine
from repro.serving.kv_pool import KVPagePool
from repro.serving.scheduler import Request, RequestScheduler


# ---------------------------------------------------------------------------
# Packed residue codec + page quantizer
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mset", [KV8, KV4], ids=["kv8", "kv4"])
def test_packed_roundtrip_full_centered_range(mset):
    """The PackedFormat codec is exact over the whole centered range
    [-M/2, M/2) — the packed byte stream is a lossless integer codec."""
    fmt = mset.packed()
    lo, hi = -mset.M // 2, mset.M // 2 - 1
    vpb = fmt.values_per_byte
    x = np.arange(lo, hi + 1, dtype=np.int32)
    pad = (-len(x)) % vpb
    x = np.concatenate([x, np.zeros(pad, np.int32)]).reshape(2, -1)
    packed = fmt.encode(jnp.asarray(x))
    assert packed.dtype == jnp.uint8
    assert packed.shape[-1] == x.shape[-1] // vpb
    np.testing.assert_array_equal(np.asarray(fmt.decode(packed)), x)


@pytest.mark.parametrize("name", ["rns8", "rns4"])
def test_page_quantizer_error_bound(name):
    fmt = kvp.KV_FORMATS[name]
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(0, 2, (3, 8, 2, 16)).astype(np.float32))
    planes, scale = kvp.quantize_to_format(x, fmt)
    t = kvp.ResidueTensor(planes=planes, scale=scale, mset=fmt.mset,
                          layout="rns_pack", qbits=fmt.qbits,
                          max_abs=1.0)
    y = np.asarray(kvp.dequantize_page_values(t))
    err = np.abs(y - np.asarray(x))
    # symmetric quantization: error bounded by half a step per head row
    bound = np.asarray(scale)[..., None, :, :] * 0.5 + 1e-6
    assert (err <= np.broadcast_to(bound.squeeze(-3), err.shape)).all()


def test_bytes_per_token_residue_cut():
    """The acceptance gate: rns4 pages cut KV bytes per resident token by
    >= 2x vs bf16 (rns8 lands ~1.9x)."""
    n_kv, hd = 2, 64
    dense = kvp.bytes_per_token("bf16", n_kv, hd)
    rns8 = kvp.bytes_per_token("rns8", n_kv, hd)
    rns4 = kvp.bytes_per_token("rns4", n_kv, hd)
    assert dense / rns4 >= 2.0
    assert dense / rns8 > 1.5
    assert rns4 < rns8 < dense


# ---------------------------------------------------------------------------
# Host page pool: refcounts, prefix sharing, eviction, exhaustion
# ---------------------------------------------------------------------------


def _pool(num_pages=8, page_size=4, prefix_cache=True):
    return KVPagePool(1, num_pages, page_size, 1, 8, fmt="bf16",
                      prefix_cache=prefix_cache)


def test_pool_alloc_release_cycle():
    pool = _pool()
    pages = pool.alloc(3)
    assert len(set(pages)) == 3 and 0 not in pages
    assert pool.free_pages == 4
    pool.release(pages)
    assert pool.free_pages == 7
    assert pool.stats.pages_allocated == 3 and pool.stats.pages_freed == 3


def test_pool_exhaustion_raises():
    pool = _pool(num_pages=4, prefix_cache=False)
    pool.alloc(3)
    with pytest.raises(RuntimeError, match="exhausted"):
        pool.alloc(1)


def test_pool_prefix_sharing_refcounts():
    pool = _pool(page_size=4)
    toks = np.arange(10)
    a = pool.admit(toks, 10)          # 2 full pages + 1 partial
    assert a.prefix_hits == 0 and a.pages_allocated == 3
    b = pool.admit(toks, 10)          # same prompt: full pages shared
    assert b.prefix_hits == 2 and b.pages_allocated == 1
    assert b.pages[:2] == a.pages[:2]           # shared prompt pages
    assert b.pages[2] != a.pages[2]             # exclusive decode page
    pool.release(a.pages)
    # shared pages still referenced by b -> not freed
    assert pool.stats.pages_freed == 1
    pool.release(b.pages)
    assert pool.stats.pages_freed == 4


def test_pool_cached_free_revival_and_eviction():
    pool = _pool(num_pages=4, page_size=4)     # 3 usable pages
    toks = np.arange(4)
    a = pool.admit(toks, 4)                    # 1 full (cached) page
    pool.release(a.pages)                      # cached-free, off free list
    b = pool.admit(toks, 4)                    # revived from the cache
    assert b.prefix_hits == 1 and b.pages == a.pages
    pool.release(b.pages)
    # exhaust the free list; the cached-free page must be evicted
    pages = pool.alloc(3)
    assert pool.stats.evictions == 1
    pool.release(pages)
    c = pool.admit(toks, 4)
    assert c.prefix_hits == 0                  # cache entry gone


def test_pool_prefill_skip_requires_page_alignment():
    pool = _pool(page_size=4)
    aligned, ragged = np.arange(8), np.arange(7)
    pool.admit(aligned, 8)
    pool.admit(ragged, 7)
    pool.remember_logits(aligned, np.ones(16))
    pool.remember_logits(ragged, np.ones(16))
    assert pool.admit(aligned, 8).cached_logits is not None
    assert pool.admit(ragged, 7).cached_logits is None   # partial last page
    assert pool.stats.prefill_skips == 1


# ---------------------------------------------------------------------------
# Paged flash-decode kernel: ragged lengths, GQA, residue pages
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("fmt", ["bf16", "rns8", "rns4"])
@pytest.mark.parametrize("kv_lens", [(5, 12), (8, 3)],
                         ids=["mid-page", "page-edge"])
def test_paged_decode_kernel_vs_ref(fmt, kv_lens):
    """Kernel == gather-dequant-dense reference on page-unaligned kv_len
    (finish mid-page) and GQA head grouping, for every page format."""
    B, H, Kv, hd, ps, n_pmax = 2, 4, 2, 16, 4, 3
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.normal(0, 1, (B, H, hd)).astype(np.float32))
    pool = kvp.make_paged_kv(1, 1 + B * n_pmax, ps, Kv, hd, fmt=fmt,
                             dtype=jnp.float32)
    kd = rng.normal(0, 1, (1, B, n_pmax * ps, Kv, hd)).astype(np.float32)
    vd = rng.normal(0, 1, (1, B, n_pmax * ps, Kv, hd)).astype(np.float32)
    tab = jnp.asarray(
        np.arange(1, 1 + B * n_pmax, dtype=np.int32).reshape(B, n_pmax))
    pool = kvp.scatter_prefill(pool, jnp.asarray(kd), jnp.asarray(vd),
                               tab, page_size=ps)
    layer = kvp.layer_slice(pool, 0)
    kv_len = jnp.asarray(np.array(kv_lens, np.int32))
    out_k = paged_decode(q, layer, tab, kv_len, page_size=ps,
                         backend="interpret")
    out_r = paged_decode(q, layer, tab, kv_len, page_size=ps, backend="ref")
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                               rtol=2e-5, atol=2e-5)


def test_paged_decode_matches_dense_flash_bit_identical():
    """bf16 float32-stored pages + aligned dense chunks: the paged kernel's
    merged output is bit-identical to the dense split-KV flash decode."""
    from repro.numerics.attention import flash_decode

    B, H, Kv, hd, ps, n_pmax = 2, 4, 2, 16, 8, 3
    rng = np.random.default_rng(2)
    q = jnp.asarray(rng.normal(0, 1, (B, H, hd)).astype(np.float32))
    kd = rng.normal(0, 1, (B, n_pmax * ps, Kv, hd)).astype(np.float32)
    vd = rng.normal(0, 1, (B, n_pmax * ps, Kv, hd)).astype(np.float32)
    pool = kvp.make_paged_kv(1, 1 + B * n_pmax, ps, Kv, hd,
                             dtype=jnp.float32)
    tab = jnp.asarray(
        np.arange(1, 1 + B * n_pmax, dtype=np.int32).reshape(B, n_pmax))
    pool = kvp.scatter_prefill(pool, jnp.asarray(kd[None]),
                               jnp.asarray(vd[None]), tab, page_size=ps)
    layer = kvp.layer_slice(pool, 0)
    kv_len = jnp.asarray(np.array([17, 24], np.int32))
    out_p = paged_decode(q, layer, tab, kv_len, page_size=ps,
                         backend="interpret")
    out_d = flash_decode(q, jnp.asarray(kd), jnp.asarray(vd),
                         kv_len=kv_len, bk=ps, backend="interpret")
    np.testing.assert_array_equal(np.asarray(out_p), np.asarray(out_d))


# ---------------------------------------------------------------------------
# Engine: paged generate vs dense generate
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def small_model():
    cfg = dataclasses.replace(get_config("yi-6b").reduced(),
                              n_layers=2, vocab=256,
                              compute_dtype="float32")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return model, params, cfg


def _engines(small_model, **paged_kw):
    model, params, _ = small_model
    dense = ServingEngine(model, params, batch=4, s_max=24, paged=False)
    paged = ServingEngine(model, params, batch=4, s_max=24, paged=True,
                          **paged_kw)
    return dense, paged


def test_paged_generate_bit_identical_multi_page(small_model):
    """The tentpole pin: bf16 pages + multi-page prompts (page_size=8 over
    24 positions = 3 pages/request) emit bit-identical tokens and step
    counts vs the dense engine, greedy and sampled, with and without EOS."""
    dense, paged = _engines(small_model, page_size=8)
    assert paged.paged and paged.n_pmax == 3
    _, _, cfg = small_model
    rng = np.random.default_rng(3)
    prompts = rng.integers(0, cfg.vocab, (4, 9)).astype(np.int32)
    batch = {"tokens": prompts}
    prev = set_decode_block(8)     # align dense chunks with page boundaries
    try:
        for mx in (1, 6, 14):
            rd = dense.generate(batch, max_new=mx)
            rp = paged.generate(batch, max_new=mx)
            np.testing.assert_array_equal(rd.tokens, rp.tokens)
            np.testing.assert_array_equal(rd.prefill_logits,
                                          rp.prefill_logits)
            assert rd.steps == rp.steps
            assert rp.stats.decode_dispatches == 1
            assert rp.stats.pages_allocated > 0
            assert rp.stats.pages_allocated == rp.stats.pages_freed
        eos = int(dense.generate(batch, max_new=3).tokens[0, 1])
        rd = dense.generate(batch, max_new=12, eos=eos)
        rp = paged.generate(batch, max_new=12, eos=eos)
        np.testing.assert_array_equal(rd.tokens, rp.tokens)
        assert rd.steps == rp.steps
        key = jax.random.PRNGKey(11)
        rd = dense.generate(batch, max_new=6, temperature=0.7, key=key)
        rp = paged.generate(batch, max_new=6, temperature=0.7, key=key)
        np.testing.assert_array_equal(rd.tokens, rp.tokens)
    finally:
        set_decode_block(prev)


@pytest.mark.parametrize("fmt", ["rns8", "rns4"])
def test_residue_paged_generate_tolerance(small_model, fmt):
    """Residue pages quantize the cache — tokens may drift from the dense
    trajectory, but the first decoded tokens (driven by near-identical
    logits) must agree and outputs must stay valid ids."""
    dense, paged = _engines(small_model, page_size=8, kv_format=fmt)
    _, _, cfg = small_model
    rng = np.random.default_rng(4)
    prompts = rng.integers(0, cfg.vocab, (4, 9)).astype(np.int32)
    rd = dense.generate({"tokens": prompts}, max_new=6)
    rp = paged.generate({"tokens": prompts}, max_new=6)
    # token 0 comes from the (unquantized) prefill: exact
    np.testing.assert_array_equal(rd.tokens[:, 0], rp.tokens[:, 0])
    assert rp.tokens.shape == (4, 6)
    assert rp.tokens.min() >= 0 and rp.tokens.max() < cfg.vocab
    assert rd.steps == rp.steps == 5


# ---------------------------------------------------------------------------
# Continuous batching: mid-decode admission, ragged budgets, prefix reuse
# ---------------------------------------------------------------------------


def _sched_engine(small_model, **kw):
    model, params, _ = small_model
    eng = ServingEngine(model, params, batch=2, s_max=24, page_size=8,
                        **kw)
    assert eng.paged
    return eng


def test_continuous_mid_decode_admission(small_model):
    """More requests than slots + ragged budgets: early finishers free
    their slot mid-decode and queued requests are admitted into it (no
    batch-boundary rounds).  Every result matches a solo serve."""
    _, _, cfg = small_model
    eng = _sched_engine(small_model)
    sched = RequestScheduler(eng)
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, cfg.vocab, n).astype(np.int32)
               for n in (5, 9, 7, 4)]
    budgets = [3, 10, 6, 8]
    reqs = [Request(rid=i, tokens=p, max_new=m)
            for i, (p, m) in enumerate(zip(prompts, budgets))]
    out = sched.serve(reqs)
    assert [r.rid for r in out] == [0, 1, 2, 3]
    for r in out:
        assert len(r.result) == r.max_new
        assert r.stats.decode_dispatches >= 1
        assert r.stats.pages_allocated > 0 and r.stats.pages_freed > 0
    # rid 0 (budget 3) finishes mid-decode of rid 1 (budget 10): rid 2 was
    # admitted into the freed slot before rid 1 finished
    assert out[1].stats.decode_dispatches > 1
    # every result equals serving the request alone
    for r, p in zip(out, prompts):
        solo = RequestScheduler(eng).serve(
            [Request(rid=0, tokens=p, max_new=r.max_new)])[0]
        np.testing.assert_array_equal(r.result, solo.result)


def test_continuous_prefix_reuse_and_prefill_skip(small_model):
    """Identical page-aligned prompts share prompt pages and skip the
    repeat prefill — with identical results."""
    _, _, cfg = small_model
    eng = _sched_engine(small_model)
    sched = RequestScheduler(eng)
    rng = np.random.default_rng(6)
    toks = rng.integers(0, cfg.vocab, 16).astype(np.int32)  # 2 full pages
    reqs = [Request(rid=i, tokens=toks, max_new=4) for i in range(3)]
    out = sched.serve(reqs)
    assert sum(r.stats.prefix_hits for r in out) >= 2
    assert any(r.stats.prefill_skipped for r in out[1:])
    for r in out[1:]:
        np.testing.assert_array_equal(r.result, out[0].result)
    # a no-prefix-cache engine returns the same tokens
    eng2 = _sched_engine(small_model, prefix_cache=False)
    out2 = RequestScheduler(eng2).serve(
        [Request(rid=i, tokens=toks, max_new=4) for i in range(3)])
    assert all(r.stats.prefix_hits == 0 for r in out2)
    for r, r2 in zip(out, out2):
        np.testing.assert_array_equal(r.result, r2.result)


def test_continuous_eos_mid_page(small_model):
    """EOS landing mid-page retires the request immediately; remaining
    requests keep decoding and the freed pages return to the pool."""
    _, _, cfg = small_model
    eng = _sched_engine(small_model)
    sched = RequestScheduler(eng)
    rng = np.random.default_rng(7)
    toks = rng.integers(0, cfg.vocab, 5).astype(np.int32)
    probe = sched.serve([Request(rid=0, tokens=toks, max_new=6)])[0]
    eos = int(probe.result[2])     # some token the trajectory emits early
    want = int(np.nonzero(probe.result == eos)[0][0]) + 1
    out = sched.serve([
        Request(rid=1, tokens=toks, max_new=12, eos=eos),
        Request(rid=2, tokens=toks, max_new=12),
    ])
    assert len(out[0].result) == want < 12
    assert int(out[0].result[-1]) == eos
    assert len(out[1].result) == 12
    assert out[0].stats.pages_freed > 0
    assert eng.pool.free_pages > 0


def test_paged_true_raises_where_unsupported():
    """paged=True is a demand: a family without a paged decode path (or a
    host loop, or a mesh) is an error, not a quiet dense fallback."""
    cfg = get_config("mamba2-780m").reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="paged serving is unsupported"):
        ServingEngine(model, params, batch=2, s_max=16, paged=True)
    assert not ServingEngine(model, params, batch=2, s_max=16).paged
