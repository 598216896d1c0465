"""Host spans and admission counters of the serving path, and the device
scopes of the fused paged program.

A tiny rns model with redundant pages serves a handful of requests through
``RequestScheduler`` on a paged engine (batch 2, so later requests admit
mid-wave).  The spans must nest as the scheduler and engine nest, carry the
request ids they serve, and cost nothing when the tracer is off; the
prefill counters must equal what ``admit_prefill`` computed.
"""
from __future__ import annotations

import glob
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ArchConfig
from repro.core.moduli import P21R2
from repro.models.api import build_model
from repro.serving.engine import ServingEngine
from repro.serving.scheduler import Request, RequestScheduler
from repro.serving.trace import Tracer
from repro.testing.faults import FaultSpec, inject_faults

CFG = ArchConfig(name="t", family="dense", d_model=64, n_layers=2,
                 n_heads=4, n_kv=2, d_ff=128, vocab=97,
                 compute_dtype="float32")

SCOPES = ("embed", "attn.norm", "attn.qkv", "attn.rope", "attn.core",
          "attn.out", "kv.layer", "mlp.norm", "mlp.gate_up", "mlp.down",
          "logits", "sample")


@pytest.fixture(scope="module")
def rmodel():
    model = build_model(CFG, system="rns", rns_mset=P21R2)
    return model, model.init(jax.random.PRNGKey(0))


def _engine(rmodel, **kw):
    model, params = rmodel
    return ServingEngine(model, params, batch=2, s_max=32, paged=True,
                         page_size=4, kv_format="rns8r", **kw)


@pytest.fixture(scope="module")
def engines(rmodel):
    """One engine per fault policy, shared so that each compiles once;
    every test hangs its own tracer on them."""
    return {p: _engine(rmodel, policy=p) for p in ("off", "strict")}


def _requests(n=5, seed=3):
    rng = np.random.default_rng(seed)
    return [Request(rid=10 + i,
                    tokens=rng.integers(0, CFG.vocab,
                                        int(rng.integers(3, 11))
                                        ).astype(np.int32),
                    max_new=int(rng.integers(2, 7))) for i in range(n)]


def _serve(eng, reqs):
    return RequestScheduler(eng).serve(reqs)


def _by_id(spans):
    return {s.id: s for s in spans}


def _children(spans, parent):
    return [s for s in sorted(spans, key=lambda s: s.t0)
            if s.parent == parent.id]


@pytest.mark.parametrize("policy", ["off", "strict"])
def test_spans_nest_with_parents_and_rids(engines, policy):
    eng = engines[policy]
    eng.tracer = Tracer()
    reqs = _requests()
    rids = {r.rid for r in reqs}
    _serve(eng, reqs)
    spans = eng.tracer.spans
    by_id = _by_id(spans)

    (serve,) = [s for s in spans if s.name == "serve"]
    assert serve.parent is None and set(serve.rids) == rids
    for s in spans:
        if s.parent is not None:
            p = by_id[s.parent]
            assert p.t0 <= s.t0 <= s.t1 <= p.t1, (s.name, p.name)

    admitted = []
    for a in (s for s in spans if s.name == "sched.admit"):
        assert a.parent == serve.id
        assert [c.name for c in _children(spans, a)
                if c.name.startswith("admit.")] == [
            "admit.pages", "admit.prefill", "admit.scatter"]
        assert all(c.rids == a.rids for c in _children(spans, a)
                   if c.name.startswith("admit."))
        admitted += a.rids
    assert sorted(admitted) == sorted(rids)     # each admitted once
    assert len([s for s in spans if s.name == "sched.admit"]) > 1

    segments = [s for s in spans if s.name == "sched.segment"]
    assert segments
    for g in segments:
        assert g.parent == serve.id and g.rids
        assert set(g.rids) <= rids
        assert [c.name for c in _children(spans, g)
                if c.name.startswith("segment.")] == [
            "segment.prepare", "segment.dispatch", "segment.wait",
            "segment.readback"]

    retired = [s for s in spans if s.name == "sched.retire"]
    assert sorted(r for s in retired for r in s.rids) == sorted(rids)
    for s in retired:
        assert len(s.rids) == 1
        assert by_id[s.parent].name in ("sched.segment", "sched.admit")
        assert s.rids[0] in by_id[s.parent].rids


def test_segment_wait_lies_inside_its_segment(engines):
    eng = engines["strict"]
    eng.tracer = Tracer()
    _serve(eng, _requests())
    spans = eng.tracer.spans
    by_id = _by_id(spans)
    waits = [s for s in spans if s.name == "segment.wait"]
    assert len(waits) == len([s for s in spans
                              if s.name == "sched.segment"])
    for w in waits:
        seg = by_id[w.parent]
        assert seg.name == "sched.segment"
        assert seg.t0 <= w.t0 <= w.t1 <= seg.t1
        (disp,) = [s for s in _children(spans, seg)
                   if s.name == "segment.dispatch"]
        (back,) = [s for s in _children(spans, seg)
                   if s.name == "segment.readback"]
        assert disp.t1 <= w.t0 and w.t1 <= back.t0


def test_escalate_span_only_on_nonzero_syndromes(engines):
    """A KV flip under policy="strict": the segment that reads it escalates
    (repair and replay) inside a ``segment.escalate`` span that follows its
    wait; the tokens stay those of a clean run."""
    for e in engines.values():
        e.pool.reset()          # slot 0's first page is page 1 again
    clean = [r.result for r in _serve(engines["off"], _requests(2, 11))]
    eng = engines["strict"]
    eng.tracer = Tracer()
    replays = eng.stats.faults.replays
    # layer 0, page 1 (slot 0's first page), row 0, kv-head 0, dim 0
    faults = [FaultSpec(kind="kv", which="k", channel=2, at=(0, 1, 0, 0, 0),
                        bit=0x01)]
    with inject_faults(eng, faults, after_steps=1) as log:
        out = _serve(eng, _requests(2, 11))
    assert len(log) == 1
    for r, ref in zip(out, clean):
        np.testing.assert_array_equal(r.result, ref)
    spans = eng.tracer.spans
    by_id = _by_id(spans)
    (esc,) = [s for s in spans if s.name == "segment.escalate"]
    assert by_id[esc.parent].name == "sched.segment"
    assert any(s.name == "segment.wait" and s.t1 <= esc.t0
               for s in _children(spans, by_id[esc.parent]))
    assert eng.stats.faults.replays > replays


def test_disabled_tracer_records_nothing(rmodel, engines):
    assert not _engine(rmodel).tracer.enabled       # the default
    eng = engines["strict"]
    tr = eng.tracer = Tracer(enabled=False)
    assert tr.span("a") is tr.span("b", [1], {"x": 1})
    with tr.span("a") as sp:
        assert sp is None
    out = _serve(eng, _requests())
    assert all(r.result is not None for r in out)
    assert tr.spans == [] and tr._open == []


def test_prefill_counters_equal_admit_shapes(engines):
    """``prefill_rows`` is batch x bucket of every prefill the engine ran,
    ``prefill_tokens`` the prompt tokens of the slots that needed one; an
    admission whose prompt is whole in the prefix cache adds to neither."""
    eng = engines["off"]
    eng.tracer = Tracer()
    st = eng.stats
    start = st.snapshot()
    shapes, needed = [], []
    prefill, admit = eng._prefill, eng.admit_prefill

    def prefill_shape(params, batch, **kw):
        shapes.append(batch["tokens"].shape)
        return prefill(params, batch, **kw)

    def admit_lengths(slot_tokens, slot_total):
        out = admit(slot_tokens, slot_total)
        needed.extend(len(slot_tokens[s]) for s, (_, info) in out.items()
                      if info.cached_logits is None)
        return out

    eng._prefill, eng.admit_prefill = prefill_shape, admit_lengths
    reqs = _requests(6, seed=5)
    try:
        _serve(eng, reqs)
    finally:
        eng._prefill = prefill
        del eng.admit_prefill                 # back to the bound method
    rows = st.prefill_rows - start.prefill_rows
    tokens = st.prefill_tokens - start.prefill_tokens
    assert rows == sum(b * s for b, s in shapes)
    assert tokens == sum(needed) == sum(len(r.tokens) for r in reqs)
    assert tokens < rows
    spans = [s for s in eng.tracer.spans if s.name == "admit.prefill"]
    assert sum(s.attrs["rows"] for s in spans) == rows
    assert sum(s.attrs["tokens"] for s in spans) == tokens

    # a page-aligned prompt served twice: the second admission is a
    # whole-prompt cache hit and runs no prefill
    before = st.snapshot()
    prompt = np.arange(1, 9, dtype=np.int32)          # two full pages
    _serve(eng, [Request(rid=0, tokens=prompt, max_new=2)])
    mid = st.snapshot()
    assert mid.prefill_tokens == before.prefill_tokens + 8
    (r,) = _serve(eng, [Request(rid=1, tokens=prompt, max_new=2)])
    assert r.stats.prefill_skipped
    assert (st.prefill_rows, st.prefill_tokens) == (mid.prefill_rows,
                                                    mid.prefill_tokens)


def test_annotated_spans_reach_the_profiler(engines, tmp_path):
    """``annotate=True``: every span is also a ``repro.<name>`` host event
    of the profiler's trace, one event per recorded span."""
    from jax.profiler import ProfileData

    eng = engines["strict"]
    _serve(eng, _requests(2))                         # compile outside
    eng.tracer = Tracer(annotate=True)
    with jax.profiler.trace(str(tmp_path)):
        _serve(eng, _requests(3))
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    seen: dict[str, int] = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("repro."):
                    seen[e.name] = seen.get(e.name, 0) + 1
    want: dict[str, int] = {}
    for s in eng.tracer.spans:
        want["repro." + s.name] = want.get("repro." + s.name, 0) + 1
    assert "repro.segment.wait" in want and seen == want


def test_fused_paged_program_carries_device_scopes(engines):
    """Every sub-layer scope reaches the op metadata of the compiled fused
    paged program, which the device trace carries as each op's name stack;
    the kernels keep the names of their jitted entries."""
    eng = engines["strict"]
    B = eng.batch
    low = eng._fused_paged.lower(
        eng.params, jnp.zeros((B, 1), jnp.int32), eng.pool.kv,
        jnp.zeros((B, eng.n_pmax), jnp.int32), jnp.zeros(B, jnp.int32),
        jnp.full(B, -1, jnp.int32), jnp.ones(B, bool),
        jnp.zeros(B, jnp.int32), jnp.float32(0), jax.random.PRNGKey(0),
        jnp.int32(1), jnp.int32(0), jnp.bool_(False), seg_cap=8,
        greedy=True)
    text = low.compile().as_text()
    stacks = re.findall(r'op_name="([^"]*)"', text)
    for scope in SCOPES:
        assert any(f"/{scope}/" in s for s in stacks), scope
    core = [s for s in stacks if "/attn.core/" in s]
    assert any("jit(flash_paged_decode_pallas)" in s for s in core)
    assert all(s.startswith("jit(_fused_paged_fn)/") for s in stacks
               if any(f"/{sc}/" in s for sc in SCOPES))
