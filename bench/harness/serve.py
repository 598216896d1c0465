"""Drive the program's serving path: build the engine from a cell's files,
warm up the cell's shapes, and fill the measured window with waves.

The program is entered through its public serving surface only:
``ServingEngine`` and ``RequestScheduler.serve``.  To time the layers, the
harness wraps three calls on the engine *instance* (never in ``src/``):
``admit_prefill`` and ``paged_segment``, which return host arrays and so
end when their work has, and the jitted ``_prefill``, only to record the
shape of the token array it is handed.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time

import jax
import numpy as np

from harness import traffic as tr


@dataclasses.dataclass
class Span:
    name: str
    t0: float
    t1: float
    attrs: dict


class Recorder:
    """Host spans and counters at the harness's layer boundaries.  With
    ``annotate`` set, each span is also a profiler ``TraceAnnotation`` so
    device idle gaps can be put down to what the host was doing."""

    def __init__(self, annotate: bool = False):
        self.annotate = annotate
        self.spans: list[Span] = []

    @contextlib.contextmanager
    def span(self, name: str, **attrs):
        ctx = (jax.profiler.TraceAnnotation(f"bench.{name}")
               if self.annotate else contextlib.nullcontext())
        t0 = time.perf_counter()
        with ctx:
            yield attrs
        self.spans.append(Span(name, t0, time.perf_counter(), attrs))


@dataclasses.dataclass
class Served:
    engine: object
    config: dict
    family: object             # the configuration's harness.arch module
    dims: object               # family.Dims
    spec: tr.Spec
    rec: Recorder
    first_token: dict          # id(prompt array) -> end of its admission
    owner: dict                # page id -> id(prompt array) of last owner
    pages: dict                # id(prompt array) -> its page list


def build(cell, seed: int, rec: Recorder) -> Served:
    """Engine of ``cell`` with weights of ``seed``, instrumented."""
    from repro.models.api import build_model
    from repro.serving.engine import ServingEngine

    conf, fam = cell.config, cell.family
    cfg = fam.program_config(conf)
    dims = fam.dims_of(conf)
    spec = tr.spec_of(cell.traffic)
    model = build_model(cfg, system=conf["system"],
                        rns_bits=int(conf.get("rns_bits", 4)))
    params = fam.program_params(fam.make_weights(seed, dims))
    want = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    got = jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), params)
    if jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), want) != got:
        raise ValueError(f"the program's parameter layout is not the one "
                         f"{fam.__name__}.program_params builds")
    engine = ServingEngine(model, params, batch=spec.batch,
                           s_max=tr.s_max(spec),
                           page_size=int(conf["page_size"]),
                           kv_format=conf["kv_format"],
                           policy=conf["policy"], paged=True)
    del params
    jax.block_until_ready(engine.params)
    served = Served(engine, conf, fam, dims, spec, rec, {}, {}, {})
    _instrument(served)
    return served


def _instrument(sv: Served) -> None:
    eng, rec = sv.engine, sv.rec
    admit, segment, prefill = (eng.admit_prefill, eng.paged_segment,
                               eng._prefill)
    shapes: list = []

    def prefill_shape(params, batch, **kw):
        shapes.append(tuple(batch["tokens"].shape))
        return prefill(params, batch, **kw)

    def admit_prefill(slot_tokens, slot_total):
        del shapes[:]
        with rec.span("admit") as a:
            out = admit(slot_tokens, slot_total)
        t1 = time.perf_counter()
        a["prompts"] = [len(v) for v in slot_tokens.values()]
        a["computed"] = [list(s) for s in shapes]
        for s, toks in slot_tokens.items():
            sv.first_token[id(toks)] = t1
            sv.pages[id(toks)] = list(out[s][1].pages)
            for p in out[s][1].pages:
                sv.owner[p] = id(toks)
        return out

    def paged_segment(tok0, pos0, remaining, eos_vec, done0, tabs, **kw):
        live = ~np.asarray(done0, bool)
        with rec.span("segment") as a:
            res = segment(tok0, pos0, remaining, eos_vec, done0, tabs, **kw)
        a["steps"] = int(res.steps)
        a["pos0"] = np.asarray(pos0)[live].tolist()
        a["remaining"] = np.asarray(remaining)[live].tolist()
        return res

    eng.admit_prefill = admit_prefill
    eng.paged_segment = paged_segment
    eng._prefill = prefill_shape


def bucket_groups(lengths, cap: int) -> list[int]:
    """One prompt length per power-of-two group of ``lengths`` (the
    longest of each), capped like the engine's prefill buckets."""
    best: dict[int, int] = {}
    for n in lengths:
        b = min(max(8, 1 << (int(n) - 1).bit_length()), cap)
        best[b] = max(best.get(b, 0), int(n))
    return sorted(best.values())


def warm_up(sv: Served) -> None:
    """Compile and run once every shape this cell's traffic uses: the
    prefill of each bucket its prompt lengths fall in, and the fused paged
    segment at the cap of its longest output (the engine serves every
    shorter segment from that compiled cap)."""
    eng, spec = sv.engine, sv.spec
    plens, olens = tr.wave_lengths(spec)
    rng = np.random.default_rng(0x5EED)
    for n in bucket_groups(plens, eng.n_pmax * eng.page_size):
        toks = rng.integers(spec.vocab_low, sv.dims.vocab, n).astype(np.int32)
        out = eng.admit_prefill({0: toks}, {0: n + 1})
        eng.pool.release(out[0][1].pages)
    B = eng.batch
    eng.paged_segment(np.zeros((B, 1), np.int32), np.zeros(B, np.int32),
                      np.zeros(B, np.int32), np.full(B, -1, np.int64),
                      np.ones(B, bool), np.zeros((B, eng.n_pmax), np.int32),
                      seg=int(olens.max()) - 1, stop_on_finish=True)
    sv.rec.spans.clear()
    sv.first_token.clear()
    sv.owner.clear()
    sv.pages.clear()


@dataclasses.dataclass
class Done:
    """One completed request."""
    prompt: np.ndarray
    tokens: np.ndarray
    first: float      # end of its admission (its first token exists)
    complete: float   # end of the segment that finished it


def run_window(sv: Served, seed: int, seconds: float, on_wave=None):
    """Consecutive waves while the window is open; the last one runs to
    its end.  Returns ``(t_start, t_end, done)``.  ``on_wave(i, t)`` is
    called after each wave (the traced run stops its trace there)."""
    from repro.serving.scheduler import Request, RequestScheduler

    done: list[Done] = []
    t_start = time.perf_counter()
    i = 0
    while time.perf_counter() - t_start < seconds:
        items = tr.make_wave(sv.spec, sv.dims.vocab, seed, i)
        reqs = [Request(rid=j, tokens=t, max_new=m)
                for j, (t, m) in enumerate(items)]
        with sv.rec.span("wave", index=i):
            t0 = time.perf_counter()
            out = RequestScheduler(sv.engine).serve(reqs)
        for r in out:
            done.append(Done(prompt=r.tokens, tokens=np.asarray(r.result),
                             first=sv.first_token[id(r.tokens)],
                             complete=t0 + r.stats.latency_s))
        i += 1
        if on_wave is not None:
            on_wave(i, time.perf_counter())
    return t_start, time.perf_counter(), done


def intact(sv: Served, d: Done) -> bool:
    """True where no later request was given any of ``d``'s pages, so its
    KV rows are still as the window wrote them."""
    key = id(d.prompt)
    return all(sv.owner.get(p) == key for p in sv.pages[key] if p)
