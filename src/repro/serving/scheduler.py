"""Request scheduler: continuous batching over the paged engine.

Two scheduling modes, picked by the engine's configuration:

* **Continuous batching** (``engine.paged``, the default): requests admit
  into any free slot *mid-decode* — the engine decodes in fused segments
  that halt as soon as a slot finishes (``stop_on_finish``), the scheduler
  retires it immediately (freeing its KV pages back to the pool) and
  admits the next queued request into the freed slot with one batched
  right-padded prefill.  Ragged prompt lengths and token budgets coexist
  in one batch: each slot carries its own position and remaining budget
  into the segment, so no request waits for the round's stragglers.
  Identical prompt prefixes share KV pages (and page-aligned repeat
  prompts skip prefill entirely) via the engine's pool.

* **Fixed rounds** (dense engines, ``fused_loop=False`` baselines): the
  original batch-boundary admission — pack up to ``batch`` requests,
  right-align prompts to the round's maximum, decode until every member
  hits EOS or ``max_new``, then refill all slots from the queue.

Per-request results keep their own lengths; both modes fill the same
telemetry fields on the returned :class:`Request`.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Sequence

import numpy as np

from repro.serving.engine import ServingEngine
from repro.serving.stats import RequestStats, SpecStats, deprecated_stat

__all__ = ["Request", "RequestScheduler"]


@dataclasses.dataclass
class Request:
    rid: int
    tokens: np.ndarray            # (prompt_len,) int32
    max_new: int
    eos: int | None = None

    result: np.ndarray | None = None   # filled by the scheduler
    # per-request telemetry (filled by the scheduler) — see
    # repro.serving.stats.RequestStats for the field inventory
    stats: RequestStats = dataclasses.field(default_factory=RequestStats)

    # legacy telemetry attributes (property objects are not dataclass fields)
    decode_steps = deprecated_stat("Request", "decode_steps")
    decode_dispatches = deprecated_stat("Request", "decode_dispatches")
    pages_allocated = deprecated_stat("Request", "pages_allocated")
    pages_freed = deprecated_stat("Request", "pages_freed")
    prefix_hits = deprecated_stat("Request", "prefix_hits")
    prefill_skipped = deprecated_stat("Request", "prefill_skipped")
    latency_s = deprecated_stat("Request", "latency_s")


@dataclasses.dataclass
class _Slot:
    """Host-side state of one live request slot (continuous mode)."""
    req: Request
    emitted: list[int]            # tokens emitted so far (incl. tok0)
    tab: np.ndarray               # (n_pmax,) block-table row
    pages: list[int]              # pages to release at retirement

    @property
    def rid(self) -> int:
        return self.req.rid


class RequestScheduler:
    def __init__(self, engine: ServingEngine, *, pad_token: int = 0):
        self.engine = engine
        self.pad = pad_token

    def serve(self, requests: Sequence[Request]) -> list[Request]:
        """Serve all requests; returns them with ``result`` filled."""
        queue = list(requests)
        done: list[Request] = []
        self._t0 = time.perf_counter()
        with self.engine.tracer.span("serve", queue):
            if self.engine.paged:
                done = self._serve_continuous(queue)
            else:
                B = self.engine.batch
                while queue:
                    round_reqs = queue[:B]
                    queue = queue[B:]
                    done += self._run_round(round_reqs)
        return sorted(done, key=lambda r: r.rid)

    # -- continuous batching (paged engine) ----------------------------------

    def _serve_continuous(self, queue: list[Request]) -> list[Request]:
        eng = self.engine
        tr = eng.tracer
        B = eng.batch
        cap = eng.n_pmax * eng.page_size      # per-request KV capacity
        slots: dict[int, _Slot] = {}
        finished: list[Request] = []
        # recompute resume prefixes: tokens a request had already (trustedly)
        # emitted before an unrepairable fault forced its pages to be dropped.
        # On re-admission the prefix rides the prompt through prefill, so the
        # request resumes exactly where it left off — bit-identical to a
        # fault-free run, because prefill logits match decode logits
        # position-for-position.
        resume: dict[int, list[int]] = {}

        def admit(free: list[int]) -> None:
            batch_toks: dict[int, np.ndarray] = {}
            batch_total: dict[int, int] = {}
            pend: dict[int, Request] = {}
            for s in free:
                if not queue:
                    break
                r = queue.pop(0)
                pend[s] = r
                toks = np.asarray(r.tokens, np.int32)
                resumed = resume.get(id(r))
                if resumed:
                    toks = np.concatenate(
                        [toks, np.asarray(resumed, np.int32)])
                batch_toks[s] = toks
                # spec_lookahead: speculative verifies overshoot the last
                # emitted row by up to k positions — reserve the headroom
                # (the resumed prefix is part of max_new, so the bound is
                # unchanged by recompute re-admissions)
                batch_total[s] = min(
                    len(r.tokens) + r.max_new + eng.spec_lookahead, cap)
            if pend:
                with tr.span("sched.admit", pend.values()):
                    seat(pend, batch_toks, batch_total)

        def seat(pend: dict[int, Request], batch_toks: dict[int, np.ndarray],
                 batch_total: dict[int, int]) -> None:
            """Prefill the popped requests and seat them in their slots."""
            admitted = eng.admit_prefill(batch_toks, batch_total)
            for s, r in pend.items():
                logits, info = admitted[s]
                r.stats.pages_allocated += info.pages_allocated
                r.stats.prefix_hits += info.prefix_hits
                r.stats.prefill_skipped = info.cached_logits is not None
                resumed = resume.pop(id(r), None)
                if resumed is None:
                    emitted = [int(np.argmax(logits))]
                else:
                    # re-admission: the prefill only rebuilt the KV pages
                    # for prompt + trusted prefix.  The next token must come
                    # from a *decode* step over those (quantized) pages —
                    # prefill logits attend over the full-precision prefill
                    # cache, which under a lossy page format (rns8r) need
                    # not argmax-match the paged decode the clean run took
                    # at this position.  Seeding the slot with the resumed
                    # prefix (and no prefill-sampled token) makes the next
                    # segment retrace the decode path bit-identically.
                    emitted = list(resumed)
                tok0 = emitted[-1]
                slot = _Slot(req=r, emitted=emitted,
                             tab=eng.pool.tab_row(info.pages, eng.n_pmax),
                             pages=info.pages)
                if (r.eos is not None and tok0 == r.eos) \
                        or len(slot.emitted) >= r.max_new:
                    retire(slot)          # finished on the prefill token
                else:
                    slots[s] = slot

        def retire(slot: _Slot) -> None:
            r = slot.req
            with tr.span("sched.retire", (r,)):
                toks = np.asarray(slot.emitted[: r.max_new], np.int32)
                if r.eos is not None:
                    hits = np.nonzero(toks == r.eos)[0]
                    if hits.size:
                        toks = toks[: hits[0] + 1]
                r.result = toks
                freed_before = eng.pool.stats.pages_freed
                eng.pool.release(slot.pages)
                r.stats.pages_freed = (eng.pool.stats.pages_freed
                                       - freed_before)
                r.stats.latency_s = time.perf_counter() - self._t0
                finished.append(r)

        def segment() -> None:
            """One fused decode segment over the live slots, then their
            bookkeeping: tokens appended, finished requests retired."""
            tok0 = np.zeros((B, 1), np.int32)
            pos0 = np.zeros(B, np.int32)
            remaining = np.zeros(B, np.int32)
            eos_vec = np.full(B, -1, np.int64)
            done0 = np.ones(B, bool)
            tabs = np.zeros((B, eng.n_pmax), np.int32)
            for s, sl in slots.items():
                r = sl.req
                tok0[s, 0] = sl.emitted[-1]
                pos0[s] = len(r.tokens) + len(sl.emitted) - 1
                remaining[s] = r.max_new - len(sl.emitted)
                if r.eos is not None:
                    eos_vec[s] = r.eos
                done0[s] = False
                tabs[s] = sl.tab
            seg = int(remaining.max())
            res = eng.paged_segment(
                tok0, pos0, remaining, eos_vec, done0, tabs,
                seg=seg, stop_on_finish=bool(queue))
            if res.needs_recompute is not None and res.needs_recompute.any():
                # strict fault policy: these slots held a page that could not
                # be repaired — the segment's tokens for them are untrusted.
                # Discard them, drop the pages (quarantined ones never return
                # to the free list) and re-admit prompt + trusted prefix
                # through prefill at the head of the queue.
                for s in list(slots):
                    if not res.needs_recompute[s]:
                        continue
                    sl = slots.pop(s)
                    r = sl.req
                    eng.pool.release(sl.pages)
                    resume[id(r)] = list(sl.emitted)
                    r.stats.recomputes += 1
                    eng.stats.faults.recomputes += 1
                    queue.insert(0, r)
            for s, sl in list(slots.items()):
                r = sl.req
                # per-slot counts: speculative segments advance slots by
                # ragged accepted-block jumps, so row s holds counts[s]
                # valid tokens (plain segments fill counts with steps)
                avail = res.steps if res.counts is None else int(res.counts[s])
                take = min(avail, r.max_new - len(sl.emitted))
                row = res.tokens[s, :take]
                stop = None
                if r.eos is not None:
                    hits = np.nonzero(row == r.eos)[0]
                    if hits.size:
                        stop = int(hits[0]) + 1
                sl.emitted += [int(t) for t in row[:stop]]
                r.stats.decode_steps += res.steps
                r.stats.decode_dispatches += 1
                if res.proposed:
                    # segment-wide drafting telemetry: like the scrub
                    # counters, every co-resident request rode the same
                    # verify steps, so each carries the segment's counts
                    if r.stats.spec is None:
                        r.stats.spec = SpecStats()
                    r.stats.spec.proposed += res.proposed
                    r.stats.spec.accepted += res.accepted
                    r.stats.spec.emitted += take
                    r.stats.spec.verify_steps += res.steps
                    r.stats.spec.blocks += res.proposed // eng.spec_lookahead
                # scrub counters are pool/param-wide per segment — every
                # co-resident request observed (and survived) the same
                # faults, so each carries the segment's counts
                r.stats.faults_detected += res.faults_detected
                r.stats.faults_corrected += res.faults_corrected
                if (stop is not None
                        or len(sl.emitted) >= r.max_new):
                    del slots[s]
                    retire(sl)

        while queue or slots:
            free = [s for s in range(B) if s not in slots]
            if queue and free:
                admit(free)
            if not slots:
                continue    # admitted requests all finished on prefill
            with tr.span("sched.segment", slots.values()):
                segment()
        return finished

    # -- fixed rounds (dense / baseline engines) -----------------------------

    def _run_round(self, reqs: list[Request]) -> list[Request]:
        B = self.engine.batch
        plen = max(len(r.tokens) for r in reqs)
        max_new = max(r.max_new for r in reqs)
        prompts = np.full((B, plen), self.pad, np.int32)
        # Early stop: the engine halts the decode loop once every *active*
        # slot has emitted its EOS — unfilled padding slots are marked
        # inactive so they can never pin the round to the full max_new.
        # Requests without an EOS keep their slot live for the whole round
        # (entries < 0 never match a token id).
        eos_vec = np.full(B, -1, np.int64)
        active = np.zeros(B, bool)
        for i, r in enumerate(reqs):
            # right-align so the final prompt token sits at position plen-1
            prompts[i, plen - len(r.tokens):] = r.tokens
            active[i] = True
            if r.eos is not None:
                eos_vec[i] = r.eos
        has_eos = any(r.eos is not None for r in reqs)
        out = self.engine.generate({"tokens": prompts}, max_new=max_new,
                                   prompt_len=plen,
                                   eos=eos_vec if has_eos else None,
                                   active=active)
        for i, r in enumerate(reqs):
            toks = out.tokens[i, : r.max_new]
            if r.eos is not None:
                hits = np.nonzero(toks == r.eos)[0]
                if hits.size:
                    toks = toks[: hits[0] + 1]
            r.result = toks
            r.stats.decode_steps = out.steps
            r.stats.decode_dispatches = out.stats.decode_dispatches
            r.stats.pages_allocated = out.stats.pages_allocated
            r.stats.pages_freed = out.stats.pages_freed
            r.stats.faults_detected = out.stats.faults_detected
            r.stats.faults_corrected = out.stats.faults_corrected
            # every round member returns at the round boundary — the short
            # requests' latency is pinned to the round's straggler
            r.stats.latency_s = time.perf_counter() - self._t0
        return reqs
