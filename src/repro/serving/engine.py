"""Batched serving engine: prefill + greedy/temperature decode loop.

``ServingEngine`` owns the jitted prefill/decode steps for one model and
drives request batches: right-padded prompts prefill in one pass, then tokens
decode with the stacked-layer KV/SSM caches updated in place (functionally).
Static batching with slot reuse — the engine refills finished slots between
generate() calls; positions are uniform per batch (the decode-step contract),
which matches throughput-oriented TPU serving.

Decode loop (DESIGN.md §10): by default the whole loop is **one device
dispatch** — a jitted ``lax.while_loop`` carrying the cache, a
device-resident ``(B, max_new)`` token buffer, and per-slot EOS masks, so
the host synchronizes once per ``generate()`` instead of once per token
(the per-token round-trip dominated small-step decode latency).
``fused_loop=False`` keeps the original host-driven loop as the measured
baseline; both loops are bit-identical by construction (same jitted decode
step, same sampling fold-in, same EOS/step accounting — pinned by
tests/test_serving.py).

Under the (SD-)RNS systems the engine makes weights *residue-resident* at
construction (``prepare=True``, the default): ``model.prepare_params`` runs
the quantize-once / forward-convert-once pass, replacing every dense weight
— layer stacks, MoE expert stacks, the tied-embedding logits weight — with
a typed :class:`~repro.numerics.ResidueTensor`, so the steady-state decode
loop performs zero weight quantize or forward-convert work: each step
quantizes only the token activations and consumes the precomputed digit or
residue planes (DESIGN.md §7–8).  The prefill/decode jit signatures accept
either parameter form; prepared trees are ordinary pytrees (the tensors'
planes/scale are leaves, their moduli/layout metadata is static).

On the production mesh the same step functions lower with sharded caches —
launch/dryrun.py compiles exactly these for the decode_32k / long_500k cells.
"""
from __future__ import annotations

import dataclasses
import logging
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro import numerics as nx
from repro.models.api import Model
from repro.numerics import runners
from repro.numerics import ResidueTensor
from repro.numerics import kv_pages as kvp
from repro.parallel.sharding import get_shard_ctx
from repro.serving.kv_pool import KVPagePool
from repro.serving.spec import SpecConfig, accept_blocks
from repro.serving.stats import (EngineStats, RequestStats, SpecStats,
                                 deprecated_stat)
from repro.serving.trace import Tracer

__all__ = ["ServingEngine", "GenerateResult", "SegmentResult"]

logger = logging.getLogger(__name__)


@dataclasses.dataclass
class GenerateResult:
    tokens: np.ndarray          # (B, n_emitted) generated ids
    prefill_logits: np.ndarray  # (B, vocab) — logits of the *prefill* pass
    steps: int                  # decode steps actually executed
    stats: RequestStats = dataclasses.field(default_factory=RequestStats)

    # legacy counter attributes (property objects are not dataclass fields)
    decode_dispatches = deprecated_stat("GenerateResult", "decode_dispatches")
    pages_allocated = deprecated_stat("GenerateResult", "pages_allocated")
    pages_freed = deprecated_stat("GenerateResult", "pages_freed")


@dataclasses.dataclass
class SegmentResult:
    """One continuous-batching decode segment (one fused dispatch)."""
    tokens: np.ndarray   # (B, n) tokens emitted this segment, all slots
    steps: int           # decode steps executed (== n without spec)
    done: np.ndarray     # (B,) bool — per-slot finished mask at exit
    faults_detected: int = 0   # scrub detections during this segment
    faults_corrected: int = 0  # ... repaired before the dispatch ran
    # per-slot emitted counts: with speculative decoding slots advance by
    # ragged accepted-block jumps, so row s holds counts[s] valid tokens
    # (plain segments fill it with `steps` for every slot)
    counts: np.ndarray | None = None
    proposed: int = 0    # draft tokens proposed this segment (spec only)
    accepted: int = 0    # ... accepted by the greedy verify rule
    # (B,) bool under policy="strict": slots holding an unrepairable page —
    # their tokens this segment are untrusted and must be discarded; the
    # scheduler re-admits the request (prompt + previously emitted tokens)
    # through prefill instead of emitting corrupt output
    needs_recompute: np.ndarray | None = None


class ServingEngine:
    def __init__(self, model: Model, params: Any, *, batch: int,
                 s_max: int, cache_dtype=jnp.bfloat16, prepare: bool = True,
                 fused_loop: bool = True, paged: bool | None = None,
                 page_size: int = 64, kv_format: str = "bf16",
                 num_pages: int | None = None, prefix_cache: bool = True,
                 scrub: str = "off", spec=None, policy: str = "off",
                 quarantine_after: int = 3):
        """``prepare=True`` makes quantized weights residue-resident up
        front (identity under the bns backend); ``prepare=False`` keeps the
        convert-per-call path — useful only as a baseline to measure the
        conversion overhead against (benchmarks/serving_bench.py).

        ``fused_loop=True`` (default) runs the whole decode loop as one
        jitted ``lax.while_loop`` dispatch; ``fused_loop=False`` keeps the
        per-token host loop as the measured baseline.

        Paged KV serving (the default where supported): ``paged=None``
        enables the block-table page pool whenever the family has a paged
        decode path, the fused loop is on, and no mesh is installed;
        ``paged=True`` demands it and raises where it is unsupported;
        ``paged=False`` pins the dense contiguous cache.  ``page_size``
        is the page length in tokens (== the split-KV flash-decode chunk);
        ``kv_format`` picks the page storage — ``"bf16"`` (bit-identical
        to the dense cache), ``"rns8"`` or ``"rns4"`` (packed residue
        planes, ~1.9x / ~3.6x fewer cache bytes, tolerance-pinned);
        ``num_pages`` sizes the pool (default: full capacity for ``batch``
        slots plus one dump page); ``prefix_cache`` enables shared-prefix
        page reuse on the scheduler's admission path.

        ``scrub="decode"`` turns on the redundant-residue scrub policy:
        before every decode dispatch the engine syndrome-checks all
        redundant residue state — resident weight planes (``nx.scrub``)
        and redundant KV pages (``kv_pages.verify_pages``) — repairing any
        single-channel fault in place and counting it under
        ``engine.stats.faults``.  A no-op unless the model weights carry a
        redundant moduli set (``build_model(rns_mset=...)``) or the pool
        uses a redundant page format (``kv_format="rns8r"``).
        ``scrub="rotate:k"`` amortizes the policy: the redundant units
        (weight planes + the K/V page pools) are round-robined into ``k``
        groups and each dispatch checks one group, so full coverage costs
        ``k`` dispatches at ~1/k the per-dispatch scrub time.

        ``spec=`` turns on speculative decoding (DESIGN.md §13): a
        :class:`~repro.serving.spec.SpecConfig` or a ``"ngram"`` /
        ``"ngram:k"`` / ``"rns:k"`` string.  The drafter proposes k
        tokens per step, the target verifies the whole block in one
        batched paged step inside the same single-dispatch fused loop,
        and greedy acceptance emits the longest agreed prefix —
        bit-identical tokens, fewer target steps.  Requires the paged
        fused loop and greedy sampling.

        ``policy=`` turns on the fault-escalation layer (DESIGN.md §15)
        over redundant KV pages (``kv_format="rns8r"``): the paged decode
        kernel accumulates a per-(slot, layer) *syndrome count* as an
        extra reduction output — integrity checking rides the decode hot
        path for free, with no separate ``verify_pages`` sweep.  Nonzero
        syndromes escalate: ``"detect"`` only counts them
        (``stats.faults.syndromes``); ``"correct"`` additionally runs a
        *targeted* page repair on the flagged (slot, layer) pages and
        replays the segment from repaired state (single faults produce
        bit-identical tokens); ``"strict"`` further quarantines pages
        that fail repair or re-fault ``quarantine_after`` times (sticky
        cells leave the free list for good) and flags requests holding an
        unrepairable page for *recompute* — corrupt tokens are never
        emitted.  Needs the paged fused loop; not supported with
        ``spec=``."""
        self.model = model
        self.params = model.prepare_params(params) if prepare else params
        self.prepared = prepare
        self.batch = batch
        self.s_max = s_max
        self.cache_dtype = cache_dtype
        self.fused_loop = fused_loop
        self._prefill = jax.jit(model.prefill, static_argnames=("s_max",))
        self._decode = jax.jit(model.decode, donate_argnums=(2,))
        self._fused = jax.jit(self._fused_loop_fn,
                              static_argnames=("max_new_cap", "greedy"),
                              donate_argnums=(2,))
        self._scrub_groups = 0      # rotate:k group count (0 = not rotating)
        self._scrub_cursor = 0      # which group the next dispatch checks
        if scrub.startswith("rotate:"):
            self._scrub_groups = int(scrub.split(":", 1)[1])
            if self._scrub_groups < 1:
                raise ValueError(f"scrub rotate group count must be >= 1, "
                                 f"got {scrub!r}")
        elif scrub not in ("off", "decode"):
            raise ValueError(
                f"scrub must be 'off', 'decode' or 'rotate:k', got {scrub!r}")
        self.scrub = scrub
        self.stats = EngineStats()
        # host spans of admission and segments (repro.serving.trace); set
        # ``engine.tracer = Tracer(...)`` to record them
        self.tracer = Tracer(enabled=False)
        # Baseline for the channel_shard fallback counter: the runner-level
        # count is process-lifetime, the stat is engine-lifetime.
        self._fallback_base = runners.fallback_gather_count()
        self._trace_count = 0
        self._last_scrub = (0, 0)   # (detected, corrected) of the last pass
        self._compiled_buckets: dict[str, set[int]] = {}

        supported = (fused_loop and model.decode_paged is not None
                     and get_shard_ctx() is None)
        if paged is None:
            paged = supported
        elif paged and not supported:
            raise ValueError(
                f"paged serving is unsupported here (fused_loop={fused_loop}, "
                f"family={model.cfg.family!r}, mesh="
                f"{get_shard_ctx() is not None}); pass paged=None to let the "
                "engine choose, or paged=False for the dense cache")
        self.paged = paged
        self.page_size = page_size
        self.kv_format = kv_format
        if paged:
            self.n_pmax = -(-s_max // page_size)
            if num_pages is None:
                num_pages = 1 + batch * self.n_pmax
            cfg = model.cfg
            self.pool = KVPagePool(cfg.n_layers, num_pages, page_size,
                                   cfg.n_kv, cfg.hd, fmt=kv_format,
                                   dtype=cache_dtype,
                                   prefix_cache=prefix_cache)
            self._scatter = jax.jit(kvp.scatter_prefill,
                                    static_argnames=("page_size",),
                                    donate_argnums=(0,))
            self._fused_paged = jax.jit(self._fused_paged_fn,
                                        static_argnames=("seg_cap", "greedy"),
                                        donate_argnums=(2,))
            self.stats.pool = self.pool.stats
        else:
            self.pool = None

        self.spec = None
        self._drafter = None
        if spec is not None:
            if not self.paged:
                raise ValueError(
                    "spec= needs the paged fused decode loop (paged=True, "
                    "fused_loop=True, a family with a paged decode path, "
                    "and no mesh)")
            from repro.serving.drafters import make_drafter
            self.spec = SpecConfig.parse(spec)
            self._drafter = make_drafter(
                self.spec, model, self.params, batch=batch,
                num_pages=self.pool.num_pages, page_size=page_size,
                n_pmax=self.n_pmax, cache_dtype=cache_dtype)
            self._spec_state = self._drafter.init_state(batch)
            self._fused_spec = jax.jit(self._fused_spec_fn,
                                       static_argnames=("seg_cap",),
                                       donate_argnums=(2, 3))
            self.stats.spec = SpecStats()

        if policy not in ("off", "detect", "correct", "strict"):
            raise ValueError(
                f"policy must be 'off', 'detect', 'correct' or 'strict', "
                f"got {policy!r}")
        if policy != "off":
            if not (self.paged and self.pool.fmt.is_residue
                    and self.pool.fmt.redundant):
                raise ValueError(
                    "policy= needs paged serving with a redundant KV page "
                    "format (kv_format='rns8r') — the in-kernel syndrome "
                    "reduction reads the witness lanes")
            if spec is not None:
                raise ValueError(
                    "policy= is not supported with speculative decoding "
                    "(the spec verify loop is syndrome-free)")
        if quarantine_after < 1:
            raise ValueError(
                f"quarantine_after must be >= 1, got {quarantine_after}")
        self.policy = policy
        self._quarantine_after = quarantine_after
        # bound on repair->replay rounds within one segment before residual
        # faults escalate (recompute under "strict", counted under
        # "correct"); sticky cells re-fault every round, so this also caps
        # the time to quarantine at one segment
        self._fault_max_replays = max(2, quarantine_after)
        self._last_recompute = np.zeros(batch, bool)

    # legacy counter attributes (see repro.serving.stats)
    decode_steps = deprecated_stat("ServingEngine", "decode_steps")
    decode_dispatches = deprecated_stat("ServingEngine", "decode_dispatches")
    fused_retraces = deprecated_stat("ServingEngine", "fused_retraces")

    # -- trace accounting (satellite: silent per-bucket retraces) ------------

    def fused_cache_size(self) -> int:
        """Compiled-trace count of the active fused decode loop."""
        if self._drafter is not None:
            fn = self._fused_spec
        else:
            fn = self._fused_paged if self.paged else self._fused
        return fn._cache_size()

    def _pick_bucket(self, kind: str, n: int) -> int:
        """Bucket cap for a decode loop of length ``n``, reusing traces.

        A length landing between already-compiled buckets runs under the
        *next-larger compiled* cap instead of retracing its own power-of-
        two bucket — the loop length is a runtime operand, so any compiled
        cap >= the wanted bucket serves it bit-identically (only the
        donated token-buffer width changes, and callers slice it anyway).
        """
        want = self._bucket(n)
        caps = self._compiled_buckets.setdefault(kind, set())
        bigger = [c for c in caps if c >= want]
        if bigger:
            return min(bigger)
        caps.add(want)
        return want

    def _note_fused_dispatch(self, bucket: int) -> None:
        cur = self.fused_cache_size()
        if cur > self._trace_count:
            if self._trace_count > 0:
                self.stats.fused_retraces += cur - self._trace_count
            logger.info(
                "fused decode loop traced for bucket cap=%d (%d trace(s) "
                "total, %d retrace(s))", bucket, cur,
                self.stats.fused_retraces)
            self._trace_count = cur

    def _sync_fallback_gathers(self) -> None:
        """Refresh ``stats.fallback_gathers`` from the runner-level counter.

        The planner warns and counts once per plan resolution (i.e. per
        traced matmul under a channel_shard context that could not take
        the partial-CRT psum path) — nonzero here means this engine's
        mesh/moduli pairing is mis-sharded and decode is quietly running
        the gathered layout.
        """
        self.stats.fallback_gathers = (
            runners.fallback_gather_count() - self._fallback_base)

    # -- redundant-residue scrub (DESIGN.md §12) -----------------------------

    def _scrub_launch(self) -> list:
        """Dispatch the scrub pass *without* host-syncing its counts.

        Walks the resident parameter tree (redundant ``rns`` weight planes
        via :func:`repro.numerics.scrub`) and the paged KV pool (redundant
        page formats via :func:`repro.numerics.kv_pages.verify_pages`),
        swapping each unit's repaired (donated) device arrays in
        immediately and collecting the ``(detected, corrected)`` *device
        scalars* of every launched pass.  The decode dispatch that follows
        consumes the repaired arrays, so the device orders scrub before
        decode through plain data dependencies — but the host never blocks
        between the two: the counts are read by :meth:`_drain_scrub` after
        the decode segment is already enqueued.  (The old in-line scrub
        host-synced its counts before every dispatch, serializing scrub
        with decode.)

        Under ``rotate:k`` the scrubbable units — each redundant weight
        plane, plus the K and V page pools — are numbered in a fixed
        (tree-deterministic) order and partitioned round-robin into ``k``
        groups; one group is checked per pass and the cursor advances, so
        any persistent fault is caught within ``k`` dispatches at ~1/k
        the per-dispatch cost (gated in BENCH_fault.json).
        """
        if self.scrub == "off":
            return []
        groups = self._scrub_groups          # 0 => scrub everything
        active = self._scrub_cursor % groups if groups else 0
        unit = 0
        pending = []                         # (det, cor) device scalars
        scrubbed_weights = False

        def due() -> bool:
            nonlocal unit
            mine = not groups or unit % groups == active
            unit += 1
            return mine

        def fix(t):
            nonlocal scrubbed_weights
            if (isinstance(t, ResidueTensor) and t.layout == "rns"
                    and t.mset.redundant and due()):
                t, d, c = nx.scrub(t, sync=False, donate=True)
                pending.append((d, c))
                scrubbed_weights = True
            return t

        self.params = jax.tree_util.tree_map(
            fix, self.params,
            is_leaf=lambda x: isinstance(x, ResidueTensor))
        if scrubbed_weights:
            self.stats.faults.weight_scrubs += 1
        if (self.paged and self.pool.fmt.is_residue
                and self.pool.fmt.redundant):
            kv = self.pool.kv
            k_pool, v_pool = kv.k, kv.v
            scrubbed_kv = False
            if due():
                k_pool, dk, ck = kvp.verify_pages(k_pool, sync=False,
                                                  donate=True)
                pending.append((dk, ck))
                scrubbed_kv = True
            if due():
                v_pool, dv, cv = kvp.verify_pages(v_pool, sync=False,
                                                  donate=True)
                pending.append((dv, cv))
                scrubbed_kv = True
            if scrubbed_kv:
                self.pool.kv = kvp.PagedKV(k_pool, v_pool)
                self.stats.faults.kv_scrubs += 1
        if groups:
            self._scrub_cursor += 1
        return pending

    def _drain_scrub(self, pending: list) -> tuple[int, int]:
        """Host-sync the launched scrub counts and fold them into stats."""
        det = cor = 0
        for d, c in pending:
            det += int(d)
            cor += int(c)
        self.stats.faults.detected += det
        self.stats.faults.corrected += cor
        return det, cor

    def _scrub_pass(self) -> tuple[int, int]:
        """Synchronous scrub: launch + drain in one call.

        Returns the ``(detected, corrected)`` element counts of this pass.
        No-op unless ``scrub="decode"`` / ``"rotate:k"`` and some state
        actually carries redundancy.  The dispatch path uses the split
        :meth:`_scrub_launch` / :meth:`_drain_scrub` pair instead, so the
        scrub overlaps with the decode segment.
        """
        return self._drain_scrub(self._scrub_launch())

    @staticmethod
    def _bucket(n: int) -> int:
        """Power-of-two trace bucket for decode-loop lengths."""
        return max(8, 1 << (max(n, 1) - 1).bit_length())

    def generate(self, batch_inputs: dict[str, Any], *, max_new: int,
                 prompt_len: int | None = None,
                 temperature: float = 0.0,
                 key: jax.Array | None = None,
                 eos: int | np.ndarray | None = None,
                 active: np.ndarray | None = None) -> GenerateResult:
        """Prefill ``batch_inputs`` then decode up to ``max_new`` tokens.

        ``prompt_len``: position of the first generated token (defaults to
        the prompt length inferred from the inputs).

        ``eos``: early-stop token — a scalar, or a per-slot ``(B,)`` array
        (entries < 0 never match, for slots without an EOS).  Decoding
        stops as soon as every *active* slot has emitted its EOS; slots
        marked inactive in ``active`` (e.g. the scheduler's unfilled
        padding slots) are treated as already finished.  Without ``eos``
        the loop always runs the full ``max_new`` tokens.
        """
        logits, cache = self._prefill(self.params, batch_inputs,
                                      s_max=self.s_max)
        prefill_logits = np.asarray(logits)   # before the decode loop
        if prompt_len is None:
            if "tokens" in batch_inputs:
                prompt_len = batch_inputs["tokens"].shape[1]
                if "patches" in batch_inputs:
                    prompt_len += batch_inputs["patches"].shape[1]
            else:
                prompt_len = 0
        tok = self._sample(logits, temperature, key, 0)
        B = tok.shape[0]
        if self.paged:
            if self._drafter is not None:
                if "tokens" not in batch_inputs:
                    raise ValueError(
                        "spec= needs token prompts (drafters condition on "
                        "the token stream)")
                self._last_prompts = np.asarray(batch_inputs["tokens"])
            return self._generate_paged(tok, cache, prompt_len, max_new,
                                        temperature, key, eos, active,
                                        prefill_logits)
        if self.fused_loop:
            return self._generate_fused(tok, cache, prompt_len, max_new,
                                        temperature, key, eos, active,
                                        prefill_logits)
        done = None
        if eos is not None:
            eos = np.broadcast_to(np.asarray(eos, np.int64), (B,))
            done = np.zeros(B, bool) if active is None else \
                ~np.asarray(active, bool)
        f_det, f_cor = self._scrub_pass()
        outs = []
        steps = 0
        for i in range(max_new):
            t_np = np.asarray(tok[:, 0])
            outs.append(t_np)
            if done is not None:
                done = done | ((eos >= 0) & (t_np == eos))
                if done.all():
                    break   # every live slot has hit EOS — stop decoding
            if i + 1 == max_new:
                break       # last token emitted; no step needed for it
            pos = jnp.int32(prompt_len + i)
            logits, cache = self._decode(self.params, tok, cache, pos)
            steps += 1
            tok = self._sample(logits, temperature, key, i + 1)
        self.stats.decode_steps += steps
        self.stats.decode_dispatches += steps
        self._sync_fallback_gathers()
        return GenerateResult(
            tokens=np.stack(outs, axis=1), prefill_logits=prefill_logits,
            steps=steps,
            stats=RequestStats(decode_steps=steps, decode_dispatches=steps,
                               faults_detected=f_det,
                               faults_corrected=f_cor))

    # -- fused decode loop ---------------------------------------------------

    def _generate_fused(self, tok, cache, prompt_len, max_new, temperature,
                        key, eos, active, prefill_logits) -> GenerateResult:
        """One device dispatch for the whole decode loop."""
        B = tok.shape[0]
        if eos is not None:
            eos_vec = np.broadcast_to(np.asarray(eos, np.int64), (B,))
            done0 = np.zeros(B, bool) if active is None else \
                ~np.asarray(active, bool)
        else:
            # no EOS: the done mask stays all-False, matching the host
            # loop's "run the full max_new tokens" contract
            eos_vec = np.full(B, -1, np.int64)
            done0 = np.zeros(B, bool)
        greedy = temperature <= 0.0 or key is None
        # the token buffer is sized by a power-of-two bucket and the actual
        # max_new rides as a runtime operand — scheduler rounds with varying
        # max_new (max over the packed requests) retrace per *bucket*, not
        # per value (the host loop compiled model.decode exactly once; a
        # per-value retrace of the whole fused graph would dwarf the
        # per-token dispatch overhead this loop exists to eliminate); a
        # max_new landing between compiled buckets reuses the next-larger
        # compiled trace instead of retracing (_pick_bucket)
        cap = self._pick_bucket("fused", max_new)
        f_det, f_cor = self._scrub_pass()
        buf, n, steps, _ = self._fused(
            self.params, tok, cache, jnp.int32(prompt_len),
            jnp.asarray(np.clip(eos_vec, -1, 2**31 - 1), jnp.int32),
            jnp.asarray(done0),
            jnp.float32(temperature),
            key if key is not None else jax.random.PRNGKey(0),
            jnp.int32(max_new),
            max_new_cap=cap, greedy=greedy)
        self._note_fused_dispatch(cap)
        n = int(n)          # the single host sync of the whole decode loop
        steps = int(steps)
        self.stats.decode_steps += steps
        self.stats.decode_dispatches += 1
        self._sync_fallback_gathers()
        return GenerateResult(
            tokens=np.asarray(buf)[:, :n], prefill_logits=prefill_logits,
            steps=steps,
            stats=RequestStats(decode_steps=steps, decode_dispatches=1,
                               faults_detected=f_det,
                               faults_corrected=f_cor))

    def _fused_loop_fn(self, params, tok0, cache, start_pos, eos, done0,
                       temperature, key, max_new, *, max_new_cap: int,
                       greedy: bool):
        """Device-resident decode loop (jitted; cache donated).

        Carry: (i, halt, tok, cache, done, buf, steps).  Iteration i
        records token i into the on-device buffer, updates the EOS mask,
        and — unless every live slot is done or this was the last token —
        runs one decode step and samples token i+1.  Mirrors the host loop
        statement for statement so the two are bit-identical.

        ``max_new`` is a runtime scalar (<= the static ``max_new_cap``
        sizing the buffer), so varying request budgets reuse one trace
        per bucket.
        """
        B = tok0.shape[0]
        buf0 = jnp.zeros((B, max_new_cap), jnp.int32)

        @jax.named_scope("sample")
        def sample(logits, step):
            if greedy:
                t = jnp.argmax(logits, axis=-1)
            else:
                k = jax.random.fold_in(key, step)
                t = jax.random.categorical(k, logits / temperature, axis=-1)
            return t[:, None].astype(jnp.int32)

        def cond(st):
            _, halt = st[0], st[1]
            return jnp.logical_not(halt)

        def body(st):
            i, _, tok, cache, done, buf, steps = st
            buf = jax.lax.dynamic_update_slice(buf, tok, (0, i))
            done = done | ((eos >= 0) & (tok[:, 0] == eos))
            halt = jnp.all(done) | (i + 1 >= max_new)

            def step_fn(op):
                tok, cache, steps = op
                logits, cache2 = self.model.decode(params, tok, cache,
                                                   start_pos + i)
                return sample(logits, i + 1), cache2, steps + 1

            tok, cache, steps = jax.lax.cond(
                halt, lambda op: op, step_fn, (tok, cache, steps))
            return (i + 1, halt, tok, cache, done, buf, steps)

        init = (jnp.int32(0), jnp.bool_(False), tok0, cache, done0, buf0,
                jnp.int32(0))
        i, _, _, cache, _, buf, steps = jax.lax.while_loop(cond, body, init)
        # the final cache is returned (and discarded by the caller) so the
        # donated input cache can alias an output — without it XLA must
        # keep a second KV-cache copy live for the whole loop
        return buf, i, steps, cache

    # -- paged decode loop ---------------------------------------------------

    def _fused_paged_fn(self, params, tok0, kv, tab, pos0, eos, done_in,
                        remaining, temperature, key, seg, key_base,
                        stop_flag, *, seg_cap: int, greedy: bool):
        """Device-resident paged decode *segment* (jitted; pool donated).

        The caller has already recorded ``tok0`` (the prefill sample, or
        the last token of the previous segment); iteration i feeds the
        current token through the paged decode step at per-slot position
        ``pos0 + i`` and records the *next* token into ``buf[:, i]``.

        Per-slot ``remaining`` budgets (tokens left after ``tok0``) feed
        the done mask, so ragged request budgets coexist in one segment;
        ``seg`` (<= the static ``seg_cap`` sizing the buffer) bounds the
        segment length, and ``stop_flag`` halts the segment as soon as any
        slot *newly* finishes — the continuous scheduler's signal to admit
        a queued request into the freed slot.  Finished slots keep decoding
        harmlessly until the segment ends: their writes land in their own
        (already exclusive) pages or the dump page, and the scheduler
        truncates their rows on the host — this keeps the loop's sampled
        token stream bit-identical to the dense fused loop.

        Under a fault ``policy`` every decode step also emits the
        in-kernel per-(slot, layer) KV syndrome counts; the carry folds
        steps together with ``jnp.maximum`` (a persistent fault is
        re-counted by every step that reads it — max, not sum, keeps the
        count equal to the number of faulty elements) and the segment
        returns the ``(B, L)`` map for the escalation layer.  Without a
        policy the syndrome output is constant zeros and the decode step
        runs syndrome-free.
        """
        B = tok0.shape[0]
        L = self.model.cfg.n_layers
        buf0 = jnp.zeros((B, seg_cap), jnp.int32)
        syn0 = jnp.zeros((B, L), jnp.int32)
        with_syn = self.policy != "off"
        done0 = (done_in | ((eos >= 0) & (tok0[:, 0] == eos))
                 | (remaining <= 0))
        fin0 = done0

        @jax.named_scope("sample")
        def sample(logits, step):
            if greedy:
                t = jnp.argmax(logits, axis=-1)
            else:
                k = jax.random.fold_in(key, step)
                t = jax.random.categorical(k, logits / temperature, axis=-1)
            return t[:, None].astype(jnp.int32)

        def cond(st):
            return jnp.logical_not(st[1])

        def body(st):
            i, _, tok, kv, done, buf, steps, syn = st
            if with_syn:
                logits, kv2, syn_i = self.model.decode_paged(
                    params, tok, kv, tab, pos0 + i,
                    page_size=self.page_size, cache_dtype=self.cache_dtype,
                    with_syndrome=True)
                syn = jnp.maximum(syn, syn_i)
            else:
                logits, kv2 = self.model.decode_paged(
                    params, tok, kv, tab, pos0 + i,
                    page_size=self.page_size, cache_dtype=self.cache_dtype)
            tok2 = sample(logits, key_base + i + 1)
            buf = jax.lax.dynamic_update_slice(buf, tok2, (0, i))
            done = (done | ((eos >= 0) & (tok2[:, 0] == eos))
                    | (i + 1 >= remaining))
            halt = (jnp.all(done) | (i + 1 >= seg)
                    | (stop_flag & jnp.any(done & ~fin0)))
            return (i + 1, halt, tok2, kv2, done, buf, steps + 1, syn)

        init = (jnp.int32(0), jnp.all(done0) | (seg <= 0), tok0, kv,
                done0, buf0, jnp.int32(0), syn0)
        (i, _, _, kv, done, buf, steps,
         syn) = jax.lax.while_loop(cond, body, init)
        return buf, i, steps, kv, done, syn

    # -- speculative decode loop (DESIGN.md §13) -----------------------------

    def _fused_spec_fn(self, params, tok0, kv, dstate, tab, pos0, eos,
                       done_in, remaining, seg, stop_flag, *, seg_cap: int):
        """Device-resident speculative decode segment (jitted; pool and
        drafter state donated).

        Each iteration: the drafter proposes ``k`` tokens, the target
        verifies ``tok0 + drafts`` in one batched ``verify_paged`` step
        (writing all k+1 KV rows; rejected rows are overwritten by the
        next iteration at the same positions, and the per-row ``kv_len``
        masking means they are never read), and the greedy acceptance
        rule (:func:`repro.serving.spec.accept_blocks`) emits 1..k+1
        tokens per live slot.  Slots therefore advance *raggedly*: the
        carry tracks per-slot positions and emitted counts, finished
        slots freeze (their re-verifies rewrite identical bytes), and the
        caller reads row ``b``'s first ``cnt[b]`` buffer entries.

        Every emitted token is the argmax of a target logits row over
        exactly the prefix the plain loop would have used, so the token
        streams are bit-identical — drafting only changes how many rows
        one verify step retires (``steps`` counts verify iterations, not
        tokens).
        """
        B = tok0.shape[0]
        k = self._drafter.k
        kp1 = k + 1
        buf0 = jnp.zeros((B, seg_cap), jnp.int32)
        done0 = (done_in | ((eos >= 0) & (tok0[:, 0] == eos))
                 | (remaining <= 0))
        fin0 = done0
        j = jnp.arange(kp1)[None, :]
        rows = jnp.arange(B)[:, None]

        def cond(st):
            return jnp.logical_not(st[1])

        def body(st):
            it, _, tok, kv, dstate, done, pos, cnt, buf, prop, acc = st
            live = ~done
            drafts, dstate = self._drafter.propose(dstate, tok, pos, tab)
            vtok = jnp.concatenate([tok, drafts], axis=1)       # (B, k+1)
            logits, kv = self.model.verify_paged(
                params, vtok, kv, tab, pos,
                page_size=self.page_size, cache_dtype=self.cache_dtype)
            blk = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # (B, k+1)
            m, n_acc = accept_blocks(drafts, blk, eos=eos,
                                     budget=remaining - cnt, live=live)
            idx = jnp.where(j < m[:, None], cnt[:, None] + j, seg_cap)
            buf = buf.at[rows, idx].set(blk, mode="drop")
            cnt = cnt + m
            pos = pos + m
            tok = jnp.where(live[:, None],
                            jnp.take_along_axis(
                                blk, jnp.maximum(m - 1, 0)[:, None], axis=1),
                            tok)
            hit_eos = jnp.any((j < m[:, None]) & (eos[:, None] >= 0)
                              & (blk == eos[:, None]), axis=1)
            done = done | (live & (hit_eos | (cnt >= remaining)))
            dstate = self._drafter.observe(dstate, blk, m, pos - m, tab)
            prop = prop + k * jnp.sum(live.astype(jnp.int32))
            acc = acc + jnp.sum(jnp.where(
                live, jnp.minimum(n_acc, jnp.maximum(m - 1, 0)), 0))
            halt = (jnp.all(done) | (it + 1 >= seg)
                    | (stop_flag & jnp.any(done & ~fin0)))
            return (it + 1, halt, tok, kv, dstate, done, pos, cnt, buf,
                    prop, acc)

        init = (jnp.int32(0), jnp.all(done0) | (seg <= 0), tok0, kv, dstate,
                done0, jnp.asarray(pos0, jnp.int32),
                jnp.zeros(B, jnp.int32), buf0, jnp.int32(0), jnp.int32(0))
        (it, _, _, kv, dstate, done, _, cnt, buf,
         prop, acc) = jax.lax.while_loop(cond, body, init)
        return buf, cnt, it, kv, dstate, done, prop, acc

    def _dispatch_segment(self, tok0, pos0, eos_vec, done0, remaining,
                          tabs, seg, temperature, key, key_base,
                          stop_on_finish, greedy):
        """Shared fused-paged dispatch: generate() and the continuous
        scheduler both funnel through here.  Returns ``(tokens, steps,
        done, counts, proposed, accepted)`` — tokens truncated to the
        emitted width, ``counts`` the per-slot valid-token counts (ragged
        under speculation, uniform ``steps`` otherwise)."""
        if self._drafter is not None and not greedy:
            raise ValueError("speculative decoding (spec=) is greedy-"
                             "acceptance only; run with temperature=0")
        cap = self._pick_bucket("spec" if self._drafter is not None
                                else "paged", seg)
        tr = self.tracer
        with tr.span("segment.prepare"):
            # scrub is *launched* (repaired arrays swapped in, counts left
            # on device) and drained only after the decode dispatch is
            # enqueued — the device orders scrub before decode via the data
            # dependency, the host never blocks between them (DESIGN.md §15)
            scrub_pending = self._scrub_launch()
            tok_dev = jnp.asarray(tok0, jnp.int32)
            eos_dev = jnp.asarray(np.clip(eos_vec, -1, 2**31 - 1), jnp.int32)
            tab_dev = jnp.asarray(tabs, jnp.int32)
            pos_dev = jnp.asarray(pos0, jnp.int32)
            done_dev = jnp.asarray(done0)
            rem_dev = jnp.asarray(remaining, jnp.int32)
            key_dev = key if key is not None else jax.random.PRNGKey(0)
        B = tok_dev.shape[0]
        if self._drafter is not None:
            with tr.span("segment.dispatch"):
                (buf, cnt, steps, kv, dstate, done, prop,
                 acc) = self._fused_spec(
                    self.params, tok_dev, self.pool.kv, self._spec_state,
                    tab_dev, pos_dev, eos_dev, done_dev, rem_dev,
                    jnp.int32(seg), jnp.bool_(stop_on_finish), seg_cap=cap)
                self.pool.kv = kv          # donated in, aliased out
                self._spec_state = dstate  # ditto (drafter KV / history)
                self._note_fused_dispatch(cap)
            with tr.span("segment.wait"):
                self._last_scrub = self._drain_scrub(scrub_pending)
                counts = np.asarray(cnt)   # the single host sync
            self._last_recompute = np.zeros(B, bool)
            with tr.span("segment.readback"):
                steps, prop, acc = int(steps), int(prop), int(acc)
                n = int(counts.max()) if counts.size else 0
                toks, done = np.asarray(buf)[:, :n], np.asarray(done)
            self.stats.decode_steps += steps
            self.stats.decode_dispatches += 1
            self._sync_fallback_gathers()
            sp = self.stats.spec
            sp.proposed += prop
            sp.accepted += acc
            sp.emitted += int(counts.sum())
            sp.verify_steps += steps
            sp.blocks += prop // self._drafter.k
            return toks, steps, done, counts, prop, acc

        def run_once():
            # same operands every time: a replay after an in-place page
            # repair recomputes the segment bit-identically to a fault-free
            # run (the in-kernel syndrome fires *after* the faulty read, so
            # the first run's tokens are untrusted once syn != 0)
            return self._fused_paged(
                self.params, tok_dev, self.pool.kv, tab_dev, pos_dev, eos_dev,
                done_dev, rem_dev, jnp.float32(temperature), key_dev,
                jnp.int32(seg), jnp.int32(key_base),
                jnp.bool_(stop_on_finish), seg_cap=cap, greedy=greedy)

        with tr.span("segment.dispatch"):
            buf, n, steps, kv, done, syn = run_once()
            self.pool.kv = kv      # donated in, aliased out
            self._note_fused_dispatch(cap)
        with tr.span("segment.wait"):
            # the first read that blocks on the segment: its syndromes under
            # a fault policy (the escalation layer's input), else n
            self._last_scrub = self._drain_scrub(scrub_pending)
            syn = np.asarray(syn) if self.policy != "off" else None
            n = int(n)
        recompute = np.zeros(B, bool)
        if syn is not None and syn.any():
            with tr.span("segment.escalate"):
                buf, n, steps, done, recompute = self._fault_escalate(
                    run_once, buf, n, steps, done, syn, np.asarray(tabs))
                n = int(n)
        self._last_recompute = recompute
        with tr.span("segment.readback"):
            steps = int(steps)
            toks, done = np.asarray(buf)[:, :n], np.asarray(done)
        self.stats.decode_steps += steps
        self.stats.decode_dispatches += 1
        self._sync_fallback_gathers()
        counts = np.full(B, steps, np.int64)
        return toks, steps, done, counts, 0, 0

    # -- fault-domain escalation (DESIGN.md §15) -----------------------------

    def _fault_repair(self, layers, tabs_np, slots) -> dict[int, list[int]]:
        """Targeted verify/repair of the pages the flagged slots hold.

        Slices the flagged ``layers`` x pages rectangle out of both page
        pools, runs the CRT repair there (``kv_pages.repair_pages``), and
        scatters the fixed planes back.  Folds element counts into
        ``stats.faults`` and returns the per-page ledger
        ``{page_id: [detected, uncorrectable]}`` for pages that showed any
        fault.  (The fault-injection harness wraps this method to model
        sticky cells: it re-flips its bit after every repair.)
        """
        pool = self.pool
        pages = sorted({int(p) for s in slots for p in tabs_np[s] if p})
        layers = sorted(int(la) for la in layers)
        ledger: dict[int, list[int]] = {}
        if not pages or not layers:
            return ledger
        new = {}
        for name, t in (("k", pool.kv.k), ("v", pool.kv.v)):
            t2, det, cor, unc = kvp.repair_pages(t, layers, pages)
            new[name] = t2
            self.stats.faults.detected += int(det.sum())
            self.stats.faults.corrected += int(cor.sum())
            self.stats.faults.uncorrected += int(unc.sum())
            page_det = det.sum(axis=0)
            page_unc = unc.sum(axis=0)
            for i, pid in enumerate(pages):
                if page_det[i]:
                    rec = ledger.setdefault(pid, [0, 0])
                    rec[0] += int(page_det[i])
                    rec[1] += int(page_unc[i])
        pool.kv = kvp.PagedKV(new["k"], new["v"])
        return ledger

    def _fault_escalate(self, run_once, buf, n, steps, done, syn_np,
                        tabs_np):
        """Escalate nonzero in-kernel syndromes: detect -> correct ->
        quarantine -> recompute.

        ``syn_np`` is the segment's ``(B, L)`` per-(slot, layer)
        faulty-element map, read to the host; the caller escalates only
        when it is nonzero.  Clean segments (the overwhelmingly common
        case) cost that one small read — no repair pass, no standalone
        ``verify_pages`` sweep on the hot path.

        Escalation rounds (``policy="correct"``/``"strict"``): repair the
        flagged slots' pages at the flagged layers, charge each faulty page
        one strike (``pool.note_fault``), quarantine pages that failed
        repair (double faults) or reached ``quarantine_after`` strikes, and
        replay the segment from repaired state — bit-identical to a
        fault-free run when the repair stuck.  Slots holding an
        unrepairable page are flagged for recompute under ``"strict"``
        (their tokens are discarded by the caller, never emitted); rounds
        are bounded by ``_fault_max_replays``, after which residual dirty
        slots escalate to recompute as well.
        """
        pool = self.pool
        B = tabs_np.shape[0]
        recompute = np.zeros(B, bool)
        self.stats.faults.syndromes += int(syn_np.sum())
        if self.policy == "detect":
            return buf, n, steps, done, recompute
        replays = 0
        while True:
            flagged = [s for s in np.nonzero(syn_np.sum(axis=1))[0]
                       if not recompute[s]]
            if not flagged:
                break
            layers = np.nonzero(syn_np.sum(axis=0))[0]
            ledger = self._fault_repair(layers, tabs_np, flagged)
            for pid, (det, unc) in sorted(ledger.items()):
                strikes = pool.note_fault(pid)
                if unc or strikes >= self._quarantine_after:
                    if pool.quarantine(pid):
                        self.stats.faults.pages_quarantined += 1
                        logger.warning(
                            "KV page %d quarantined (%d strike(s), %d "
                            "uncorrectable element(s))", pid, strikes, unc)
                    if self.policy == "strict":
                        for s in range(B):
                            if pid in tabs_np[s]:
                                recompute[s] = True
            if recompute.all():
                break
            if replays >= self._fault_max_replays:
                # residual dirty slots: repairs did not stick within the
                # round budget — never emit their tokens under "strict"
                if self.policy == "strict":
                    for s in flagged:
                        recompute[s] = True
                break
            buf, n, steps, kv, done, syn = run_once()
            self.pool.kv = kv
            self.stats.faults.replays += 1
            replays += 1
            syn_np = np.asarray(syn)
            fresh = int(syn_np.sum())
            if fresh == 0:
                break
            self.stats.faults.syndromes += fresh
        return buf, n, steps, done, recompute

    def _generate_paged(self, tok, cache, prompt_len, max_new, temperature,
                        key, eos, active, prefill_logits) -> GenerateResult:
        """generate() over the paged pool — same contract (and, for bf16
        pages, the same bits) as the dense fused loop."""
        B = tok.shape[0]
        if eos is not None:
            eos_vec = np.broadcast_to(np.asarray(eos, np.int64), (B,))
            done0 = np.zeros(B, bool) if active is None else \
                ~np.asarray(active, bool)
        else:
            eos_vec = np.full(B, -1, np.int64)
            done0 = np.zeros(B, bool)
        greedy = temperature <= 0.0 or key is None
        pool = self.pool
        pool.reset()    # generate() owns the whole pool for this call
        a0 = pool.stats.snapshot()
        # speculative verifies overshoot the last emitted row by up to k
        # positions — allocate the headroom so the tail writes stay on the
        # slot's own pages (past-capacity rows fall to the dump page)
        k_spec = self._drafter.k if self._drafter is not None else 0
        n_pages = min(-(-(prompt_len + max_new + k_spec) // self.page_size),
                      self.n_pmax)
        slot_pages = [pool.alloc(n_pages) for _ in range(B)]
        tabs = np.stack([pool.tab_row(p, self.n_pmax) for p in slot_pages])
        tab_dev = jnp.asarray(tabs)
        pool.kv = self._scatter(pool.kv, cache.k, cache.v, tab_dev,
                                page_size=self.page_size)
        if self._drafter is not None:
            prompts = np.asarray(self._last_prompts)
            tok_np = np.asarray(tok[:, 0])
            self._spec_state = self._drafter.init_state(B)
            self._spec_state = self._drafter.begin(
                self._spec_state,
                {b: prompts[b] for b in range(B)},
                {b: int(tok_np[b]) for b in range(B)},
                jnp.asarray(prompts), tab_dev, prompts.shape[1])
        # tok0 is recorded on the host; the device segment emits the rest.
        # remaining = max_new - 1 further tokens; seg bounds the segment at
        # the same count, so steps/halting match the dense loop exactly.
        recomputes = 0
        while True:
            buf, steps, _, counts, prop, acc = self._dispatch_segment(
                tok, np.full(B, prompt_len, np.int32), eos_vec, done0,
                np.full(B, max_new - 1, np.int32), tab_dev,
                max_new - 1, temperature, key, 0, False, greedy)
            if not (self.policy == "strict" and self._last_recompute.any()
                    and recomputes < 2):
                break
            # recompute: slots held an unrepairable (now quarantined) page.
            # Release everything, re-allocate from the shrunk free list, and
            # re-scatter the surviving dense prefill cache (self._scatter
            # donates only the pool, so `cache` is still alive) — the retry
            # recomputes all tokens from position 0, bit-identical to a
            # fault-free run on healthy pages.
            recomputes += int(self._last_recompute.sum())
            self.stats.faults.recomputes += int(self._last_recompute.sum())
            for p in slot_pages:
                pool.release(p)
            slot_pages = [pool.alloc(n_pages) for _ in range(B)]
            tabs = np.stack([pool.tab_row(p, self.n_pmax)
                             for p in slot_pages])
            tab_dev = jnp.asarray(tabs)
            pool.kv = self._scatter(pool.kv, cache.k, cache.v, tab_dev,
                                    page_size=self.page_size)
        tokens = np.concatenate([np.asarray(tok), buf], axis=1)
        for p in slot_pages:
            pool.release(p)
        f_det, f_cor = self._last_scrub
        spec_stats = None
        if self._drafter is not None:
            spec_stats = SpecStats(proposed=prop, accepted=acc,
                                   emitted=int(counts.sum()),
                                   verify_steps=steps,
                                   blocks=prop // self._drafter.k)
        return GenerateResult(
            tokens=tokens, prefill_logits=prefill_logits, steps=steps,
            stats=RequestStats(
                decode_steps=steps, decode_dispatches=1,
                pages_allocated=(pool.stats.pages_allocated
                                 - a0.pages_allocated),
                pages_freed=pool.stats.pages_freed - a0.pages_freed,
                faults_detected=f_det, faults_corrected=f_cor,
                recomputes=recomputes, spec=spec_stats))

    # -- continuous-batching admission / segment API -------------------------

    def admit_prefill(self, slot_tokens: dict[int, np.ndarray],
                      slot_total: dict[int, int]):
        """Admit requests into slots: allocate pages (sharing prompt-prefix
        pages), prefill the slots that need it in one right-padded batch,
        and scatter the fresh KV into the pool.

        ``slot_tokens`` maps slot index -> prompt tokens; ``slot_total``
        bounds each request's final KV length (prompt + budget).  Returns
        ``{slot: (prefill_logits_row, AdmitInfo)}`` — rows come from the
        prefill dispatch or, when the whole prompt was page-aligned and
        prefix-cached, from the logits cache (the prefill is skipped).
        """
        pool = self.pool
        tr = self.tracer
        with tr.span("admit.pages"):
            infos = {s: pool.admit(np.asarray(slot_tokens[s]), slot_total[s])
                     for s in sorted(slot_tokens)}
        need = [s for s, inf in infos.items() if inf.cached_logits is None]
        out = {s: (infos[s].cached_logits, infos[s]) for s in infos
               if infos[s].cached_logits is not None}
        if not need:
            # prefill skipped everywhere; the drafter still registers the
            # prompts (shadow pages already hold the draft KV — page
            # content is a pure function of the token prefix per model)
            self._spec_begin(slot_tokens, out, None, None, 0)
            return out
        s_buck = min(self._bucket(max(len(slot_tokens[s]) for s in need)),
                     self.n_pmax * self.page_size)
        prompts = np.zeros((self.batch, s_buck), np.int64)
        logits_at = np.zeros(self.batch, np.int32)
        tabs = np.zeros((self.batch, self.n_pmax), np.int32)
        for s in need:
            toks = np.asarray(slot_tokens[s])
            prompts[s, : len(toks)] = toks
            logits_at[s] = len(toks) - 1
            tabs[s] = pool.tab_row(infos[s].pages, self.n_pmax)
        rows = self.batch * s_buck
        used = sum(len(slot_tokens[s]) for s in need)
        self.stats.prefill_rows += rows
        self.stats.prefill_tokens += used
        with tr.span("admit.prefill", attrs={"rows": rows, "tokens": used}):
            logits, cache = self._prefill(
                self.params, {"tokens": jnp.asarray(prompts)}, s_max=s_buck,
                logits_at=jnp.asarray(logits_at))
            logits = np.asarray(logits)
        # non-admitted rows keep all-dump tab rows, so their padding
        # garbage scatters into the dump page; prefix-shared pages are
        # rewritten with identical bytes (page contents are a pure
        # function of the token prefix)
        with tr.span("admit.scatter"):
            pool.kv = self._scatter(pool.kv, cache.k, cache.v,
                                    jnp.asarray(tabs),
                                    page_size=self.page_size)
        for s in need:
            pool.remember_logits(slot_tokens[s], logits[s])
            out[s] = (logits[s], infos[s])
        self._spec_begin(slot_tokens, out, jnp.asarray(prompts),
                         jnp.asarray(tabs), s_buck)
        return out

    def _spec_begin(self, slot_tokens, out, prompts, tabs, s_max) -> None:
        """Register newly admitted prompts with the drafter (spec= only):
        the n-gram drafter seeds its history rows; the rns drafter runs its
        own prefill over the same padded batch and scatters the shadow
        pages (one extra *prefill* dispatch — decode stays one dispatch
        per segment)."""
        if self._drafter is None:
            return
        tok0 = {s: int(np.argmax(out[s][0])) for s in slot_tokens}
        self._spec_state = self._drafter.begin(
            self._spec_state,
            {s: np.asarray(slot_tokens[s]) for s in slot_tokens},
            tok0, prompts, tabs, s_max)

    @property
    def spec_lookahead(self) -> int:
        """Draft block size k (0 without spec=) — the KV-position headroom
        admissions must reserve for speculative verify overshoot."""
        return self._drafter.k if self._drafter is not None else 0

    def paged_segment(self, tok0, pos0, remaining, eos_vec, done0, tabs, *,
                      seg: int, stop_on_finish: bool,
                      temperature: float = 0.0,
                      key: jax.Array | None = None,
                      key_base: int = 0) -> SegmentResult:
        """Run one continuous-batching decode segment (one fused dispatch).

        ``tok0 (B, 1)``: each slot's current last token (already emitted);
        ``pos0 (B,)``: the position its KV row lands at; ``remaining``:
        per-slot token budgets after ``tok0``.  ``stop_on_finish=True``
        ends the segment early when a slot newly finishes, so the
        scheduler can retire it and admit from the queue.
        """
        greedy = temperature <= 0.0 or key is None
        buf, steps, done, counts, prop, acc = self._dispatch_segment(
            tok0, pos0, eos_vec, done0, remaining,
            tabs, seg, temperature, key, key_base, stop_on_finish, greedy)
        f_det, f_cor = self._last_scrub
        return SegmentResult(tokens=buf, steps=steps, done=done,
                             faults_detected=f_det, faults_corrected=f_cor,
                             counts=counts, proposed=prop, accepted=acc,
                             needs_recompute=self._last_recompute.copy())

    @staticmethod
    def _sample(logits: jax.Array, temperature: float,
                key: jax.Array | None, step: int) -> jax.Array:
        if temperature <= 0.0 or key is None:
            tok = jnp.argmax(logits, axis=-1)
        else:
            k = jax.random.fold_in(key, step)
            tok = jax.random.categorical(k, logits / temperature, axis=-1)
        return tok[:, None].astype(jnp.int32)
