"""Typed serving telemetry: one surface for every counter the stack keeps.

Before this module the observability story was scattered: the engine grew
bare ``decode_steps`` / ``decode_dispatches`` / ``fused_retraces`` ints,
``GenerateResult`` carried its own copy of three of them, the scheduler's
``Request`` had seven loose fields, and the KV pool kept a separate
``PoolStats``.  Everything now lives here as typed dataclasses:

* :class:`EngineStats` — engine-lifetime counters (``engine.stats``), with
  the pool's :class:`PoolStats` and the corruption :class:`FaultStats`
  nested under it; ``engine.stats.snapshot()`` is the single entry point
  for a consistent point-in-time copy.
* :class:`RequestStats` — per-request telemetry (``request.stats`` on the
  scheduler's ``Request``, ``result.stats`` on ``GenerateResult``).
* :class:`FaultStats` — the redundant-residue corruption counters (new in
  the fault-tolerance work; these land *only* on the typed surface).

Timed spans of the same path live in :mod:`repro.serving.trace`
(``engine.tracer``, off by default); counters stay here.

The old attribute paths still work as ``DeprecationWarning`` property
shims (kept green under the ``-W error::DeprecationWarning`` CI variant);
:func:`deprecated_stat` builds them.
"""
from __future__ import annotations

import dataclasses
import warnings

__all__ = [
    "FaultStats",
    "PoolStats",
    "SpecStats",
    "RequestStats",
    "EngineStats",
    "deprecated_stat",
]


@dataclasses.dataclass
class FaultStats:
    """Redundant-residue corruption telemetry (see DESIGN.md §12)."""

    detected: int = 0        # residue inconsistencies observed (elements)
    corrected: int = 0       # faulty channels reconstructed (elements)
    weight_scrubs: int = 0   # scrub passes over resident weight planes
    kv_scrubs: int = 0       # scrub passes over resident KV pages
    # escalation-policy counters (DESIGN.md §15)
    syndromes: int = 0           # faulty elements flagged by the in-kernel
    #                              syndrome reduction (pre-repair)
    uncorrected: int = 0         # detected-but-uncorrectable elements left
    #                              in place (policy="detect"/"correct")
    replays: int = 0             # decode segments replayed after a repair
    recomputes: int = 0          # requests re-admitted through prefill
    pages_quarantined: int = 0   # pages retired from the pool for good

    def snapshot(self) -> "FaultStats":
        return dataclasses.replace(self)


@dataclasses.dataclass
class PoolStats:
    """KV page-pool telemetry (lifetime of the pool)."""

    pages_allocated: int = 0
    pages_freed: int = 0
    prefix_hits: int = 0     # prompt pages served from the prefix cache
    prefill_skips: int = 0   # whole-prompt cache hits (no prefill pass)
    evictions: int = 0       # cached-but-free pages reclaimed

    def snapshot(self) -> "PoolStats":
        return dataclasses.replace(self)


@dataclasses.dataclass
class SpecStats:
    """Speculative-decoding telemetry (see DESIGN.md §13).

    One verify step is one batched target-model call inside the fused
    spec loop; it emits between 1 and k+1 tokens per live slot, so
    ``mean_accepted_len`` > 1 is the whole point of drafting.
    """

    proposed: int = 0       # draft tokens proposed (k per live slot/step)
    accepted: int = 0       # ... accepted by the greedy verify rule
    emitted: int = 0        # tokens emitted through the spec loop
    verify_steps: int = 0   # batched verify steps (target-model calls)
    blocks: int = 0         # accepted blocks emitted (live slot-steps)

    @property
    def acceptance_rate(self) -> float:
        """Fraction of proposed draft tokens the target accepted."""
        return self.accepted / max(self.proposed, 1)

    @property
    def mean_accepted_len(self) -> float:
        """Tokens emitted per accepted block — the per-slot advance one
        verify step buys (1.0 = drafting bought nothing)."""
        return self.emitted / max(self.blocks, 1)

    def snapshot(self) -> "SpecStats":
        return dataclasses.replace(self)


@dataclasses.dataclass
class RequestStats:
    """Per-request telemetry, filled by the engine/scheduler."""

    decode_steps: int = 0          # fused decode steps this request rode in
    decode_dispatches: int = 0     # decode segments it participated in
    pages_allocated: int = 0       # KV pages newly allocated at admission
    pages_freed: int = 0           # KV pages released at retirement
    prefix_hits: int = 0           # prompt pages reused from the prefix cache
    prefill_skipped: bool = False  # whole prompt cached -> no prefill pass
    latency_s: float = 0.0         # serve() entry -> request completed
    faults_detected: int = 0       # corruption seen while this request rode
    faults_corrected: int = 0      # ... and repaired in-flight
    recomputes: int = 0            # times this request was recomputed after
    #                                an unrepairable fault (pages released,
    #                                prompt + emitted tokens re-admitted)
    spec: SpecStats | None = None  # speculative segments it rode in

    def snapshot(self) -> "RequestStats":
        return dataclasses.replace(
            self, spec=self.spec.snapshot() if self.spec is not None else None)


@dataclasses.dataclass
class EngineStats:
    """Engine-lifetime telemetry — ``engine.stats``.

    ``snapshot()`` deep-copies the nested stats so the result is a
    consistent point-in-time view (the live object keeps mutating).
    """

    decode_steps: int = 0        # decode tokens produced
    decode_dispatches: int = 0   # host->device decode dispatches
    # admission prefills: batch rows x bucket as computed, and the prompt
    # tokens of the slots that needed them (their ratio is the useful share)
    prefill_rows: int = 0
    prefill_tokens: int = 0
    fused_retraces: int = 0      # fused-loop retraces (new length buckets)
    # channel_shard plan resolutions that fell back to the replicated /
    # gathered decode layout (C not divisible by the tensor axis, or a
    # moduli set past the int32 partial-CRT bound).  Counted per plan
    # resolution — once per traced matmul, not per decode step — so a
    # nonzero value means the mesh/moduli pairing is mis-sharded, not that
    # every step gathered.  Mirrors runners.fallback_gather_count().
    fallback_gathers: int = 0
    faults: FaultStats = dataclasses.field(default_factory=FaultStats)
    pool: PoolStats | None = None   # shared with the engine's KVPagePool
    spec: SpecStats | None = None   # set when the engine runs with spec=

    def snapshot(self) -> "EngineStats":
        return dataclasses.replace(
            self,
            faults=self.faults.snapshot(),
            pool=self.pool.snapshot() if self.pool is not None else None,
            spec=self.spec.snapshot() if self.spec is not None else None,
        )


def deprecated_stat(owner: str, name: str, *, stats_attr: str = "stats",
                    alias: str | None = None) -> property:
    """A property shim forwarding ``obj.<name>`` to ``obj.<stats_attr>.<name>``
    with a :class:`DeprecationWarning` (read and write).

    ``alias`` names the field on the stats object when it differs from the
    legacy attribute name.
    """
    field = alias or name

    def _warn() -> None:
        warnings.warn(
            f"{owner}.{name} is deprecated; use {owner}.{stats_attr}.{field}",
            DeprecationWarning, stacklevel=3)

    def fget(self):
        _warn()
        return getattr(getattr(self, stats_attr), field)

    def fset(self, value):
        _warn()
        setattr(getattr(self, stats_attr), field, value)

    return property(fget, fset, doc=f"Deprecated alias of {stats_attr}.{field}.")
