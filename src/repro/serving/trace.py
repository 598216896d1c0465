"""Host spans of the serving path: where the scheduler and engine spend
wall time, on the same clock as a device trace.

One :class:`Tracer` hangs on each engine (``engine.tracer``) and the
scheduler serving through that engine uses it too.  It is disabled by
default; then ``span()`` hands back one shared null context and reads no
clock, allocates nothing and appends nothing.  Enabled, each span records
its name, ``time.perf_counter`` bounds, the id of the span open around it,
the request ids it serves (inherited from that parent where the caller
names none) and a few attributes, and keeps them in memory (``spans``).
With ``annotate=True`` each span is also a
``jax.profiler.TraceAnnotation`` named ``repro.<name>``, so a profiler
trace shows it on the device trace's clock.

Counters stay on :class:`~repro.serving.stats.EngineStats`; this module
holds spans only.

Spans, outermost first (children indented)::

    serve                   RequestScheduler.serve
      sched.admit           one admission round (rids admitted)
        admit.pages         host page allocation (pool.admit)
        admit.prefill       prefill enqueue + the logits read-back
        admit.scatter       enqueue of the page scatter
      sched.segment         one pass of the continuous loop (live rids)
        segment.prepare     host operands to device, scrub launch
        segment.dispatch    enqueue of the fused paged loop
        segment.wait        first blocking read (syndromes, else n)
        segment.escalate    repair / replay, only on nonzero syndromes
        segment.readback    tokens and done mask to host
      sched.retire          one request retired (also inside the above)
"""
from __future__ import annotations

import dataclasses
import time

import jax

__all__ = ["Span", "Tracer"]


@dataclasses.dataclass
class Span:
    id: int
    name: str
    parent: int | None     # id of the span open around this one
    t0: float              # time.perf_counter() at entry
    t1: float              # ... at exit
    rids: tuple            # request ids this span serves
    attrs: dict


class _Null:
    """The shared context of a disabled tracer."""

    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False


_NULL = _Null()


class _Open:
    """The context of one enabled span."""

    __slots__ = ("tracer", "span", "note")

    def __init__(self, tracer: "Tracer", span: Span):
        self.tracer = tracer
        self.span = span
        self.note = None

    def __enter__(self) -> Span:
        tr, sp = self.tracer, self.span
        if tr.annotate:
            self.note = jax.profiler.TraceAnnotation("repro." + sp.name)
            self.note.__enter__()
        tr._open.append(sp)
        sp.t0 = time.perf_counter()
        return sp

    def __exit__(self, *exc):
        sp = self.span
        sp.t1 = time.perf_counter()
        self.tracer._open.pop()
        if self.note is not None:
            self.note.__exit__(*exc)
        self.tracer.spans.append(sp)
        return False


class Tracer:
    """Spans at the serving path's boundaries (see the module docstring).

    ``Tracer()`` records; ``Tracer(enabled=False)`` is the engine's
    default and records nothing.
    """

    def __init__(self, enabled: bool = True, annotate: bool = False):
        self.enabled = enabled
        self.annotate = annotate
        self.spans: list[Span] = []
        self._open: list[Span] = []
        self._ids = 0

    def span(self, name: str, rids=None, attrs: dict | None = None):
        """Context of one span.  ``rids``: the requests it serves, as
        request ids or objects with a ``rid``; ``None`` inherits the open
        parent's.  Yields the :class:`Span` (``None`` when disabled)."""
        if not self.enabled:
            return _NULL
        parent = self._open[-1] if self._open else None
        if rids is None:
            rids = parent.rids if parent is not None else ()
        else:
            rids = tuple(getattr(r, "rid", r) for r in rids)
        self._ids += 1
        sp = Span(id=self._ids, name=name,
                  parent=parent.id if parent is not None else None,
                  t0=0.0, t1=0.0, rids=rids, attrs=attrs or {})
        return _Open(self, sp)
