"""Query-lifecycle spans and the opt-in JAX profiler hook.

A `Trace` is one traced unit of work (a query's life, a tick, a graph
update) holding an ordered list of named `Span`s. Spans are wall-clock
(`time.perf_counter`) intervals opened either bracketed::

    with trace.span("solve_device"):
        jax.block_until_ready(out)

or split across call sites (a query's queue time starts at submit and ends
inside a later tick)::

    trace.begin("queue")
    ...                       # other code, other calls
    trace.end("queue")

JAX dispatch is asynchronous, so a span around a jitted call measures HOST
time (trace/dispatch) unless the result is fenced. The serve path therefore
separates `solve_dispatch` (enqueue to the device stream) from
`solve_device` (a `jax.block_until_ready` fence) — the device span is the
only place the tick blocks on the accelerator, so host and device time
never alias. `Span.kind` records which side a span timed.

The `Tracer` owns a bounded ring of completed traces (newest kept) so a
long-running service can always answer "show me the last N queries" without
growing. A disabled tracer hands out `NULL_TRACE`, which absorbs the whole
API at a cost of one attribute lookup per call.

`profiled(logdir)` is the deep-dive hook: it wraps a region in
`jax.profiler.trace` when a logdir is given (view with TensorBoard or
Perfetto), raising if the profiler cannot start, and is a free no-op
otherwise.

This module (any function) and the service `_harvest` are the ONLY
sanctioned blocking-fence points: `repro.analysis`'s JL006 rule flags
`block_until_ready`/`device_get` anywhere else
(`LintConfig.blocking_allowed` is the allowlist; see
docs/static-analysis.md).
"""
from __future__ import annotations

import time
from collections import deque
from contextlib import contextmanager
from dataclasses import dataclass, field

__all__ = ["Span", "Trace", "Tracer", "NULL_TRACE", "profiled"]


@dataclass
class Span:
    """One named interval inside a trace. `kind` is "host" or "device"."""

    name: str
    start: float
    end: float = 0.0
    kind: str = "host"

    @property
    def duration(self) -> float:
        return max(0.0, self.end - self.start)

    @property
    def closed(self) -> bool:
        return self.end != 0.0


class Trace:
    """Ordered spans for one traced unit (query, tick, or update)."""

    __slots__ = ("name", "meta", "spans", "_open", "created")

    def __init__(self, name: str, **meta):
        self.name = name
        self.meta = meta
        self.spans: list[Span] = []
        self._open: dict[str, Span] = {}
        self.created = time.perf_counter()

    def begin(self, name: str, kind: str = "host") -> None:
        """Open a span; re-opening an already-open name restarts it."""
        sp = Span(name=name, start=time.perf_counter(), kind=kind)
        self._open[name] = sp
        self.spans.append(sp)

    def end(self, name: str) -> float:
        """Close the span opened under `name`; returns its duration.
        Ending a span that was never begun is a no-op returning 0.0 (a
        cache-hit query never opens batch-form/solve spans)."""
        sp = self._open.pop(name, None)
        if sp is None:
            return 0.0
        sp.end = time.perf_counter()
        return sp.duration

    @contextmanager
    def span(self, name: str, kind: str = "host"):
        self.begin(name, kind=kind)
        try:
            yield self
        finally:
            self.end(name)

    def mark(self, name: str, kind: str = "host") -> None:
        """Record a zero-width event (e.g. "submit")."""
        now = time.perf_counter()
        self.spans.append(Span(name=name, start=now, end=now, kind=kind))

    def duration(self, name: str) -> float:
        """Total closed duration of all spans named `name`."""
        return sum(s.duration for s in self.spans
                   if s.name == name and s.closed)

    def span_names(self) -> list[str]:
        return [s.name for s in self.spans]

    def total(self) -> float:
        """Wall time from trace creation to the latest closed span end."""
        ends = [s.end for s in self.spans if s.closed]
        return max(ends) - self.created if ends else 0.0

    def as_dict(self) -> dict:
        return {
            "name": self.name,
            "meta": dict(self.meta),
            "total_s": self.total(),
            "spans": [
                {"name": s.name, "kind": s.kind, "start_s": s.start - self.created,
                 "duration_s": s.duration}
                for s in self.spans if s.closed
            ],
        }


class _NullTrace(Trace):
    """Shared do-nothing trace: accepts the full Trace API, records nothing.
    Handed out by a disabled Tracer so call sites never branch."""

    def __init__(self):
        super().__init__("null")

    def begin(self, name, kind="host"):
        pass

    def end(self, name):
        return 0.0

    def mark(self, name, kind="host"):
        pass


NULL_TRACE = _NullTrace()


class Tracer:
    """Factory + bounded retention ring for traces.

    `start(...)` returns a live Trace when enabled, else `NULL_TRACE`.
    Completed traces are `finish()`ed into a deque keeping the newest
    `keep` entries, so retention cost is O(keep) regardless of uptime.
    """

    def __init__(self, enabled: bool = True, keep: int = 256):
        self.enabled = enabled
        self.keep = keep
        self.finished: deque[Trace] = deque(maxlen=keep)

    def start(self, name: str, **meta) -> Trace:
        if not self.enabled:
            return NULL_TRACE
        return Trace(name, **meta)

    def finish(self, trace: Trace) -> None:
        if trace is NULL_TRACE or not self.enabled:
            return
        self.finished.append(trace)

    def last(self, name: str | None = None) -> Trace | None:
        """Most recent finished trace, optionally filtered by name."""
        for tr in reversed(self.finished):
            if name is None or tr.name == name:
                return tr
        return None

    def drain(self) -> list[Trace]:
        out = list(self.finished)
        self.finished.clear()
        return out


@contextmanager
def profiled(logdir: str | None):
    """Opt-in deep-dive: wrap a region in `jax.profiler.trace(logdir)`.

    No-op when logdir is falsy. When a logdir IS given, a profiler that
    cannot start raises: a run asked to produce a trace must not quietly
    produce none.
    """
    if not logdir:
        yield
        return
    import jax
    with jax.profiler.trace(logdir):
        yield
