"""Reduce a JAX profiler trace to what the per-layer metrics read.

The benchmark wraps its measured window in the host span `bench.window`
and its own work in other `bench.*` spans (`jax.profiler.TraceAnnotation`,
so they share the profiler's clock with the device). From the trace:

  busy     the union of the intervals in which an operation ran on a
           device, clipped to the window; averaged over the devices;
  modules  device time of the compiled programs whose name holds a given
           string (the solve program of a cell);
  ops      device time per operation (the breakdown's top ten), by HLO
           name, shape and opcode; a `while` holds the ops of its body;
  gaps     every idle interval of the first device inside the window,
           cut at the host spans' edges, each piece named by the shortest
           `bench.*` span that covers it: what the host was doing.
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field

WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."


@dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def short_op(name: str) -> str:
    """"%fusion.21 = f32[1040000]{0:T(1024)} fusion(...), kind=..." ->
    "fusion.21 f32[1040000] fusion": the HLO name, result shape ("tuple"
    for a tuple) and opcode of a TPU op event."""
    head, sep, rest = name.partition(" = ")
    if not sep:
        return name[:120]
    rest = re.sub(r"\{[^{}]*\}", "", rest)
    m = re.match(r"(\([^()]*\)|\S+) ([\w\-]+)\(", rest)
    if m is None:
        return head.lstrip("%")
    shape = "tuple" if m.group(1).startswith("(") else m.group(1)
    return f"{head.lstrip('%')} {shape} {m.group(2)}"


def find_xplane(logdir: str) -> str:
    paths = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return paths[-1]


def load_events(path: str) -> list[Event]:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    return [Event(plane.name, line.name, ev.name, float(ev.start_ns),
                  float(ev.duration_ns))
            for plane in pd.planes for line in plane.lines
            for ev in line.events]


def is_tpu_op(e: Event) -> bool:
    return e.plane.startswith("/device:TPU:") and e.line == "XLA Ops"


def is_tpu_module(e: Event) -> bool:
    return e.plane.startswith("/device:TPU:") and e.line == "XLA Modules"


def is_cpu_op(e: Event) -> bool:
    """XLA:CPU runs its operations on host threads (tests only)."""
    return (e.plane == "/host:CPU" and e.line.startswith("tf_XLA")
            and e.dur_ns > 0 and not e.name.startswith("ThreadpoolListener"))


def is_none(e: Event) -> bool:
    return False


# (operation, program) selectors by JAX platform
SELECTORS = {"tpu": (is_tpu_op, is_tpu_module), "cpu": (is_cpu_op, is_none)}


def union(intervals) -> list[tuple[float, float]]:
    """Merge (start, end) intervals into disjoint sorted ones."""
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def clip(intervals, lo: float, hi: float):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def gaps(busy, lo: float, hi: float) -> list[tuple[float, float]]:
    """Idle intervals of [lo, hi] between the merged busy intervals."""
    out, t = [], lo
    for a, b in busy:
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if hi > t:
        out.append((t, hi))
    return out


def split_gap(gap, spans: list[Event]) -> list[tuple[str, float]]:
    """Cut an idle interval at the host spans' edges and name each piece by
    the shortest `bench.*` span that covers it: what the host was doing."""
    a, b = gap
    over = [s for s in spans if s.start_ns < b and s.end_ns > a]
    cuts = sorted({a, b} | {t for s in over for t in (s.start_ns, s.end_ns)
                            if a < t < b})
    out = []
    for x, y in zip(cuts, cuts[1:]):
        cover = [s for s in over if s.start_ns <= x and s.end_ns >= y]
        name = (min(cover, key=lambda s: s.dur_ns).name[len(SPAN_PREFIX):]
                if cover else "no bench span")
        out.append((name, y - x))
    return out


@dataclass
class TraceSummary:
    window_ns: tuple[float, float]
    busy_ns: float                      # mean over devices
    n_devices: int
    modules: dict[str, float] = field(default_factory=dict)   # name -> ns
    ops: dict[str, float] = field(default_factory=dict)       # name -> ns
    gaps: dict[str, float] = field(default_factory=dict)      # host -> ns
    spans: dict[str, float] = field(default_factory=dict)     # name -> ns

    @property
    def window_s(self) -> float:
        return (self.window_ns[1] - self.window_ns[0]) * 1e-9

    @property
    def busy_s(self) -> float:
        return self.busy_ns * 1e-9

    def module_s(self, fragment: str) -> float:
        return sum(t for name, t in self.modules.items()
                   if fragment in name) * 1e-9

    def breakdown(self, top: int = 10) -> dict:
        def head(d):
            return [[k, v * 1e-9] for k, v in
                    sorted(d.items(), key=lambda kv: -kv[1])[:top]]
        return {"device_ops": head(self.ops), "idle_gaps": head(self.gaps)}


def summarize(events: list[Event], is_op=is_tpu_op,
              is_module=is_tpu_module) -> TraceSummary:
    """Reduce one trace; `is_op` / `is_module` pick the device events (the
    defaults read a TPU's planes)."""
    spans = [e for e in events if e.name.startswith(SPAN_PREFIX)]
    windows = [e for e in spans if e.name == WINDOW_SPAN]
    if len(windows) != 1:
        raise ValueError(f"expected one {WINDOW_SPAN} span, found "
                         f"{len(windows)}")
    lo, hi = windows[0].start_ns, windows[0].end_ns
    ops = [e for e in events if is_op(e)]
    planes = sorted({e.plane for e in ops})
    if not planes:
        raise ValueError("no device operation in the trace")
    busy_by_plane = {p: union(clip([(e.start_ns, e.end_ns) for e in ops
                                    if e.plane == p], lo, hi))
                     for p in planes}
    busy = sum(sum(b - a for a, b in iv) for iv in busy_by_plane.values())
    s = TraceSummary(window_ns=(lo, hi), busy_ns=busy / len(planes),
                     n_devices=len(planes))
    for e in ops:
        d = min(e.end_ns, hi) - max(e.start_ns, lo)
        if d > 0:
            name = short_op(e.name)
            s.ops[name] = s.ops.get(name, 0.0) + d
    for e in events:
        if is_module(e):
            d = min(e.end_ns, hi) - max(e.start_ns, lo)
            if d > 0:
                s.modules[e.name] = s.modules.get(e.name, 0.0) + d
    inner = [e for e in spans if e.name != WINDOW_SPAN]
    for e in inner:
        s.spans[e.name] = s.spans.get(e.name, 0.0) + e.dur_ns
    for g in gaps(busy_by_plane[planes[0]], lo, hi):
        for name, d in split_gap(g, inner):
            s.gaps[name] = s.gaps.get(name, 0.0) + d
    return s
