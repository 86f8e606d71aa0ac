"""Reduce a profiler trace by the program's own spans and scopes.

bench/trace.py names the device's idle time by the benchmark's `bench.*`
spans alone. The program puts its own names on the same clock: the
service times every stage of a tick as a `serve.<stage>` profiler span
(`repro.obs.trace.stage`), and the solvers wrap their kernels in
`jax.named_scope`s (`spmm.gather`, `spmm.scatter`, `cheb.residual`,
`topk`). From a trace that holds both:

  program_gaps  every idle interval of the first device inside the
                window, cut at the edges of the `bench.*` and `serve.*`
                spans; each piece is filed under the shortest `bench.*`
                span covering it (as bench/trace.py names it) and the
                shortest `serve.*` span covering it, else `unattributed`;
  scoped_ops    device op time inside the window by the innermost of
                SCOPES on the op's scope path, per compiled program.

A TPU op event's scope path is the `tf_op` stat of its event metadata,
which jax's ProfileData does not expose: `op_scopes` reads it from the
file's protobuf (tsl/profiler/protobuf/xplane.proto). An XLA:CPU op has
none, so on the CPU `scoped_ops` stays empty.
"""
from __future__ import annotations

from dataclasses import dataclass, field

from bench import trace
from bench.trace import Event

PROGRAM_PREFIX = "serve."
UNATTRIBUTED = "unattributed"
SCOPES = ("spmm.gather", "spmm.scatter", "cheb.residual", "topk")
SCOPE_STAT = "tf_op"


@dataclass(frozen=True)
class OpEvent(Event):
    """A device op event with its scope path and compiled program."""
    scope: str = ""
    module: str = ""


def load_events(path: str, is_op=trace.is_tpu_op) -> list[Event]:
    """bench/trace.py's events, with the device op events (`is_op`) read
    as `OpEvent`s carrying their scope path and program ("jit(f)")."""
    from jax.profiler import ProfileData
    scopes = op_scopes(path)
    out: list[Event] = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                e = Event(plane.name, line.name, ev.name, float(ev.start_ns),
                          float(ev.duration_ns))
                if is_op(e):
                    scope = scopes.get((plane.name, ev.name), "")
                    e = OpEvent(e.plane, e.line, e.name, e.start_ns,
                                e.dur_ns, scope=scope,
                                module=scope.split("/")[0])
                out.append(e)
    return out


# -- the protobuf wire format, as far as op_scopes reads it --------------
def _varint(buf, i: int) -> tuple[int, int]:
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """(field number, value) of one message: an int for a varint, bytes
    (a memoryview) otherwise."""
    i = 0
    while i < len(buf):
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            v, i = buf[i:i + n], i + n
        elif wire in (1, 5):
            n = 8 if wire == 1 else 4
            v, i = buf[i:i + n], i + n
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield key >> 3, v


def _text(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def op_scopes(path: str) -> dict[tuple[str, str], str]:
    """(plane name, event name) -> the SCOPE_STAT of the event's metadata,
    for every event metadata of the trace that has one. XSpace: planes 1;
    XPlane: name 2, event_metadata 4 and stat_metadata 5 (map entries: key
    1, value 2); XEventMetadata: name 2, stats 5; XStatMetadata: id 1,
    name 2; XStat: metadata_id 1, str_value 5, ref_value 7."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out = {}
    for field, plane in _fields(space):
        if field != 1:
            continue
        name, metas, stat_names = "", [], {}
        for pf, pv in _fields(plane):
            if pf == 2:
                name = _text(pv)
            elif pf in (4, 5):
                value = dict(_fields(pv)).get(2)
                if value is None:
                    continue
                if pf == 4:
                    metas.append(value)
                else:
                    sm = dict(_fields(value))
                    stat_names[sm.get(1, 0)] = _text(sm.get(2, b""))
        for md in metas:
            ename, scope = "", ""
            for mf, mv in _fields(md):
                if mf == 2:
                    ename = _text(mv)
                elif mf == 5:
                    st = dict(_fields(mv))
                    if stat_names.get(st.get(1)) == SCOPE_STAT:
                        scope = _text(st[5]) if 5 in st else \
                            stat_names.get(st.get(7), "")
            if scope:
                out[(name, ename)] = scope
    return out


def scope_of(path: str) -> str:
    """The innermost of SCOPES on a scope path ("jit(f)/while/body/
    spmm.gather/gather" -> "spmm.gather"), or ""."""
    for part in reversed(path.split("/")):
        if part in SCOPES:
            return part
    return ""


def _innermost(spans: list[Event], x: float, y: float) -> Event | None:
    cover = [s for s in spans if s.start_ns <= x and s.end_ns >= y]
    return min(cover, key=lambda s: s.dur_ns) if cover else None


@dataclass
class ProgramSummary:
    # bench span -> (serve span or UNATTRIBUTED) -> idle ns
    program_gaps: dict[str, dict[str, float]] = field(default_factory=dict)
    # compiled program -> scope -> device ns
    scoped_ops: dict[str, dict[str, float]] = field(default_factory=dict)
    spans: dict[str, int] = field(default_factory=dict)   # name -> count

    def scoped_s(self, scope: str, program: str = "") -> float:
        """Device seconds under `scope` in the programs whose name holds
        `program`."""
        return sum(d.get(scope, 0.0) for name, d in self.scoped_ops.items()
                   if program in name) * 1e-9

    def table(self) -> str:
        """One line: the idle under each bench span, split by program
        span, in seconds."""
        parts = []
        for b, d in sorted(self.program_gaps.items(), key=lambda kv: -sum(
                kv[1].values())):
            split = ", ".join(f"{k} {v * 1e-9:.6f}" for k, v in
                              sorted(d.items(), key=lambda kv: -kv[1]))
            parts.append(f"{b} {sum(d.values()) * 1e-9:.6f}s ({split})")
        return "; ".join(parts)


def summarize(events: list[Event], is_op=trace.is_tpu_op) -> ProgramSummary:
    """Split device 0's idle time inside `bench.window` by the benchmark's
    and the program's spans, and sum device op time by scope."""
    windows = [e for e in events if e.name == trace.WINDOW_SPAN]
    if len(windows) != 1:
        raise ValueError(f"expected one {trace.WINDOW_SPAN} span, found "
                         f"{len(windows)}")
    lo, hi = windows[0].start_ns, windows[0].end_ns
    ops = [e for e in events if is_op(e)]
    if not ops:
        raise ValueError("no device operation in the trace")
    first = min(e.plane for e in ops)
    busy = trace.union(trace.clip([(e.start_ns, e.end_ns) for e in ops
                                   if e.plane == first], lo, hi))
    inside = [e for e in events if e.start_ns < hi and e.end_ns > lo]
    bench = [e for e in inside if e.name.startswith(trace.SPAN_PREFIX)
             and e.name != trace.WINDOW_SPAN]
    prog = [e for e in inside if e.name.startswith(PROGRAM_PREFIX)]
    s = ProgramSummary()
    for e in bench + prog:
        s.spans[e.name] = s.spans.get(e.name, 0) + 1
    for a, b in trace.gaps(busy, lo, hi):
        over_b = [e for e in bench if e.start_ns < b and e.end_ns > a]
        over_p = [e for e in prog if e.start_ns < b and e.end_ns > a]
        cuts = sorted({a, b} | {t for e in over_b + over_p
                                for t in (e.start_ns, e.end_ns) if a < t < b})
        for x, y in zip(cuts, cuts[1:]):
            bs = _innermost(over_b, x, y)
            ps = _innermost(over_p, x, y)
            bname = bs.name[len(trace.SPAN_PREFIX):] if bs else \
                "no bench span"
            pname = ps.name if ps else UNATTRIBUTED
            d = s.program_gaps.setdefault(bname, {})
            d[pname] = d.get(pname, 0.0) + (y - x)
    for e in ops:
        name = scope_of(getattr(e, "scope", ""))
        d = min(e.end_ns, hi) - max(e.start_ns, lo)
        if name and d > 0:
            prog_d = s.scoped_ops.setdefault(getattr(e, "module", ""), {})
            prog_d[name] = prog_d.get(name, 0.0) + d
    return s
