"""The driver: builds a cell's graph from its configuration, hands the
measured window to the loop its traffic file names, and checks what the
window produced against the float64 reference.

A configuration's `graph` names its generator, `bench/graphs/<name>.py`;
a traffic file's `loop` names its loop, `bench/loops/<name>.py`. The loop
gets a `Context`: the run record, the cell, the program's Graph, the
window's length, and the profiler and compile counter that bracket the
window. It sets up what it drives, opens the window with
`ctx.open_window()`, closes it with `ctx.close_window()`, and returns the
answers its `check` compares.
"""
from __future__ import annotations

import gc
import sys
import time
from dataclasses import dataclass, field

from bench import manifest
from bench.reference import Reference


# ------------------------------------------------------------ the record --
@dataclass
class Run:
    """What one run measured: the metric readers' only input."""
    seed: int
    n: int = 0
    m: int = 0
    setup_s: float = 0.0
    window_s: float = 0.0
    solves: int = 0
    latencies: list = field(default_factory=list)
    answered: int = 0
    attempted: int = 0
    failed: int = 0
    program: str = ""                                # solve program's name
    calls: list = field(default_factory=list)        # its calls in the window
    counters: dict = field(default_factory=dict)     # deltas over the window
    compiles_in_window: int = 0
    late_s: list = field(default_factory=list)       # submit - scheduled
    depth: list = field(default_factory=list)        # queue at window ticks
    trace: object = None                             # trace.TraceSummary
    peak: dict = field(default_factory=dict)
    memory_peak_bytes: int | None = None
    checks: dict = field(default_factory=dict)       # name -> (value, limit)

    @property
    def correct(self) -> bool:
        return all(v <= lim for v, lim in self.checks.values())


class _Compiles:
    """Counts programs lowered in this process (JAX's monitoring events)."""

    def __init__(self):
        self.count = 0

    def __call__(self, event: str, duration: float, **_):
        if event == "/jax/core/compile/jaxpr_to_mlir_module_duration":
            self.count += 1


_COMPILES = None


def compile_counter() -> _Compiles:
    global _COMPILES
    if _COMPILES is None:
        import jax
        _COMPILES = _Compiles()
        jax.monitoring.register_event_duration_secs_listener(_COMPILES)
    return _COMPILES


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def span(name: str):
    import jax
    return jax.profiler.TraceAnnotation(f"bench.{name}")


class Profiler:
    def __init__(self, logdir: str | None):
        self.logdir = logdir
        self.on = False

    def start(self):
        if self.logdir:
            import jax
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self.logdir, profiler_options=opts)
            self.on = True

    def stop(self):
        if self.on:
            import jax
            jax.profiler.stop_trace()
            self.on = False

    def summary(self):
        if not self.logdir:
            return None
        import jax
        from bench import trace
        t0 = time.perf_counter()
        is_op, is_module = trace.SELECTORS[jax.devices()[0].platform]
        s = trace.summarize(trace.load_events(trace.find_xplane(self.logdir)),
                            is_op=is_op, is_module=is_module)
        log(f"trace reduced in {time.perf_counter() - t0:.3f}s; programs: "
            + ", ".join(f"{k} {v * 1e-9:.6f}s" for k, v in
                        sorted(s.modules.items(), key=lambda kv: -kv[1])[:6]))
        return s


@dataclass
class Context:
    """What a loop drives the window with."""
    run: Run
    cell: manifest.Cell
    g: object                     # the program's Graph
    seconds: float
    profiler: Profiler
    compiles: _Compiles
    t_process0: float             # when set-up began
    _window: object = None
    _c0: int = 0

    def open_window(self):
        """The `bench.window` span opens; compiles are counted from here."""
        self._window = span("window")
        self._window.__enter__()
        self._c0 = self.compiles.count

    def close_window(self):
        if self._window is not None:
            self._window.__exit__(None, None, None)
            self._window = None
            self.run.compiles_in_window = self.compiles.count - self._c0


def make_graph(root, spec: dict, seed: int):
    """(n, u, v) for a configuration's `graph` entry: {"generator": name,
    ...sizes}. A `seed` in the entry fixes the graph; otherwise the run's
    seed draws it."""
    kw = {k: v for k, v in spec.items() if k != "generator"}
    kw.setdefault("seed", seed)
    return manifest.module(root, "graphs", spec["generator"]).make(**kw)


def _memory_peak(jax) -> int | None:
    peaks = []
    for d in jax.local_devices():
        stats = d.memory_stats()
        if stats and "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


# --------------------------------------------------------------- the run --
def run_cell(cell, seed: int, seconds: float, trace_dir: str | None,
             t_process0: float) -> Run:
    """Set up, drive the window, check. `trace_dir` turns the profiler on
    over the window loop; `t_process0` is when set-up began."""
    import jax
    loop = manifest.module(cell.root, "loops", cell.traffic["loop"])
    run = Run(seed=seed)
    compiles = compile_counter()
    log(f"set-up: process and JAX start "
        f"{time.perf_counter() - t_process0:.3f}s")
    t0 = time.perf_counter()
    n, u, v = make_graph(cell.root, cell.config["graph"], seed)
    run.n = n
    t1 = time.perf_counter()
    from repro.graph.structure import Graph
    g = Graph.from_undirected_edges(n, u, v)
    log(f"{cell.name}: n={n} m={g.m} loop={cell.traffic['loop']} "
        f"seed={seed}; set-up: edge list {t1 - t0:.3f}s, program's Graph "
        f"{time.perf_counter() - t1:.3f}s")
    profiler = Profiler(trace_dir)
    ctx = Context(run=run, cell=cell, g=g, seconds=seconds,
                  profiler=profiler, compiles=compiles,
                  t_process0=t_process0)
    answers = loop.drive(ctx)
    ctx.close_window()
    profiler.stop()
    del g, ctx
    run.memory_peak_bytes = _memory_peak(jax)
    gc.collect()
    run.trace = profiler.summary()
    t0 = time.perf_counter()
    ref = Reference(n, u, v)
    run.m = ref.m
    loop.check(run, cell, ref, answers)
    log(f"reference and checks {time.perf_counter() - t0:.3f}s")
    return run
