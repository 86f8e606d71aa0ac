"""The reduction by the program's own spans and scopes
(bench/program_trace.py), on hand-made events and on a trace of the PPR
service recorded on the CPU (fixtures/cpu_service.xplane.pb: a
`bench.window` span holding three `bench.submit` + `bench.tick` pairs of
an adaptive service over tri_mesh(8, 10), 8 queries a tick at tol 1e-2
(few rounds, so the file stays small); recorded by
running this file: `PYTHONPATH=src python tests/bench/test_program_trace.py`).
"""
import io
import json
import os
import struct
import subprocess
import sys
import time
from contextlib import redirect_stdout
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))
sys.path.insert(0, str(Path(__file__).resolve().parent))

import tiny  # noqa: E402
from bench import program_split, program_trace, service, trace  # noqa: E402
from bench.program_trace import OpEvent  # noqa: E402
from bench.trace import Event  # noqa: E402

FIXTURE = Path(__file__).parent / "fixtures" / "cpu_service.xplane.pb"
STAGES = ("serve.schedule", "serve.batch_form", "serve.dispatch",
          "serve.fence", "serve.materialize", "serve.telemetry")


def op(start, dur, scope="", plane="/device:TPU:0"):
    return OpEvent(plane, "XLA Ops", "fusion", float(start), float(dur),
                   scope=scope, module=scope.split("/")[0])


def host(name, start, dur):
    return Event("/host:CPU", "python", name, float(start), float(dur))


def test_scope_of_takes_the_innermost_known_scope():
    assert program_trace.scope_of(
        "jit(cpaa_fixed)/while/body/spmm.gather/gather") == "spmm.gather"
    assert program_trace.scope_of(
        "jit(f)/cheb.residual/spmm.scatter/scatter-add") == "spmm.scatter"
    assert program_trace.scope_of("jit(f)/while/body/add") == ""
    assert program_trace.scope_of("") == ""


def test_program_gaps_by_hand():
    events = [
        host("bench.window", 0, 100),
        host("bench.tick", 0, 80),
        host("serve.batch_form", 0, 20),        # idle [0, 10]: batch_form
        host("serve.dispatch", 20, 10),
        host("serve.upload", 22, 4),            # idle [22, 26]: upload
        host("serve.fence", 30, 40),            # idle [30, 35]: fence
        host("bench.submit", 80, 20),           # idle [80, 100]
        op(10, 12), op(26, 4), op(35, 35),
    ]
    s = program_trace.summarize(events)
    # [70, 80] is inside the tick but under no serve span
    assert s.program_gaps == {
        "tick": {"serve.batch_form": 10.0, "serve.upload": 4.0,
                 "serve.fence": 5.0, "unattributed": 10.0},
        "submit": {"unattributed": 20.0}}
    assert s.spans["bench.tick"] == 1 and s.spans["serve.fence"] == 1
    # every idle piece is kept: the split sums to bench/trace.py's gaps
    old = trace.summarize(events)
    for name, d in s.program_gaps.items():
        assert sum(d.values()) == pytest.approx(old.gaps[name])


def test_gap_outside_every_span_is_unattributed():
    events = [host("bench.window", 0, 10), op(0, 4)]
    assert program_trace.summarize(events).program_gaps == \
        {"no bench span": {"unattributed": 6.0}}


def test_scoped_ops_by_hand():
    events = [
        host("bench.window", 100, 100),
        op(90, 20, "jit(cpaa_fixed)/while/body/spmm.gather/gather:"),  # 10
        op(120, 30, "jit(cpaa_fixed)/while/body/spmm.scatter/scatter-add:"),
        op(150, 5, "jit(cpaa_fixed)/while/body/add:"),
        op(160, 7, "jit(_solve_topk_adaptive)/topk/sort:"),
        op(170, 3, "jit(_solve_topk_adaptive)/while/body/cheb.residual/abs:"),
        op(250, 9, "jit(cpaa_fixed)/spmm.gather/gather:"),   # after window
    ]
    s = program_trace.summarize(events)
    assert s.scoped_ops == {
        "jit(cpaa_fixed)": {"spmm.gather": 10.0, "spmm.scatter": 30.0},
        "jit(_solve_topk_adaptive)": {"topk": 7.0, "cheb.residual": 3.0}}
    assert s.scoped_s("spmm.scatter", "cpaa_fixed") == pytest.approx(30e-9)
    assert s.scoped_s("topk", "cpaa_fixed") == 0.0
    assert s.scoped_s("spmm.gather") == pytest.approx(10e-9)


def _varint(n):
    out = bytearray()
    while True:
        b, n = n & 0x7F, n >> 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _msg(*fields):
    """A protobuf message from (field, value) pairs: int -> varint,
    bytes/str -> length-delimited, float -> fixed64."""
    out = b""
    for f, v in fields:
        if isinstance(v, int):
            out += _varint(f << 3) + _varint(v)
        elif isinstance(v, float):
            out += _varint(f << 3 | 1) + struct.pack("<d", v)
        else:
            v = v.encode() if isinstance(v, str) else v
            out += _varint(f << 3 | 2) + _varint(len(v)) + v
    return out


def test_op_scopes_reads_the_event_metadata(tmp_path):
    # XPlane: name 2, lines 3, event_metadata 4, stat_metadata 5 (map
    # entries: key 1, value 2); XEventMetadata: name 2, stats 5; XStat:
    # metadata_id 1, double 2, str 5, ref 7
    stat_md = [(5, _msg((1, k), (2, _msg((1, k), (2, name)))))
               for k, name in ((7, "tf_op"), (8, "flops"),
                               (9, "jit(f)/spmm.scatter/scatter-add:"))]
    gather = _msg((1, 1), (2, "%fusion.20 = f32[8] fusion(...)"),
                  (5, _msg((1, 8), (2, 1.5))),
                  (5, _msg((1, 7), (5, "jit(f)/while/spmm.gather/gather:"))))
    scatter = _msg((1, 2), (2, "%fusion.21 = f32[4] fusion(...)"),
                   (5, _msg((1, 7), (7, 9))))
    plain = _msg((1, 3), (2, "%copy.1 = f32[4] copy(...)"))
    tpu = _msg((1, 5), (2, "/device:TPU:0"), (3, _msg((2, "XLA Ops"))),
               *[(4, _msg((1, i), (2, md)))
                 for i, md in enumerate((gather, scatter, plain), 1)],
               *stat_md)
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_msg((1, _msg((2, "/host:CPU"))), (1, tpu),
                          (4, "host")))
    assert program_trace.op_scopes(str(path)) == {
        ("/device:TPU:0", "%fusion.20 = f32[8] fusion(...)"):
            "jit(f)/while/spmm.gather/gather:",
        ("/device:TPU:0", "%fusion.21 = f32[4] fusion(...)"):
            "jit(f)/spmm.scatter/scatter-add:"}


def test_cpu_ops_carry_no_scope():
    events = [host("bench.window", 0, 10),
              Event("/host:CPU", "tf_XLAEigen/1", "fusion", 1.0, 2.0)]
    s = program_trace.summarize(events, trace.is_cpu_op)
    assert s.scoped_ops == {}


def test_one_window_span_is_required():
    with pytest.raises(ValueError):
        program_trace.summarize([op(0, 1)])
    with pytest.raises(ValueError):
        program_trace.summarize([host("bench.window", 0, 10)])


def _spans(events, name):
    return sorted((e for e in events if e.name == name),
                  key=lambda e: e.start_ns)


def _inside(inner, outer):
    return outer.start_ns <= inner.start_ns and inner.end_ns <= outer.end_ns


def test_recorded_service_trace_nests_every_stage_in_order():
    events = program_trace.load_events(str(FIXTURE), trace.is_cpu_op)
    ticks = _spans(events, "bench.tick")
    assert len(ticks) == 3
    for t in ticks:
        inside = [e for e in events if e.name.startswith("serve.")
                  and _inside(e, t)]
        order = [e.name for e in sorted(inside, key=lambda e: e.start_ns)]
        assert [n for n in order if n != "serve.upload"] == list(STAGES)
        upload, = (e for e in inside if e.name == "serve.upload")
        dispatch, = (e for e in inside if e.name == "serve.dispatch")
        assert _inside(upload, dispatch)
    # no stage span outside a tick
    n_stage = sum(1 for e in events if e.name.startswith("serve."))
    assert n_stage == 3 * (len(STAGES) + 1)


def test_recorded_service_trace_splits_the_tick_idle():
    events = program_trace.load_events(str(FIXTURE), trace.is_cpu_op)
    s = program_trace.summarize(events, trace.is_cpu_op)
    old = trace.summarize(events, *trace.SELECTORS["cpu"])
    tick = s.program_gaps["tick"]
    assert sum(tick.values()) == pytest.approx(old.gaps["tick"], rel=1e-9)
    assert sum(tick.values()) > 0
    # the host stages are where the device waits; the stage spans cover
    # nearly all of the tick
    assert {"serve.batch_form", "serve.materialize"} <= set(tick)
    assert tick.get("unattributed", 0.0) < 0.25 * sum(tick.values())
    assert "serve.schedule" not in s.program_gaps.get("submit", {})


def test_split_tool_on_a_tiny_bulk_run(tmp_path):
    root = tiny.make_root(tmp_path / "root")
    keep = tmp_path / "trace"
    buf = io.StringIO()
    counters = service.counters
    with redirect_stdout(buf):
        rc = program_split.main(
            ["--workload", "mesh-1m.bulk", "--seed", str(2**33 + 5),
             "--seconds", "1.0", "--keep", str(keep)],
            root=root, require_tpu=False, t0=time.perf_counter())
    assert rc == 0
    assert service.counters is counters          # restored
    out = json.loads(buf.getvalue().strip().splitlines()[-1])
    assert out["correct"] is True and out["ticks"] > 0
    n = tiny.TINY_GRAPHS["mesh-1m"]["rows"] * tiny.TINY_GRAPHS["mesh-1m"][
        "cols"]
    # every tick is a full 128-column bucket of float32
    assert out["numbers"]["h2d_mb"] == pytest.approx(n * 128 * 4 / 1e6)
    assert 0.0 <= out["numbers"]["tick_idle_unattributed"] <= 100.0
    assert set(out["stage_ms_per_tick"]) >= {
        "schedule", "batch_form", "upload", "solve_dispatch",
        "solve_device", "materialize", "telemetry"}
    # the split keeps every idle piece bench/trace.py names
    events = trace.load_events(trace.find_xplane(str(keep)))
    old = trace.summarize(events, *trace.SELECTORS["cpu"])
    for name, d in out["program_gaps"].items():
        assert sum(d.values()) == pytest.approx(old.gaps[name] * 1e-9,
                                                rel=1e-6)


def test_split_tool_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/program_split.py", "--workload",
         "mesh-1m.bulk", "--seed", "1", "--seconds", "1"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode == 2
    assert p.stdout == ""
    assert "TPU" in p.stderr


def record(path: Path = FIXTURE) -> None:
    """Record the fixture (run on the CPU)."""
    import glob
    import os
    import shutil
    import tempfile

    import jax

    from repro.graph import generators
    from repro.serve import GraphRegistry, PageRankService, PPRQuery
    reg = GraphRegistry()
    reg.register("g", generators.tri_mesh(8, 10))
    svc = PageRankService(reg, max_batch=8, max_top_k=8, adaptive=True)
    qid = 0

    def submit(n):
        nonlocal qid
        for _ in range(n):
            svc.submit(PPRQuery(qid=qid, graph="g", seeds=(qid % 80,),
                                tol=1e-2, top_k=8))
            qid += 1

    submit(8)
    svc.tick()                               # compiles outside the trace
    with tempfile.TemporaryDirectory() as d:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(d, profiler_options=opts)
        with jax.profiler.TraceAnnotation("bench.window"):
            for _ in range(3):
                with jax.profiler.TraceAnnotation("bench.submit"):
                    submit(8)
                with jax.profiler.TraceAnnotation("bench.tick"):
                    svc.tick()
                time.sleep(0.002)
        jax.profiler.stop_trace()
        src, = glob.glob(os.path.join(d, "**", "*.xplane.pb"),
                         recursive=True)
        shutil.copyfile(src, path)


if __name__ == "__main__":
    record()
