"""Open-loop PPR through the service on a seeded schedule.

Traffic: {"loop": "open", "rate_qps", "prefill_s", "first_tick_queue",
"tail_s", "warm_buckets", and the query parameters of bench/service.py}.
A warm tick at each of `warm_buckets` comes first; then the schedule
starts `prefill_s` before the window, and the service ticks from the
moment `first_tick_queue` queries wait. Only queries scheduled inside the
window count, and each is timed from its scheduled arrival to its answer;
the schedule runs on `tail_s` past the window, so the last of them meet a
steady load. Checked: bench/service.py's top-k check.
"""
from __future__ import annotations

import time

import numpy as np

from bench import service
from bench.drive import log, span


def drive(ctx):
    run, traffic = ctx.run, ctx.cell.traffic
    svc, rng, query, tick, results = service.service_parts(
        run, ctx.cell.config, traffic, ctx.g)
    n, seconds = ctx.g.n, ctx.seconds
    prefill = float(traffic["prefill_s"])
    w0, w1 = prefill, prefill + seconds
    times = service.arrivals(traffic, w1 + traffic.get("tail_s", 60.0), rng)
    seeds = service.seed_sets(traffic, n, times.size, rng)
    counted = np.flatnonzero((times >= w0) & (times < w1))
    # compile the buckets the traffic reaches before the schedule starts
    warm_rng = np.random.default_rng([run.seed, 3])
    t0 = time.perf_counter()
    with span("warm"):
        for width in traffic.get("warm_buckets", [svc.max_batch]):
            for j, s in enumerate(service.seed_sets(traffic, n, width,
                                                    warm_rng)):
                svc.submit(query(-1 - j, s))
            while svc.pending():
                tick(record=False)
    log(f"set-up: warm ticks {time.perf_counter() - t0:.3f}s")
    first_tick_queue = traffic.get("first_tick_queue", 1)
    done: dict[int, float] = {}
    left = {int(q) for q in counted}
    in_window = False
    c_before = c_after = None
    i = 0
    started = False
    ctx.profiler.start()
    start = time.perf_counter()
    # set-up ends where the window begins on the schedule
    run.setup_s = start + w0 - ctx.t_process0
    while True:
        now = time.perf_counter() - start
        j = int(np.searchsorted(times, now, side="right"))
        if j > i:
            with span("submit"):
                for q in range(i, j):
                    svc.submit(query(q, seeds[q]))
                    if w0 <= times[q] < w1:
                        run.late_s.append(time.perf_counter() - start
                                          - times[q])
            i = j
        if not started and svc.pending() >= first_tick_queue:
            started = True
        if started and svc.pending():
            t_a = time.perf_counter() - start
            if not in_window and c_before is None and t_a >= w0:
                c_before = service.counters(svc)
                ctx.open_window()
                in_window = True
            if in_window and t_a >= w1:
                ctx.close_window()
                in_window = False
                c_after = service.counters(svc)
            if in_window:
                run.depth.append(svc.pending())
            out, t_b = tick(record=in_window)
            for r in out:
                done[r.qid] = t_b - start
                left.discard(r.qid)
            if not left and times[i - 1] >= w1:
                break
        elif i < times.size:
            time.sleep(min(1e-3, max(0.0, times[i] - now)))
        else:
            break
    if in_window:
        ctx.close_window()
        c_after = service.counters(svc)
    ctx.profiler.stop()
    run.counters = service.delta(c_before, c_after) if c_before else {}
    run.attempted = int(counted.size)
    run.latencies = [done[int(q)] - times[q] for q in counted
                     if int(q) in done]
    run.failed = run.attempted - len(run.latencies)
    run.answered = len(run.latencies)
    run.window_s = seconds
    if run.late_s:
        late = np.asarray(run.late_s)
        log(f"generator lateness: mean {late.mean():.6f}s, max "
            f"{late.max():.6f}s over {late.size} queries")
    queries = {int(q): seeds[q] for q in counted if int(q) in done}
    out = {q: results[q] for q in queries}
    del svc
    return queries, out


check = service.check_topk
control = service.control_topk
