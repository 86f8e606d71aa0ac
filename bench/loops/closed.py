"""Closed-loop PPR through the service: a queue that never holds fewer
than `queued_min` queries; the window closes at the first tick that ends
after --seconds.

Traffic: {"loop": "closed", "queued_min", and the query parameters of
bench/service.py}. Checked: bench/service.py's top-k check.
"""
from __future__ import annotations

import time

from bench import service
from bench.drive import log, span


def drive(ctx):
    run, traffic = ctx.run, ctx.cell.traffic
    svc, rng, query, tick, results = service.service_parts(
        run, ctx.cell.config, traffic, ctx.g)
    queued_min = traffic["queued_min"]
    pool = service.seed_sets(traffic, ctx.g.n, 1 << 16, rng)  # cycled
    next_qid = 0

    def top_up():
        nonlocal next_qid
        with span("submit"):
            while svc.pending() < queued_min:
                svc.submit(query(next_qid, pool[next_qid % len(pool)]))
                next_qid += 1

    top_up()
    t0 = time.perf_counter()
    with span("warm"):
        tick(record=False)
    log(f"set-up: warm tick {time.perf_counter() - t0:.3f}s")
    top_up()
    ctx.profiler.start()
    run.setup_s = time.perf_counter() - ctx.t_process0
    c_before = service.counters(svc)
    ctx.open_window()
    answered = []
    t_start = time.perf_counter()
    while True:
        out, t_end = tick(record=True)
        answered += [r.qid for r in out]
        if t_end - t_start >= ctx.seconds:
            break
        top_up()
    ctx.close_window()
    ctx.profiler.stop()
    run.counters = service.delta(c_before, service.counters(svc))
    run.window_s = t_end - t_start
    run.answered = run.attempted = len(answered)
    queries = {q: pool[q % len(pool)] for q in answered}
    out = {q: results[q] for q in answered}
    del svc
    return queries, out


check = service.check_topk
control = service.control_topk
