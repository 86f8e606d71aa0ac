"""Global PageRank solves back to back: `select_engine` at batch 1 and
`cpaa` to the configuration's tol, each solve fenced. The window closes at
the first solve that ends after --seconds.

Traffic: {"loop": "global"}. Checked: the L1 distance of every timed
solve's pi from the reference, and the rounds each solve reports against
the paper's Formula 8 count for the configuration's tol.
"""
from __future__ import annotations

import time

import numpy as np

from bench.drive import log, span
from bench.reference import formula8_rounds, l1_error


def drive(ctx):
    import jax
    from repro.core.engine import select_engine
    from repro.core.pagerank import cpaa
    run = ctx.run
    gcfg = ctx.cell.config["global"]
    c, tol = gcfg["c"], gcfg["tol"]
    t0 = time.perf_counter()
    eng = select_engine(ctx.g, batch=1, mode=gcfg.get("engine", "auto"))
    t1 = time.perf_counter()
    with span("warm"):
        warm = cpaa(eng, c=c, tol=tol)
        jax.block_until_ready(warm.pi)
    log(f"engine {eng.name}, {warm.iterations} rounds per solve; set-up: "
        f"select_engine {t1 - t0:.3f}s, warm solve "
        f"{time.perf_counter() - t1:.3f}s")
    del warm
    run.program = "cpaa_fixed"
    ctx.profiler.start()
    run.setup_s = time.perf_counter() - ctx.t_process0
    ctx.open_window()
    pis = []
    t_start = time.perf_counter()
    while True:
        with span("solve"):
            res = cpaa(eng, c=c, tol=tol)
            jax.block_until_ready(res.pi)
        pis.append(res.pi)
        run.calls.append({"rounds": int(res.iterations), "columns": 1})
        t_end = time.perf_counter()
        if t_end - t_start >= ctx.seconds:
            break
    ctx.close_window()
    ctx.profiler.stop()
    run.window_s = t_end - t_start
    run.solves = run.attempted = len(pis)
    return [np.asarray(p, np.float64) for p in pis]


def check(run, cell, ref, answers) -> None:
    gcfg = cell.config["global"]
    pi_ref = ref.global_pagerank(gcfg["c"])
    err = max((l1_error(p, pi_ref) for p in answers), default=float("inf"))
    run.checks["pi_l1_err"] = (err, cell.limits["pi_l1_err"])
    want = formula8_rounds(gcfg["c"], gcfg["tol"])
    short = max((want - call["rounds"] for call in run.calls), default=want)
    run.checks["rounds_short"] = (float(max(short, 0)), 0.0)


def control(cell, ref, low, seed: int) -> dict:
    c = cell.config["global"]["c"]
    return {"pi_l1_err": l1_error(low.global_pagerank(c),
                                  ref.global_pagerank(c))}
