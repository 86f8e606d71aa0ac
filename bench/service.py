"""What the loops that drive the PPR service share: the query stream
(arrival schedules and seed sets, from the traffic file's parameters), the
service built from the configuration, its counters, and the top-k check
and control.

Query parameters: `seeds_per_query` [lo, hi] (uniform), `seed_popularity`
("uniform" or {"zipf": s}), `top_k`, `check_sample` (answers compared with
the reference per run); for open loops `rate_qps`, `arrivals` ("poisson"
or "bursty", with `burst`: {burst_factor, on_fraction, period_s}).
"""
from __future__ import annotations

import os
import time
from dataclasses import replace

import numpy as np

from bench.drive import log, span
from bench.reference import topk_error

GRAPH_NAME = "g"


# ------------------------------------------------------- arrival schedules --
# Copied from benchmarks/load_bench.py (sound; the originals are left for a
# later change to delete).
def poisson_arrivals(rate_qps: float, duration_s: float,
                     rng: np.random.Generator) -> np.ndarray:
    """Arrival times (seconds from 0) of a Poisson process at `rate_qps`,
    truncated to `duration_s`; a pure function of the generator state."""
    if rate_qps <= 0.0 or duration_s <= 0.0:
        return np.empty(0, np.float64)
    n_exp = int(rate_qps * duration_s * 1.5) + 16
    times = np.cumsum(rng.exponential(1.0 / rate_qps, n_exp))
    while times[-1] < duration_s:
        more = rng.exponential(1.0 / rate_qps, n_exp)
        times = np.concatenate([times, times[-1] + np.cumsum(more)])
    return times[times < duration_s]


def bursty_arrivals(rate_qps: float, duration_s: float,
                    rng: np.random.Generator, burst_factor: float = 5.0,
                    on_fraction: float = 0.25,
                    period_s: float = 1.0) -> np.ndarray:
    """On/off modulated Poisson whose time-average rate is `rate_qps`."""
    if rate_qps <= 0.0 or duration_s <= 0.0:
        return np.empty(0, np.float64)
    base = rate_qps / (on_fraction * burst_factor + (1.0 - on_fraction))
    out, t = [], 0.0
    while t < duration_s:
        for rate, span_s in ((burst_factor * base, on_fraction * period_s),
                             (base, (1.0 - on_fraction) * period_s)):
            seg = poisson_arrivals(rate, span_s, rng)
            if seg.size:
                out.append(t + seg)
            t += span_s
            if t >= duration_s:
                break
    times = np.concatenate(out) if out else np.empty(0, np.float64)
    return times[times < duration_s]


def arrivals(traffic: dict, duration_s: float,
             rng: np.random.Generator) -> np.ndarray:
    kind = traffic.get("arrivals", "poisson")
    if kind == "poisson":
        return poisson_arrivals(traffic["rate_qps"], duration_s, rng)
    if kind == "bursty":
        return bursty_arrivals(traffic["rate_qps"], duration_s, rng,
                               **traffic.get("burst", {}))
    raise ValueError(f"arrivals {kind!r}")


def seed_sets(traffic: dict, n: int, count: int,
              rng: np.random.Generator) -> list[tuple[int, ...]]:
    """Seed vertices of `count` queries: a uniform number in
    `seeds_per_query`, each vertex uniform or Zipf-popular."""
    lo, hi = traffic.get("seeds_per_query", [1, 3])
    k = rng.integers(lo, hi + 1, count)
    pop = traffic.get("seed_popularity", "uniform")
    if pop == "uniform":
        v = rng.integers(0, n, (count, hi))
    else:
        ranks = rng.zipf(pop["zipf"], (count, hi)) - 1
        v = rng.permutation(n)[np.minimum(ranks, n - 1)]
    return [tuple(int(x) for x in v[i, :k[i]]) for i in range(count)]


# ------------------------------------------------------------ the service --
def counters(svc) -> dict:
    m = svc.metrics
    out = {"ticks": m.ticks.total(), "padded": m.padded.total(),
           "bucket_cols": sum(int(v[2]) * c.value
                              for v, c in m.solves.children()),
           "rounds_used": m.rounds_used.total(),
           "rounds_bound": m.rounds_bound.total()}
    for v, h in m.stage.children():
        out[f"stage.{v[0]}"] = h.sum
    return out


def delta(a: dict, b: dict) -> dict:
    return {k: b.get(k, 0.0) - a.get(k, 0.0) for k in set(a) | set(b)}


def service_parts(run, cfg, traffic, g):
    """The service over graph g, and what a loop drives it with: the
    query stream's generator, a query maker, a tick that records the
    window's ticks in `run.calls`, and the answers by qid."""
    from repro.configs import pagerank_serve
    from repro.serve.pagerank_service import PPRQuery
    scfg = cfg["service"]
    t0 = time.perf_counter()
    svc = pagerank_serve.make_service(
        replace(pagerank_serve.full_config(), graphs=(), **scfg),
        graphs={GRAPH_NAME: g})
    log(f"engine {svc.registry.get(GRAPH_NAME).engine.name}; set-up: "
        f"make_service {time.perf_counter() - t0:.3f}s")
    run.program = "solve_topk_adaptive"
    rng = np.random.default_rng([run.seed, 1])
    top_k = traffic["top_k"]

    def query(qid, seeds):
        return PPRQuery(qid=qid, graph=GRAPH_NAME, seeds=seeds,
                        c=scfg["c"], tol=scfg["tol"], top_k=top_k)

    m = svc.metrics
    results = {}                                   # qid -> (idx, scores)

    def tick(record: bool):
        t_a = time.perf_counter()
        n_before = m.ticks.total()
        with span("tick"):
            out = svc.tick()
        t_b = time.perf_counter()
        for r in out:
            results[r.qid] = (r.indices, r.scores)
        if record and m.ticks.total() > n_before:
            tt = m.convergence.ticks[-1]
            run.calls.append({"start": t_a, "end": t_b, "bucket": tt.bucket,
                              "columns": tt.columns,
                              "rounds": tt.rounds_used})
        return out, t_b

    return svc, rng, query, tick, results


# -------------------------------------------------------------- the check --
def check_topk(run, cell, ref, answers) -> None:
    """Compare a sample of the window's answers, drawn from the seed, with
    the reference; check every answer for form and every counted query
    for an answer."""
    queries, out = answers
    k = cell.traffic["top_k"]
    c = cell.config["service"]["c"]
    qids = sorted(queries)
    rng = np.random.default_rng([run.seed, 2])
    sample = sorted(rng.choice(qids, min(cell.traffic["check_sample"],
                                         len(qids)),
                               replace=False).tolist()) if qids else []
    cols = ref.ppr([queries[q] for q in sample], c,
                   threads=max(1, (os.cpu_count() or 2) - 1))
    errs = [topk_error(*out[q], col, k) for q, col in zip(sample, cols)]
    # every answer of the window is checked for form: k distinct vertices
    # with finite, descending scores
    bad = sum(1 for q in qids if not _well_formed(*out[q], k, run.n))
    run.checks["topk_err"] = (max(errs) if errs else float("inf"),
                              cell.limits["topk_err"])
    run.checks["malformed"] = (float(bad), 0.0)
    run.checks["unanswered"] = (float(run.failed), 0.0)


def _well_formed(idx, scores, k: int, n: int) -> bool:
    idx = np.asarray(idx)
    sc = np.asarray(scores)
    want = min(k, n)
    return (idx.shape == (want,) and sc.shape == (want,)
            and bool(np.all(np.isfinite(sc)))
            and np.unique(idx).size == want
            and bool(np.all(np.diff(sc) <= 0)))


def _topk(col: np.ndarray, k: int):
    """A top-k answer as the service gives it: k vertices, descending."""
    idx = np.argpartition(col, col.size - k)[col.size - k:]
    idx = idx[np.argsort(-col[idx], kind="stable")]
    return idx, col[idx]


def control_topk(cell, ref, low, seed: int) -> dict:
    """The control's `topk_err`: the lower-precision reference answering
    the seed's check sample in the program's place."""
    traffic = cell.traffic
    c, k = cell.config["service"]["c"], traffic["top_k"]
    rng = np.random.default_rng([seed, 2])
    sets = seed_sets(traffic, ref.n, traffic["check_sample"], rng)
    want = ref.ppr(sets, c)
    got = low.ppr(sets, c)
    return {"topk_err": max(topk_error(*_topk(g, min(k, ref.n)), w, k)
                            for g, w in zip(got, want))}
