"""Benchmark driver: one section per paper table/figure + kernel benches.

Prints CSV sections; `python -m benchmarks.run [--quick] [--json PATH]`.

--json PATH additionally writes every section as machine-readable JSON —
including the structured engine-comparison records (COO vs block-ELL vs
fused round, per graph family and batch size) — so CI can archive the perf
trajectory run over run.
"""
from __future__ import annotations

import argparse
import json
import platform
import sys


def _emit(sections, title: str, rows):
    print(f"\n## {title}")
    for row in rows:
        print(",".join(str(x) for x in row))
    sections[title] = [list(row) for row in rows]


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--json", metavar="PATH", default=None,
                    help="also write results to PATH as JSON "
                         "(e.g. BENCH_pagerank.json)")
    args = ap.parse_args(argv)
    quick = args.quick

    import jax
    from benchmarks import (adaptive_bench, engine_bench, kernels_bench,
                            load_bench, paper_tables, scale_bench,
                            serve_pagerank_bench, sharded_bench,
                            update_churn_bench)

    sections: dict[str, list] = {}
    _emit(sections, "theory_check (paper §4.2 claims)",
          paper_tables.theory_check())
    _emit(sections, "figure1_convergence_rate",
          paper_tables.fig1_convergence_rate())
    _emit(sections, "figure2_relative_error",
          paper_tables.fig2_relative_error())

    # the engine comparison runs in BOTH modes: it is the perf-trajectory
    # section CI tracks from every push
    eng_rows, eng_records = engine_bench.engine_compare(quick=quick)
    _emit(sections, "engine_compare_cpaa_end_to_end", eng_rows)

    # adaptive (residual-controlled) vs fixed-round CPAA: rounds saved +
    # wall-clock, also tracked by the regression gate from every push
    ad_rows, ad_records = adaptive_bench.adaptive_compare(quick=quick)
    _emit(sections, "adaptive_compare_rounds_and_time", ad_rows)

    # sharded engines across simulated device counts (subprocesses: the
    # device count is locked at jax init, so each count re-inits jax).
    # CPU only: on an accelerator this process holds the device the
    # children would need
    sh_records = []
    if jax.default_backend() == "cpu":
        sh_rows, sh_records = sharded_bench.sharded_compare(quick=quick)
        _emit(sections, "sharded_compare_1d_2d_vs_single", sh_rows)
    else:
        print(f"sharded_compare refused on the {jax.default_backend()!r} "
              "backend: its fake-device subprocesses need the device this "
              "process holds (use chip_smoke.py --four-chips on real "
              "chips)", file=sys.stderr)

    # edge-update churn: incremental patch vs full rebuild per batch, cache
    # retention under selective invalidation — gated like solve regressions
    uc_rows, uc_records = update_churn_bench.update_churn(quick=quick)
    _emit(sections, "update_churn_incremental_vs_rebuild", uc_rows)

    # paper-scale engines: hub-tail vs COO vs (probed) block-ELL at
    # n = 10^5 / 10^6 on the scale-free family, f32 and packed bf16 weights
    # — runs in BOTH modes (the n=10^6 hub-tail speedup is the headline the
    # regression gate tracks); graphs come through the dataset cache
    sc_rows, sc_records = scale_bench.scale_compare(quick=quick)
    _emit(sections, "scale_compare_paper_scale_engines", sc_rows)

    # serving: qps + histogram-derived p50/p99/p999 per-query latency and
    # the metrics-on/off overhead check — runs in BOTH modes so the p99
    # regression gate sees every push
    sv_rows, sv_records, _ = serve_pagerank_bench.qps_vs_batch(
        batch_sizes=(1, 8, 32) if quick else (1, 8, 32, 128),
        n_queries=64 if quick else 256,
        rows=60 if quick else 100, cols=60 if quick else 100)
    _emit(sections, "ppr_serving_qps_vs_batch", sv_rows)

    # open-loop load: FIFO vs deadline scheduling under seeded
    # Poisson/bursty multi-tenant traffic — per-tenant p50/p99/p999 and
    # goodput-under-SLO, gated like the solve benches (the interactive-p99
    # gap is the scheduler tier's headline)
    lb_rows, lb_records = load_bench.load_compare(quick=quick)
    _emit(sections, "open_loop_load_fifo_vs_deadline", lb_rows)

    if not quick:
        _emit(sections, "figure3_err_vs_rounds (NACA0015 stand-in)",
              paper_tables.fig3_err_vs_rounds_and_time())
        _emit(sections, "table2_iterations_and_time (six datasets)",
              paper_tables.table2_iterations_and_time())
        _emit(sections, "figure4_time_vs_error (delaunay stand-in)",
              paper_tables.fig4_time_vs_error())
        _emit(sections, "beyond_paper_basis_ablation (paper §6 future work)",
              paper_tables.basis_ablation())
        _emit(sections, "kernel_spmm_formats", kernels_bench.spmm_formats())
        _emit(sections, "kernel_cheb_fused_update",
              kernels_bench.cheb_fused_update())

    if args.json:
        payload = {
            "meta": {
                "quick": quick,
                "backend": jax.default_backend(),
                "device_count": jax.device_count(),
                "python": platform.python_version(),
                "jax": jax.__version__,
            },
            "engine_compare": eng_records,
            "adaptive_compare": ad_records,
            "sharded_compare": sh_records,
            "update_churn": uc_records,
            "scale_compare": sc_records,
            "serve_pagerank": sv_records,
            "load_bench": lb_records,
            "sections": sections,
        }
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=2)
        print(f"\nwrote {args.json}", file=sys.stderr)


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
