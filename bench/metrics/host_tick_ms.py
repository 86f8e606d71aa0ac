"""Host milliseconds per tick: the service's own stage timings
(serve_stage_seconds: batch_form + solve_dispatch + materialize) summed
over the window's ticks, over the ticks (serve_ticks_total)."""

STAGES = ("stage.batch_form", "stage.solve_dispatch", "stage.materialize")


def read(run):
    ticks = run.counters.get("ticks", 0)
    if not ticks:
        return None
    return 1000.0 * sum(run.counters.get(s, 0.0) for s in STAGES) / ticks
