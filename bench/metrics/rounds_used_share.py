"""rounds_used_share: solver rounds run (serve_rounds_used_total)
over the Formula 8 bound (serve_rounds_bound_total), in the window's
ticks, in %."""


def read(run):
    bound = run.counters.get("rounds_bound", 0)
    if not bound:
        return None
    return 100.0 * run.counters.get("rounds_used", 0.0) / bound
