"""padded_col_share: pad columns solved (serve_padded_columns_total)
over all columns solved (bucket width times serve_solves_total per
bucket), in the window's ticks, in %."""


def read(run):
    cols = run.counters.get("bucket_cols", 0)
    if not cols:
        return None
    return 100.0 * run.counters.get("padded", 0.0) / cols
