"""global_solve_s: seconds per global PageRank solve (cpaa to tol,
fenced), the window's elapsed time over the whole solves it holds."""


def read(run):
    return run.window_s / run.solves if run.solves else None
