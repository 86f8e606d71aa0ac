"""spmm_roofline: the roofline floor of the window's solve-program calls
(bench/roofline.py: each call's rounds at its live columns) over the
device time of that program (`run.program`: cpaa_fixed for global solves,
_solve_topk_adaptive for service ticks) in the trace, in %."""
from bench.roofline import round_floor_s


def read(run):
    if run.trace is None or not run.peak or not run.calls or \
            not run.program:
        return None
    device_s = run.trace.module_s(run.program)
    if device_s <= 0:
        return None
    floor = sum(c["rounds"] * round_floor_s(run.n, run.m, c["columns"],
                                            run.peak)[0]
                for c in run.calls)
    return 100.0 * floor / device_s
