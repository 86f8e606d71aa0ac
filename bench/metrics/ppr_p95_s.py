"""ppr_p95_s: 95th percentile, over every query scheduled in the window,
of the seconds from its scheduled arrival to its answer."""
import numpy as np


def read(run):
    if not run.latencies:
        return None
    return float(np.percentile(np.asarray(run.latencies), 95.0))
