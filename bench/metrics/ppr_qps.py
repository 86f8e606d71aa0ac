"""ppr_qps: PPR queries answered per second, from the start of the window
to the end of the tick that closes it."""


def read(run):
    return run.answered / run.window_s if run.answered else None
