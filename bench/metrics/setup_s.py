"""setup_s: seconds from process start to the start of the measured
window: imports, device start, graph, registry or engine build, compiles
(or compile-cache reads) and warm traffic."""


def read(run):
    return run.setup_s
