"""Seconds from process start to window start: imports, graph build,
packing, compilation or loading from the cache, warm-up."""


def read(run):
    return run.setup_s
