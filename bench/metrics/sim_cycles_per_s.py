"""Simulated overlay cycles of every answered query, summed, over the wall
time from window start to the end of the last query begun in the window."""


def read(run):
    return sum(q.counts["cycles"] for q in run.queries) / run.window_s
