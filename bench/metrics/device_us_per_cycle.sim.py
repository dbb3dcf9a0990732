"""Device busy time inside the query spans per simulated cycle of the
queries traced, in us."""


def read(run):
    if run.trace is None:
        return None
    spans = run.trace["spans"]
    cycles = sum(q.counts["cycles"] for q in run.queries[:len(spans)])
    device_s = sum(s["device_s"] for s in spans)
    return device_s / cycles * 1e6 if device_s > 0 and cycles else None
