"""Mean over queries of the query span less the device busy time inside it:
host preparation (packing, dispatch, unpacking the result)."""


def read(run):
    if run.trace is None:
        return None
    spans = run.trace["spans"]
    return sum(s["s"] - s["device_s"] for s in spans) / len(spans)
