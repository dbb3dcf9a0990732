"""Trace one query of a cell and split its time by the program's own spans
and scopes: ``python3 bench/stage_trace.py --workload <cell> --seed <n>
[--untraced <k>] [--out <file>]`` from the root of a checkout, on a TPU.

The cell is set up and warmed as a benchmark run sets it up; ``k`` queries
run with no profiler (their host-clock times show what tracing costs), then
one query runs traced, as in a ``--trace 1`` run. The last line of standard
output is one JSON object: the query times, the benchmark's own reduction
of the trace (busy time, idle gaps and device ops, as the result line of a
traced run gives them), the device time of each cycle-body stage
(:mod:`bench.metrics.stage_reduction`), the time of each ``repro.*`` host
span, the cell's per-layer metrics, the per-layer metrics of the spans and
scopes, and the check of the traced query's answers.

The span metrics, per query traced, read:

* ``stage_us_per_cycle.<stage>.sim``: the scope's device self time over the
  simulated cycles that ``device_us_per_cycle.sim`` divides by, in us. The
  self time is read from the part of the run that the trace recorded and
  scaled by busy over recorded time: a paper-scale trace loses its later
  op events (``stage_reduction.DROPPED``), and every chunk of the loop runs
  the same ops on the same shapes;
* ``pack_s_per_query.sim``: ``repro.pack`` less the device time in it, s;
* ``dispatch_s_per_query.sim``: ``repro.to_device``, ``repro.dispatch`` and
  ``repro.unpack`` less the device time in them, s.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [_ROOT, os.path.join(_ROOT, "src")]
# The compile cache goes where a benchmark run puts it (see bench/run.py).
os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)

from bench import harness  # noqa: E402
from bench.metrics import stage_reduction as sr  # noqa: E402
from bench.metrics import trace_reduction as tr  # noqa: E402
from repro import (  # noqa: E402
    SPAN_DISPATCH, SPAN_PACK, SPAN_TO_DEVICE, SPAN_UNPACK,
)

PACK_SPANS = (SPAN_PACK,)
DISPATCH_SPANS = (SPAN_TO_DEVICE, SPAN_DISPATCH, SPAN_UNPACK)


def span_metrics(stages: dict | None, spans: dict, cycles: int,
                 queries: int) -> dict:
    """The per-layer metrics of the spans and scopes; a metric with nothing
    to read is left out."""
    out = {}
    if stages is not None and cycles and stages["recorded_s"] > 0:
        scale = stages["busy_s"] / stages["recorded_s"] / cycles * 1e6
        for scope in sr.DEVICE_SCOPES:
            stage = scope.split(".", 1)[1]
            out[f"stage_us_per_cycle.{stage}.sim"] = (
                stages["stages"][scope] * scale)
    for name, members in (("pack_s_per_query.sim", PACK_SPANS),
                          ("dispatch_s_per_query.sim", DISPATCH_SPANS)):
        if queries and all(m in spans for m in members):
            out[name] = sum(spans[m]["s"] - spans[m]["device_s"]
                            for m in members) / queries
    return out


def trace_cell(cell: harness.Cell, seed: int, untraced: int,
               log=lambda s: print(s, file=sys.stderr)) -> dict:
    """Set the cell up, time ``untraced`` queries, trace one, reduce it."""
    driver = cell.driver
    entry = driver.default_entry()
    state = driver.prepare(cell.spec, log)
    driver.warm_up(state, entry, log)
    plain_s = []
    for _ in range(untraced):
        _, (q,) = harness._window(cell, state, entry, seed, 0.0, None)
        plain_s.append(q.end - q.start)
        log(f"untraced query: {plain_s[-1]:.6f} s")
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
    try:
        _, queries = harness._window(cell, state, entry, seed, 0.0, trace_dir)
        (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                            recursive=True)
        t = time.perf_counter()
        trace = sr.read_xplane_ops(path)
        if trace is None:
            raise SystemExit("no XSpace protobuf schema installed to read "
                             "the trace's op names with")
        reduced = tr.reduce_trace(sr.plain(trace), harness.SPAN)
        stages = sr.stage_times(trace, harness.SPAN)
        spans = sr.host_spans(trace, harness.SPAN)
        log(f"trace: {os.path.getsize(path)} bytes, read and reduced in "
            f"{time.perf_counter() - t:.3f} s")
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    cycles = sum(q.counts.get("cycles", 0) for q in queries)
    run = harness.Run(setup_s=0.0, window_s=queries[-1].end - queries[-1].start,
                      queries=queries, trace=reduced)
    per_layer = {m["name"]: cell.readers[m["name"]](run)
                 for m in cell.per_layer} if reduced is not None else {}
    per_layer.update(span_metrics(stages, spans, cycles, len(queries)))
    return {
        "untraced_query_s": plain_s,
        "traced_query_s": [q.end - q.start for q in queries],
        "cycles": cycles,
        "reduced": reduced,
        "stages": stages,
        "host_spans": spans,
        "per_layer": per_layer,
        "check": driver.check(state, queries, seed),
    }


def main(argv: list[str]) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--untraced", type=int, default=1)
    ap.add_argument("--out", help="also write the JSON object to this file")
    args = ap.parse_args(argv)

    cell = harness.load_cell(_ROOT, args.workload)
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"no TPU: JAX finds {len(devices)} {devices[0].platform} "
              f"device(s)", file=sys.stderr)
        return 2
    from repro import compile_cache

    compile_cache.configure()
    out = trace_cell(cell, args.seed, args.untraced)
    out.update(workload=args.workload, seed=args.seed,
               device=devices[0].device_kind,
               setup_and_run_s=time.perf_counter() - T0)
    text = json.dumps(out, default=float)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            f.write(text)
    print(text)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
