"""One run of one cell: set-up, the measured window, the check against the
plain reference, and the result line.

Everything a cell needs is found by name from its ``BENCHMARK.json`` entry:
the configuration file it names, ``bench/traffic/<traffic>.json`` (read by
the one generator, :mod:`bench.generator`), the driver that the traffic
names, ``bench/drivers/<driver>.py`` (the call a query makes and the check
of its answers), and one reader ``bench/metrics/<metric>.py`` per metric.
A cell is added with new files and entries alone.

A driver module provides ``load(root, name, config, params)`` (the cell's
parameters, checked, read before any chip is looked for),
``default_entry()`` (the entry point under test), ``prepare(spec, log)``
(set-up: the cell's data), ``warm_up(state, entry, log)`` (compile every
shape the window runs), ``issue(state, entry, key, seed)`` (one query),
``counts(results)`` (a dict of numbers that readers sum, such as simulated
cycles), ``describe(results)`` (one log line) and ``check(state, queries,
seed)`` (``{"failed": n, "checks": {name: [reading, limit]}}``).
"""
from __future__ import annotations

import argparse
import collections
import dataclasses
import glob
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
import types
from typing import Callable

from bench import generator
from bench.metrics import trace_reduction

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SPAN = "bench.query"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


@dataclasses.dataclass
class Cell:
    root: str
    name: str
    chips: int
    traffic: generator.Traffic
    driver: types.ModuleType
    #: What the driver's ``load`` made of the configuration and traffic.
    spec: object
    end_to_end: list
    per_layer: list
    readers: dict


@dataclasses.dataclass
class Query:
    """One answered query: when it arrived, was sent and was answered (host
    clock), what it returned and the driver's counts of it."""

    index: int
    key: int
    arrival: float
    start: float
    end: float
    results: list
    counts: dict

    @property
    def latency(self) -> float:
        return self.end - self.arrival


@dataclasses.dataclass
class Run:
    """What the metric readers read. ``trace`` is the reduced profiler trace
    (``None`` unless traced); its spans are those of the first queries."""

    setup_s: float
    window_s: float
    queries: list
    trace: dict | None = None


def _applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def _load_module(path: str, name: str) -> types.ModuleType:
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod  # dataclasses look their module up by name
    spec.loader.exec_module(mod)
    return mod


def load_cell(root: str, workload: str) -> Cell:
    """The cell named ``workload`` in ``<root>/BENCHMARK.json``."""
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        spec = json.load(f)
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json; "
                         f"there are {sorted(cells)}")
    w = cells[workload]
    cfg_entry = {c["name"]: c for c in spec["configs"]}[w["config"]]
    with open(os.path.join(root, cfg_entry["file"])) as f:
        config = json.load(f)
    traffic = generator.load_traffic(
        os.path.join(root, "bench", "traffic", f"{w['traffic']}.json"))
    driver = _load_module(
        os.path.join(root, "bench", "drivers", f"{traffic.driver}.py"),
        f"bench_driver_{traffic.driver}")
    e2e = [m for m in spec["end_to_end"] if _applies(m, workload)]
    per_layer = [m for m in spec["per_layer"] if _applies(m, workload)]
    return Cell(
        root=root, name=workload, chips=w["chips"], traffic=traffic,
        driver=driver,
        spec=driver.load(root, workload, config, traffic.params),
        end_to_end=e2e, per_layer=per_layer,
        readers={m["name"]: _load_module(
            os.path.join(root, "bench", "metrics", f"{m['name']}.py"),
            f"bench_metric_{m['name']}").read for m in e2e + per_layer})


class _CompileCounter:
    """Counts the programs compiled, or loaded from the cache, inside a
    ``with`` block."""

    def __enter__(self):
        import jax

        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        return self

    def __exit__(self, *exc):
        import jax

        jax.monitoring.unregister_event_duration_listener(self._on_event)

    def _on_event(self, event: str, _secs: float, **_kw) -> None:
        if event == COMPILE_EVENT:
            self.count += 1


def _device_info(devices, chips: int) -> dict:
    peaks = []
    for d in devices[:chips]:
        stats = d.memory_stats() or {}
        peaks.append(stats.get("peak_bytes_in_use"))
    known = [p for p in peaks if p is not None]
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": chips, "memory_peak_bytes": max(known) if known else None}


def _window(cell: Cell, state, entry, seed: int, seconds: float,
            trace_dir: str | None) -> tuple[float, list]:
    """Send the traffic's queries for ``seconds``: the window's start and
    every query begun in it, the one in flight finished. A traced run
    traces its first query and ends its window there: the profiler records
    every device operation, some hundreds of MB per paper-scale query."""
    import jax

    queries: list[Query] = []
    w0 = time.perf_counter()
    for spec in generator.schedule(cell.traffic, seed):
        now = time.perf_counter()
        if spec.arrival_s is None:
            if queries and now - w0 >= seconds:
                break
            arrival = now
        else:
            if queries and spec.arrival_s >= seconds:
                break
            arrival = w0 + spec.arrival_s
            if arrival > now:
                time.sleep(arrival - now)
        if trace_dir:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        start = time.perf_counter()
        with jax.profiler.TraceAnnotation(SPAN):
            results = cell.driver.issue(state, entry, spec.key, seed)
        end = time.perf_counter()
        queries.append(Query(spec.index, spec.key, arrival, start, end, results,
                             cell.driver.counts(results)))
        if trace_dir:
            jax.profiler.stop_trace()
            break
    return w0, queries


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, t0: float,
             devices, entry: Callable | None = None,
             log: Callable = lambda s: print(s, file=sys.stderr)) -> dict:
    """Set up, measure for ``seconds``, check; the result line as a dict.

    ``entry`` is the entry point under test (the driver's default unless a
    test puts a broken one in its place).
    """
    driver = cell.driver
    entry = entry or driver.default_entry()
    state = driver.prepare(cell.spec, log)
    driver.warm_up(state, entry, log)
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    with _CompileCounter() as counter:
        w0, queries = _window(cell, state, entry, seed, seconds, trace_dir)
    setup_s = w0 - t0
    window_s = queries[-1].end - w0

    reduced = None
    if trace:
        files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                          recursive=True)
        t = time.perf_counter()
        reduced = trace_reduction.reduce_trace(
            trace_reduction.read_xplane(files[0]), SPAN)
        log(f"trace: {os.path.getsize(files[0])} bytes, reduced in "
            f"{time.perf_counter() - t:.3f} s")
        shutil.rmtree(trace_dir, ignore_errors=True)
    device = _device_info(devices, cell.chips)
    if reduced is not None:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]

    total: collections.Counter = collections.Counter()
    for q in queries:
        total.update(q.counts)
    log(f"window: {len(queries)} queries, "
        + ", ".join(f"{k} {v}" for k, v in total.items())
        + f", {window_s:.6f} s, in-flight overshoot {window_s - seconds:.6f} s")
    log(f"compilations inside the window: {counter.count}")
    for q in queries:
        log(f"query {q.index}: key {q.key}, {q.end - q.start:.6f} s, "
            f"latency {q.latency:.6f} s, {driver.describe(q.results)}")

    measured = Run(setup_s=setup_s, window_s=window_s, queries=queries,
                   trace=reduced)
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = cell.readers[m["name"]](measured)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    # The reference runs once the window has closed and the peak is read.
    outcome = driver.check(state, queries, seed)
    line = {"correct": outcome["failed"] == 0, "attempted": len(queries),
            "failed": outcome["failed"], "metrics": metrics, "device": device}
    if reduced is not None:
        line["breakdown"] = {"device_ops": reduced["device_ops"],
                             "idle_gaps": reduced["idle_gaps"]}
    line["checks"] = {k: {"value": v, "limit": lim}
                      for k, (v, lim) in outcome["checks"].items()}
    return line


def main(argv: list[str], t0: float) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = load_cell(ROOT, args.workload)
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"no TPU with {cell.chips} chip(s): JAX finds "
              f"{len(devices)} {devices[0].platform} device(s)", file=sys.stderr)
        return 2
    from repro import compile_cache

    compile_cache.configure()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    line = run_cell(cell, args.seed, args.seconds, bool(args.trace), t0, devices)
    for name, c in line["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})", file=sys.stderr)
    print(json.dumps(line))
    return 0
