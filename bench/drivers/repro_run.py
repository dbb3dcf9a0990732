"""Queries that call ``repro.run``: one graph at a time, or a vmapped sweep of
overlay configurations on one graph.

Traffic keys besides the generator's:

``calls``
    The calls that queries make, the query with key ``k`` making call
    ``k % len(calls)``. A call is ``{"batch": false, "configs": [c]}`` (one
    ``repro.run(graph, OverlayConfig(**c))``) or ``{"batch": true,
    "configs": [c1, c2, ...]}`` (one vmapped ``repro.run(graph, batch=...)``).
``value_scale``
    ``[lo, hi]``: a query runs the configuration's graph with every initial
    value scaled by its own uniform(lo, hi) factor drawn from ``(seed,
    key)``, so every new query is a new graph to pack while its structure,
    and with it every compiled shape and every simulated statistic, stays
    the same.

``bench/expected/<cell>.json`` holds, per call and member, the simulated
statistics that no speed change may move, and the limit of the value check.
"""
from __future__ import annotations

import dataclasses
import json
import os
import time

import numpy as np

from bench import generator, reference

PARAMS = {"calls", "value_scale"}
#: The simulated statistics a speed change must leave exactly as they are.
STATS = ("cycles", "delivered", "deflections", "busy_cycles",
         "noc_deflections", "eject_deflections")


@dataclasses.dataclass
class Spec:
    root: str
    name: str
    config: dict
    calls: list
    value_scale: tuple
    expected: dict


@dataclasses.dataclass
class State:
    spec: Spec
    base: dict
    graph: object  # the configuration's DataflowGraph


def load(root: str, name: str, config: dict, params: dict) -> Spec:
    """The cell's parameters, checked; nothing is built yet."""
    if set(params) != PARAMS:
        raise ValueError(f"{name}: traffic keys besides the generator's must "
                         f"be {sorted(PARAMS)}, got {sorted(params)}")
    for c in params["calls"]:
        if set(c) != {"batch", "configs"} or not c["configs"] or (
                not c["batch"] and len(c["configs"]) != 1):
            raise ValueError(f"{name}: malformed call {c}")
    lo, hi = params["value_scale"]
    if not 0 < lo <= hi:
        raise ValueError(f"{name}: value_scale must be 0 < lo <= hi, got {lo}, {hi}")
    with open(os.path.join(root, "bench", "expected", f"{name}.json")) as f:
        expected = json.load(f)
    if [len(c) for c in expected["calls"]] != [
            len(c["configs"]) for c in params["calls"]]:
        raise ValueError(f"{name}: bench/expected/{name}.json does not track "
                         f"one row per call member")
    return Spec(root=root, name=name, config=config, calls=params["calls"],
                value_scale=(lo, hi), expected=expected)


def default_entry():
    import repro

    return repro.run


def overlay_configs(call: dict) -> list:
    from repro.core.overlay import OverlayConfig

    return [OverlayConfig(**c) for c in call["configs"]]


def _call(entry, graph, call: dict, nx: int, ny: int) -> list:
    cfgs = overlay_configs(call)
    if call["batch"]:
        return list(entry(graph, batch=cfgs, nx=nx, ny=ny))
    return [entry(graph, cfgs[0], nx=nx, ny=ny)]


def prepare(spec: Spec, log) -> State:
    from repro.core.graph import DataflowGraph

    base = generator.build_graph(spec.root, spec.config["graph"])
    graph = DataflowGraph(**base)
    if (graph.num_nodes, graph.num_edges) != (
            spec.config["nodes"], spec.config["edges"]):
        raise ValueError(f"{spec.name}: the graph has {graph.num_nodes} nodes "
                         f"and {graph.num_edges} edges, the configuration "
                         f"states {spec.config['nodes']} and {spec.config['edges']}")
    log(f"graph: {graph.num_nodes} nodes, {graph.num_edges} edges, "
        f"{spec.config['nx']}x{spec.config['ny']} PEs")
    return State(spec=spec, base=base, graph=graph)


def warm_up(state: State, entry, log) -> None:
    """Compile every program the window runs, without simulating its graph:
    each call runs once on the cell's own packed memory with every node
    marked absent, which has the window's shapes and finishes in one chunk."""
    from repro.place.api import graph_memory_for_config

    nx, ny = state.spec.config["nx"], state.spec.config["ny"]
    for k, call in enumerate(state.spec.calls):
        t = time.perf_counter()
        gm = graph_memory_for_config(state.graph, nx, ny, overlay_configs(call)[0])
        empty = dataclasses.replace(gm, valid=np.zeros_like(gm.valid))
        _call(entry, empty, call, nx, ny)
        log(f"warm-up call {k}: lmax {gm.lmax}, emax {gm.emax}, "
            f"{time.perf_counter() - t:.3f} s")


def _values(state: State, seed: int, key: int) -> np.ndarray:
    return generator.query_values(state.spec.value_scale,
                                  state.base["initial_values"], seed, key)


def issue(state: State, entry, key: int, seed: int) -> list:
    spec = state.spec
    graph = dataclasses.replace(state.graph, initial_values=_values(state, seed, key))
    return _call(entry, graph, spec.calls[key % len(spec.calls)],
                 spec.config["nx"], spec.config["ny"])


def counts(results: list) -> dict:
    return {"simulations": len(results), "cycles": sum(r.cycles for r in results)}


def describe(results: list) -> str:
    return f"stats {[[getattr(r, k) for k in STATS] for r in results]}"


def check(state: State, queries: list, seed: int) -> dict:
    """Every answered query against the plain reference and the tracked
    statistics: ``{name: [reading, limit]}`` plus the failed query count."""
    spec, base = state.spec, state.base
    lv = reference.levelize(base["opcode"], base["fanout_ptr"],
                            base["fanout_dst"], base["fanout_slot"])
    limit = spec.expected["value_gap_limit"]
    gap, mismatched, not_done, failed = 0.0, 0, 0, 0
    for q in queries:
        tracked = spec.expected["calls"][q.key % len(spec.calls)]
        ref = reference.evaluate(lv, _values(state, seed, q.key))
        q_gap = max(reference.value_gap(r.values, ref) for r in q.results)
        q_bad = sum(any(getattr(r, k) != want[k] for k in STATS)
                    for r, want in zip(q.results, tracked))
        q_bad += abs(len(q.results) - len(tracked))
        q_undone = sum(not r.done for r in q.results)
        gap = max(gap, q_gap)
        mismatched += q_bad
        not_done += q_undone
        failed += bool(q_gap > limit or q_bad or q_undone)
    return {"failed": failed, "checks": {
        "value_gap": [gap, limit],
        "stats_mismatch": [mismatched, 0],
        "not_done": [not_done, 0],
    }}
