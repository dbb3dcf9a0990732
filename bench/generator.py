"""The one traffic generator: turns a cell's traffic file into the queries of
a run, from the run's seed.

A traffic file (``bench/traffic/<traffic>.json``) is data. The generator
reads its general keys:

``driver``
    The call a query makes. ``bench/drivers/<driver>.py`` builds each query
    from the configuration and the traffic's other keys, issues it, and
    checks the answers against the plain reference.
``arrivals``
    ``{"kind": "closed"}``: one caller sends the next query when the last
    has returned. ``{"kind": "poisson", "rate_per_s": r, "burst": b}``: an
    open loop; bursts of ``b`` queries arrive at the times of a Poisson
    process of ``r / b`` bursts per second, the first at the window's
    start, whether or not the queries before them have been answered, so a
    query's latency includes its wait.
``repeat`` (optional)
    ``{"share": p, "zipf_s": s}``: with probability ``p`` a query repeats
    one of the distinct earlier queries of the run, picked by Zipf(s) over
    their order of first appearance; otherwise it is new.

Query ``i`` of a run has a ``key``: ``i`` for a new query, the repeated
query's key for a repeat. A driver makes a query's content from ``(seed,
key)`` alone, so the same seed gives the same queries.

A configuration file names a graph family (``bench/families/<family>.py``)
with its sizes and the PE grid.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import itertools
import json
import os
from typing import Iterator

import numpy as np

ARRIVALS = {"closed": set(), "poisson": {"rate_per_s", "burst"}}


@dataclasses.dataclass(frozen=True)
class Traffic:
    driver: str
    arrivals: dict
    repeat: dict | None
    #: The driver's own keys.
    params: dict


@dataclasses.dataclass(frozen=True)
class Spec:
    """Query ``index`` of a run: its ``key``, and its arrival in seconds
    after the window's start (``None`` in a closed loop)."""

    index: int
    key: int
    arrival_s: float | None


def load_traffic(path: str) -> Traffic:
    with open(path) as f:
        raw = json.load(f)
    params = {k: v for k, v in raw.items()
              if k not in ("driver", "arrivals", "repeat")}
    arrivals = raw.get("arrivals", {})
    kind = arrivals.get("kind")
    if "driver" not in raw or kind not in ARRIVALS or (
            set(arrivals) != ARRIVALS[kind] | {"kind"}):
        raise ValueError(f"{path}: needs a driver and arrivals of a kind in "
                         f"{sorted(ARRIVALS)} with its keys, got {raw}")
    if kind == "poisson" and not (arrivals["rate_per_s"] > 0
                                  and int(arrivals["burst"]) >= 1):
        raise ValueError(f"{path}: poisson arrivals need rate_per_s > 0 and "
                         f"burst >= 1, got {arrivals}")
    repeat = raw.get("repeat")
    if repeat is not None and (set(repeat) != {"share", "zipf_s"}
                               or not 0 <= repeat["share"] <= 1):
        raise ValueError(f"{path}: repeat is {{share in [0, 1], zipf_s}}, "
                         f"got {repeat}")
    return Traffic(driver=raw["driver"], arrivals=arrivals, repeat=repeat,
                   params=params)


def schedule(traffic: Traffic, seed: int) -> Iterator[Spec]:
    """The run's queries in the order they are sent, without end."""
    s = seed % 2**64
    gaps = np.random.default_rng([s, 1])
    firsts: list[int] = []  # keys of the distinct queries so far
    t = 0.0
    for i in itertools.count():
        key = i
        if traffic.repeat and firsts:
            rng = np.random.default_rng([s, i, 2])
            if rng.random() < traffic.repeat["share"]:
                w = np.arange(1, len(firsts) + 1, dtype=np.float64)
                w = w ** -traffic.repeat["zipf_s"]
                key = firsts[rng.choice(len(firsts), p=w / w.sum())]
        if key == i:
            firsts.append(i)
        arrival = None
        if traffic.arrivals["kind"] == "poisson":
            burst = int(traffic.arrivals["burst"])
            if i and i % burst == 0:
                t += gaps.exponential(burst / traffic.arrivals["rate_per_s"])
            arrival = t
        yield Spec(i, key, arrival)


def build_graph(root: str, graph: dict) -> dict:
    """Base graph arrays of a configuration's ``graph`` entry."""
    args = dict(graph)
    family = args.pop("family")
    path = os.path.join(root, "bench", "families", f"{family}.py")
    spec = importlib.util.spec_from_file_location(f"bench_family_{family}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.build(**args)


def query_values(value_scale, base_values: np.ndarray, seed: int,
                 key: int) -> np.ndarray:
    """``base_values`` with every value scaled by its own uniform(lo, hi)
    factor, ``value_scale = [lo, hi]``, drawn from ``(seed, key)``."""
    rng = np.random.default_rng([seed % 2**64, key])
    lo, hi = value_scale
    scale = rng.uniform(lo, hi, size=base_values.shape).astype(np.float32)
    return base_values * scale
