"""A tiny root defined only by data and module files, so the harness's tests
run the whole path on the CPU in seconds."""
import json
import os
import shutil
import textwrap

import pytest

from bench.drivers.repro_run import STATS

BENCH = os.path.dirname(os.path.abspath(__file__))
# Simulated statistics of arrow_lu(2, 4, 2) on 2x2 under ooo with
# select_latency 1 to 4 (CPU, the cycle model's integers).
TINY_STATS = [[200, 494, 230, 247, 183, 47], [244, 494, 175, 247, 113, 62],
              [309, 494, 79, 247, 59, 20], [389, 494, 70, 247, 51, 19]]

# A driver that calls something other than ``repro.run``: the program's own
# node-by-node evaluator, checked against the benchmark's reference.
PLAIN_DRIVER = textwrap.dedent('''
    """Queries that evaluate the configuration's graph with the program's
    ``reference_evaluate``."""
    import dataclasses

    import numpy as np

    from bench import generator, reference


    def load(root, name, config, params):
        assert set(params) == {"value_scale"}
        return dict(root=root, config=config, **params)


    def default_entry():
        from repro.core.graph import reference_evaluate

        return reference_evaluate


    def prepare(spec, log):
        from repro.core.graph import DataflowGraph

        base = generator.build_graph(spec["root"], spec["config"]["graph"])
        return dict(spec=spec, base=base, graph=DataflowGraph(**base))


    def warm_up(state, entry, log):
        entry(state["graph"])


    def _values(state, seed, key):
        return generator.query_values(state["spec"]["value_scale"],
                                      state["base"]["initial_values"], seed, key)


    def issue(state, entry, key, seed):
        return entry(dataclasses.replace(state["graph"],
                                         initial_values=_values(state, seed, key)))


    def counts(results):
        return {"answers": 1}


    def describe(results):
        return f"{results.shape[0]} values"


    def check(state, queries, seed):
        b = state["base"]
        lv = reference.levelize(b["opcode"], b["fanout_ptr"], b["fanout_dst"],
                                b["fanout_slot"])
        bad = [q for q in queries if not np.array_equal(
            q.results, reference.evaluate(lv, _values(state, seed, q.key)))]
        return {"failed": len(bad), "checks": {"unequal": [len(bad), 0]}}
''')

QUERIES_PER_S = textwrap.dedent('''
    """Queries answered per second of the window."""


    def read(run):
        return len(run.queries) / run.window_s
''')


def _dump(path, obj):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        f.write(obj if isinstance(obj, str) else json.dumps(obj))


@pytest.fixture
def tiny_root(tmp_path):
    """A checkout-like root holding one tiny configuration and three cells:
    ``tiny.query`` and ``tiny.sweep`` reuse the real traffic and driver
    files; ``tiny.plain`` is an open loop with repeats whose driver, traffic
    and metric are files of its own."""
    root = str(tmp_path)
    for d in ("drivers", "families", "metrics", "traffic"):
        shutil.copytree(os.path.join(BENCH, d), os.path.join(root, "bench", d))
    _dump(os.path.join(root, "bench", "configs", "tiny.json"), {
        "graph": {"family": "arrow_lu", "blocks": 2, "block_size": 4,
                  "border": 2, "seed": 3},
        "nodes": 315, "edges": 494, "nx": 2, "ny": 2})
    rows = [dict(zip(STATS, r)) for r in TINY_STATS]
    _dump(os.path.join(root, "bench", "expected", "tiny.query.json"),
          {"value_gap_limit": 1e-4, "calls": [rows[:1]]})
    _dump(os.path.join(root, "bench", "expected", "tiny.sweep.json"),
          {"value_gap_limit": 1e-4, "calls": [rows]})
    _dump(os.path.join(root, "bench", "drivers", "plain_eval.py"), PLAIN_DRIVER)
    _dump(os.path.join(root, "bench", "metrics", "queries_per_s.py"),
          QUERIES_PER_S)
    _dump(os.path.join(root, "bench", "traffic", "plain.json"), {
        "driver": "plain_eval",
        "arrivals": {"kind": "poisson", "rate_per_s": 40.0, "burst": 2},
        "repeat": {"share": 0.5, "zipf_s": 1.0},
        "value_scale": [0.9, 1.1]})
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        spec = json.load(f)
    lu = ["tiny.query", "tiny.sweep"]
    _dump(os.path.join(root, "BENCHMARK.json"), {
        "configs": [{"name": "tiny", "file": "bench/configs/tiny.json"}],
        "workloads": [
            {"name": "tiny.query", "config": "tiny", "traffic": "query", "chips": 1},
            {"name": "tiny.sweep", "config": "tiny", "traffic": "sweep", "chips": 1},
            {"name": "tiny.plain", "config": "tiny", "traffic": "plain", "chips": 1}],
        "end_to_end": [
            {**m, "workloads": lu} if m["name"] != "setup_s" else m
            for m in spec["end_to_end"]] + [
            {"name": "queries_per_s", "unit": "queries/s", "better": "higher",
             "source": "host_clock", "workloads": ["tiny.plain"]}],
        "per_layer": [{**m, "workloads": lu} for m in spec["per_layer"]],
    })
    return root
