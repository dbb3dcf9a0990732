"""The benchmark's copy of the GAP PageRank / Kronecker graph family against
the program's generator, and the configuration's stated size."""
import json
import os

import numpy as np
import pytest

from bench import generator
from bench.harness import ROOT
from repro.core import workloads as wl

ARRAYS = ("opcode", "fanout_ptr", "fanout_dst", "fanout_slot", "initial_values")


@pytest.mark.parametrize("scale,seed,iterations", [(8, 0, 2), (7, 5, 3)])
def test_family_is_the_program_graph_id_for_id(scale, seed, iterations):
    got = generator.build_graph(ROOT, dict(
        family="kron_pagerank", scale=scale, edge_factor=16,
        iterations=iterations, damping=0.85, seed=seed))
    want = wl.kron_pagerank_graph(scale, iterations=iterations, seed=seed)
    for name in ARRAYS:
        a, b = got[name], getattr(want, name)
        assert a.dtype == b.dtype, name
        np.testing.assert_array_equal(a, b, err_msg=name)


def test_configuration_states_its_graph():
    path = os.path.join(ROOT, "bench", "configs", "gapkron14_pr2_15x20.json")
    with open(path) as f:
        config = json.load(f)
    base = generator.build_graph(ROOT, config["graph"])
    assert (base["opcode"].size, base["fanout_dst"].size) == (
        config["nodes"], config["edges"])
    assert config["nx"] * config["ny"] == config["pes"] == 300
