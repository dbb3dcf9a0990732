"""GAP PageRank on a Graph500 Kronecker graph as a dataflow DAG, and the
``inject_blocked`` counter that its hub fanout drains fill.

The DAG's final scores are GAP's pull PageRank (the plain float64 numpy
reference over the same edge list), the overlay computes the DAG's values
exactly, and ``inject_blocked`` is the PE-injection part of
``noc_deflections``, equal on every engine and path.
"""
import os
import subprocess
import sys

import numpy as np
import pytest

import repro
from repro.core import workloads as wl
from repro.core.graph import OP_ADD, OP_INPUT, OP_MUL, reference_evaluate
from repro.core.overlay import OverlayConfig
from repro.telemetry import TelemetrySpec


@pytest.fixture(scope="module")
def hub_graph():
    """Scale 6: 2,174 nodes; the top vertex has 52 neighbours, the mean 13.4."""
    return wl.kron_pagerank_graph(6, seed=1)


@pytest.mark.parametrize("scale", [6, 8])
def test_dag_scores_match_numpy_pagerank(scale):
    g = wl.kron_pagerank_graph(scale, seed=scale)
    u, v = wl.kron_edges(scale, seed=scale)
    want = wl.pagerank_reference(u, v, 1 << scale)
    verts = np.unique(u)
    # The last |V| node ids are the final scores, vertices in ascending id.
    got = reference_evaluate(g)[-verts.size:]
    # float32 sums of at most a few hundred terms.
    np.testing.assert_allclose(got, want[verts], rtol=1e-5)


@pytest.mark.parametrize("nx,ny", [(3, 5), (4, 4)])
def test_run_equals_reference_evaluate(hub_graph, nx, ny):
    r = repro.run(hub_graph, OverlayConfig(scheduler="ooo"), nx=nx, ny=ny)
    assert r.done
    np.testing.assert_array_equal(r.values, reference_evaluate(hub_graph))


@pytest.mark.parametrize("seed,nodes,edges,top_degree", [
    (0, 10896, 18912, 168),
    (3, 11028, 19224, 160),
])
def test_counts_and_skew_are_stable_for_a_seed(seed, nodes, edges, top_degree):
    g = wl.kron_pagerank_graph(8, seed=seed)
    g.validate()
    assert (g.num_nodes, g.num_edges) == (nodes, edges)
    assert set(np.unique(g.opcode)) == {OP_INPUT, OP_ADD, OP_MUL}
    u, _ = wl.kron_edges(8, seed=seed)
    deg = np.bincount(u)
    assert deg.max() == top_degree
    # Power-law skew: the top vertex feeds several times the mean.
    assert deg.max() > 3 * deg[deg > 0].mean()
    # The hub's contribution is the graph's largest fanout.
    assert g.fanout_count().max() == top_degree


def test_kron_edges_are_undirected_without_loops_or_duplicates():
    u, v = wl.kron_edges(8, seed=2)
    pairs = set(zip(u.tolist(), v.tolist()))
    assert len(pairs) == u.size
    assert all(a != b for a, b in pairs)
    assert all((b, a) in pairs for a, b in pairs)
    assert (np.diff(u * 256 + v) > 0).all()


def _counts(r):
    return (r.cycles, r.done, r.delivered, r.deflections, r.busy_cycles,
            r.noc_deflections, r.eject_deflections, r.inject_blocked)


@pytest.mark.parametrize("make", [
    lambda: wl.kron_pagerank_graph(6, seed=1),
    lambda: wl.arrow_lu_graph(2, 4, 2, seed=3),
], ids=["kron", "arrow"])
def test_inject_blocked_is_part_of_noc_deflections(make):
    r = repro.run(make(), OverlayConfig(), nx=2, ny=2)
    assert r.done
    assert 0 < r.inject_blocked <= r.noc_deflections


def test_inject_blocked_bit_equal_across_engines(hub_graph):
    solo = repro.run(hub_graph, OverlayConfig(), nx=2, ny=2)
    reference = repro.run(hub_graph, OverlayConfig(check_every=1), nx=2, ny=2)
    select = repro.run(hub_graph, OverlayConfig(engine="select"), nx=2, ny=2)
    batch = repro.run(hub_graph, batch=[OverlayConfig(select_latency=k)
                                        for k in (1, 2)], nx=2, ny=2)
    lat2 = repro.run(hub_graph, OverlayConfig(select_latency=2), nx=2, ny=2)
    assert _counts(reference) == _counts(solo)
    assert _counts(select) == _counts(solo)
    assert _counts(batch[0]) == _counts(solo)
    assert _counts(batch[1]) == _counts(lat2)


MESH_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import sys; sys.path.insert(0, "src")
import repro
from repro.core import workloads as wl
from repro.core.overlay import OverlayConfig
from repro.core.partition import build_graph_memory
from repro.launch.mesh import make_local_mesh
mesh = make_local_mesh(2, 2)
gm = build_graph_memory(wl.kron_pagerank_graph(6, seed=1), 4, 4)
keys = ("cycles", "delivered", "deflections", "busy_cycles",
        "noc_deflections", "eject_deflections", "inject_blocked")
row = lambda r: tuple(getattr(r, k) for k in keys)
cfgs = [OverlayConfig(), OverlayConfig(select_latency=2)]
solo = [repro.run(gm, c) for c in cfgs]
assert row(repro.run(gm, cfgs[0], mesh=mesh)) == row(solo[0])
for got, want in zip(repro.run(gm, mesh=mesh, batch=cfgs), solo):
    assert row(got) == row(want), (row(got), row(want))
assert solo[0].inject_blocked > 0
print("MESH_INJECT_BLOCKED_OK")
"""


def test_inject_blocked_bit_equal_on_a_2x2_mesh():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", MESH_SCRIPT], cwd=os.getcwd(),
                         capture_output=True, text=True, env=env, timeout=300)
    assert "MESH_INJECT_BLOCKED_OK" in out.stdout, out.stderr[-2000:]


def test_inject_blocked_equals_the_stall_attribution(hub_graph):
    spec = TelemetrySpec(buckets=8, bucket_cycles=64)
    r = repro.run(hub_graph, OverlayConfig(telemetry=spec), nx=2, ny=2)
    stalls = r.telemetry.traces["stall_inject"]
    assert r.inject_blocked == int(stalls.sum()) > 0
    # The hub's PE waits on its router longer than the mean PE.
    assert stalls.max() > stalls.mean()
