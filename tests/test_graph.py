"""Dataflow-graph IR: construction, validation, reference eval, criticality."""
import importlib

import numpy as np
import pytest
from _hyp import given, settings, st

from repro.core import workloads as wl
from repro.core.criticality import (
    asap_levels, criticality, criticality_levels, height, slack,
)
from repro.core.graph import (
    OP_ADD, OP_MUL, DataflowGraph, GraphBuilder, reference_evaluate,
)


def test_builder_and_reference_eval():
    b = GraphBuilder()
    x = b.input(2.0)
    y = b.input(3.0)
    s = b.op(OP_ADD, x, y)      # 5
    p = b.op(OP_MUL, s, y)      # 15
    g = b.build()
    vals = reference_evaluate(g)
    assert vals[s] == pytest.approx(5.0)
    assert vals[p] == pytest.approx(15.0)


def test_validation_catches_missing_operand():
    b = GraphBuilder()
    x = b.input(1.0)
    b._op.append(OP_ADD)  # corrupt: op node with no edges
    b._init.append(0.0)
    with pytest.raises(ValueError):
        b.build()


def test_topological_order_covers_all():
    g = wl.random_dag(200, seed=0)
    order = g.topological_order()
    assert sorted(order) == list(range(g.num_nodes))


def test_height_and_slack_invariants():
    g = wl.random_dag(150, seed=1)
    h = height(g)
    s = slack(g)
    a = asap_levels(g)
    assert (s >= 0).all()
    assert (h >= 0).all()
    # critical path nodes have zero slack
    assert (s == 0).sum() >= 1
    # height decreases along edges
    ptr, dst = g.fanout_ptr, g.fanout_dst
    for v in range(g.num_nodes):
        for u in dst[ptr[v]:ptr[v + 1]]:
            assert h[v] >= h[u] + 1
            assert a[u] >= a[v] + 1


@given(st.integers(10, 120), st.integers(0, 10_000))
@settings(max_examples=15, deadline=None)
def test_random_dag_reference_eval_finite(n, seed):
    g = wl.random_dag(n, seed=seed)
    g.validate()
    vals = reference_evaluate(g)
    assert np.isfinite(vals).all()


def test_criticality_metrics_exist():
    g = wl.reduction_tree(16)
    for m in ("height", "neg_slack", "fanout_height"):
        c = criticality(g, m)
        assert c.shape == (g.num_nodes,)
    with pytest.raises(ValueError):
        criticality(g, "bogus")


def test_workload_generators_shapes():
    for g in [wl.chain(8), wl.reduction_tree(9), wl.layered_dag(4, 6),
              wl.sparse_lu_graph(8, 0.4, seed=1), wl.banded_lu_graph(12, 3),
              wl.arrow_lu_graph(2, 4, 3), wl.elimination_tree_graph(2, 3, 4)]:
        g.validate()
        assert g.num_nodes > 0
        assert np.isfinite(reference_evaluate(g)).all()


# ---------------------------------------------------------------------------
# The level peel behind height / asap_levels against the scalar loops it
# replaced, kept here as oracles.
# ---------------------------------------------------------------------------

#: the module itself (``repro.core.criticality`` the attribute is the function)
CRIT = importlib.import_module("repro.core.criticality")


def _oracle_asap(g):
    lvl = np.zeros(g.num_nodes, dtype=np.int64)
    ptr, dst = g.fanout_ptr, g.fanout_dst
    for v in g.topological_order():
        for u in dst[ptr[v]:ptr[v + 1]]:
            lvl[u] = max(lvl[u], lvl[v] + 1)
    return lvl


def _oracle_height(g):
    h = np.zeros(g.num_nodes, dtype=np.int64)
    ptr, dst = g.fanout_ptr, g.fanout_dst
    for v in g.topological_order()[::-1]:
        lo, hi = ptr[v], ptr[v + 1]
        if hi > lo:
            h[v] = 1 + h[dst[lo:hi]].max()
    return h


def _csr(n, edges):
    """A DataflowGraph from ``(src, dst, slot)`` triples, unvalidated."""
    src = np.array([e[0] for e in edges], dtype=np.int64)
    order = np.argsort(src, kind="stable")
    ptr = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(np.bincount(src, minlength=n), out=ptr[1:])
    return DataflowGraph(
        opcode=np.zeros(n, np.int8),
        fanout_ptr=ptr,
        fanout_dst=np.array([e[1] for e in edges], dtype=np.int64)[order],
        fanout_slot=np.array([e[2] for e in edges], dtype=np.int8)[order],
        initial_values=np.zeros(n, np.float32))


def _pure_chain(n):
    """n nodes, each fed both operands by the one before: one node a level."""
    b = GraphBuilder()
    v = b.input(1.0)
    for _ in range(n - 1):
        v = b.op(OP_ADD, v, v)
    return b.build()


def _wide(width, ops):
    """``width`` inputs, then ``ops`` adds of disjoint input pairs."""
    b = GraphBuilder()
    xs = [b.input(1.0) for _ in range(width)]
    for i in range(ops):
        b.op(OP_ADD, xs[2 * i], xs[2 * i + 1])
    return b.build()


PEEL_GRAPHS = {
    "random_dag_s0": lambda: wl.random_dag(300, seed=0),
    "random_dag_s7": lambda: wl.random_dag(1000, seed=7, input_frac=0.05),
    "layered_w40": lambda: wl.layered_dag(30, 40, seed=2),
    "chain_2000": lambda: _pure_chain(2000),
    "wide_one_level": lambda: _wide(1000, 0),
    "wide_two_levels": lambda: _wide(1000, 500),
    "single_node": lambda: _wide(1, 0),
    "empty": lambda: _csr(0, []),
    "arrow_lu_4_10_8": lambda: wl.arrow_lu_graph(4, 10, 8),
}

#: ``_NARROW`` per path: every step as a loop, every step whole-array, and
#: the adaptive threshold the code ships with.
PEEL_PATHS = {"scalar": 1 << 62, "vector": 0, "adaptive": CRIT._NARROW}


@pytest.mark.parametrize("path", sorted(PEEL_PATHS))
@pytest.mark.parametrize("name", sorted(PEEL_GRAPHS))
def test_peel_matches_scalar_oracles(name, path, monkeypatch):
    monkeypatch.setattr(CRIT, "_NARROW", PEEL_PATHS[path])
    g = PEEL_GRAPHS[name]()
    h, a = height(g), asap_levels(g)
    for got, want in ((h, _oracle_height(g)), (a, _oracle_asap(g))):
        assert got.dtype == want.dtype == np.int64
        np.testing.assert_array_equal(got, want)
    c, levels = criticality_levels(g)
    np.testing.assert_array_equal(c, h)
    assert levels == (int(h.max()) + 1 if g.num_nodes else 0)


@pytest.mark.parametrize("path", sorted(PEEL_PATHS))
def test_peel_refuses_a_cycle(path, monkeypatch):
    monkeypatch.setattr(CRIT, "_NARROW", PEEL_PATHS[path])
    # Sixty-four inputs feed node 64; 64 -> 65 -> 66 -> 65 closes a cycle.
    edges = [(i, 64, 0) for i in range(64)] + [(64, 65, 0), (65, 66, 0),
                                               (66, 65, 1)]
    g = _csr(67, edges)
    for fn in (height, asap_levels, criticality, slack):
        with pytest.raises(ValueError, match="cycle"):
            fn(g)
