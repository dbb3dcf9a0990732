"""The packed edge and node words that the cycle body gathers.

``overlay.device_graph`` packs the fanout tables into one int32 word per
edge and the node fields the cycle body reads into one word per slot (see
``overlay.DeviceGraph``). Decoded, every field must equal the
``GraphMemory`` field it came from, padded entries included; the limits
must hold exactly where the words stop fitting in int32; and the runs that
read them must stay cycle-exact on the fanout shapes the ``last`` bit
decides: sinks with no fanout, single-edge fanouts and a hub.
"""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

import repro
from repro.core import overlay
from repro.core import workloads as wl
from repro.core.graph import (
    OP_ADD, OP_MUL, OP_SUB, GraphBuilder, reference_evaluate,
)
from repro.core.overlay import OverlayConfig
from repro.place.api import uniform_graph_memories

POLICIES = ("ooo", "inorder", "scan", "lru_flat")
INT32_MAX = 2**31 - 1


def _padded(g, nx, ny):
    """Two placements of ``g`` packed to one shape, so that the lighter
    PEs' node and edge rows end in padding."""
    n, pes = g.num_nodes, nx * ny
    rng = np.random.default_rng(7)
    node_pes = [np.arange(n) % pes, rng.integers(0, pes, size=n)]
    return uniform_graph_memories(g, nx, ny, node_pes)


MEMORIES = {
    "arrow_lu_4x4": lambda: _padded(wl.arrow_lu_graph(3, 6, 4, seed=5), 4, 4),
    "kron6_3x5": lambda: _padded(wl.kron_pagerank_graph(6, seed=1), 3, 5),
}


@pytest.fixture(scope="module", params=sorted(MEMORIES))
def memories(request):
    return MEMORIES[request.param]()


def _expected_last(gm):
    """``last`` flags built slot by slot, independently of the packer."""
    last = np.zeros(gm.e_dst_pe.shape, bool)
    for pe in range(gm.num_pes):
        for slot in range(gm.lmax):
            if gm.fo_count[pe, slot] > 0:
                last[pe, gm.fo_base[pe, slot] + gm.fo_count[pe, slot] - 1] = 1
    return last


@pytest.mark.parametrize("xp", [np, jnp], ids=["numpy", "jnp"])
def test_words_decode_to_the_graph_memory(memories, xp):
    for gm in memories:
        edge = overlay.edge_fields(xp.asarray(overlay.edge_word(gm)),
                                   gm.lmax)
        node = overlay.node_fields(xp.asarray(overlay.node_word(gm)))
        np.testing.assert_array_equal(edge["dst_pe"], gm.e_dst_pe)
        np.testing.assert_array_equal(edge["dst_slot"], gm.e_dst_slot)
        np.testing.assert_array_equal(edge["opidx"], gm.e_dst_opidx)
        np.testing.assert_array_equal(edge["last"], _expected_last(gm))
        np.testing.assert_array_equal(node["fo_base"], gm.fo_base)
        np.testing.assert_array_equal(node["opcode"], gm.opcode)
        np.testing.assert_array_equal(node["has_fanout"], gm.fo_count > 0)


def test_padded_entries_are_zero_words(memories):
    for gm in memories:
        used = np.arange(gm.emax) < (gm.fo_base + gm.fo_count).max(
            axis=1, keepdims=True)
        assert (~used).any(), "no padded edge column to check"
        assert (overlay.edge_word(gm)[~used] == 0).all()
        assert (overlay.node_word(gm)[~gm.valid] == 0).all()


def test_device_graph_holds_the_words(memories):
    gm = memories[0]
    g = overlay.device_graph(gm)
    assert not {"e_dst_pe", "e_dst_slot", "e_dst_opidx", "fo_base"} & set(g)
    for key, word in (("e_word", overlay.edge_word(gm)),
                      ("n_word", overlay.node_word(gm))):
        assert g[key].dtype == jnp.int32
        np.testing.assert_array_equal(
            np.asarray(g[key]), word.reshape(gm.nx, gm.ny, -1))


# ---- limits --------------------------------------------------------------

EMAX_LIMIT = 2**(30 - overlay.OPCODE_BITS) - 1


def _largest_edge_word(slots):
    return ((slots - 1) * 2 + 1) * 2 + 1


def _largest_node_word(emax):
    return ((emax << overlay.OPCODE_BITS)
            + (1 << overlay.OPCODE_BITS) - 1) * 2 + 1


@pytest.mark.parametrize("pes,lmax,emax", [
    (1, 2**29, 1), (256, 2**21, 1), (300, 3264, 3654), (1, 1, EMAX_LIMIT)])
def test_limits_accept_just_inside(pes, lmax, emax):
    overlay.check_word_limits(pes, lmax, emax)
    assert _largest_edge_word(pes * lmax) <= INT32_MAX
    assert _largest_node_word(emax) <= INT32_MAX


@pytest.mark.parametrize("pes,lmax,emax,word", [
    (1, 2**29 + 1, 1, "edge word"), (256, 2**21 + 32, 1, "edge word"),
    (1, 1, EMAX_LIMIT + 1, "node word")])
def test_limits_raise_just_past(pes, lmax, emax, word):
    with pytest.raises(ValueError, match=word):
        overlay.check_word_limits(pes, lmax, emax)
    assert (_largest_edge_word(pes * lmax) > INT32_MAX
            or _largest_node_word(emax) > INT32_MAX)


def test_device_graph_checks_the_limits(memories):
    too_wide = dataclasses.replace(memories[0], emax=EMAX_LIMIT + 1)
    with pytest.raises(ValueError, match="node word"):
        overlay.device_graph(too_wide)


# ---- cycle-exact runs on the fanout shapes the ``last`` bit decides ------

def _sinks_graph():
    """Eight sinks with no fanout over a shared layer, and an input no
    node reads."""
    b = GraphBuilder()
    x = [b.input(0.5 + 0.25 * i) for i in range(6)]
    b.input(9.0)
    mid = [b.op(OP_ADD, x[i], x[i + 1]) for i in range(5)]
    for i in range(8):
        b.op((OP_MUL, OP_SUB)[i % 2], mid[i % 5], mid[(i + 2) % 5])
    return b.build()


def _hub_graph():
    """Four hubs send 30, 17, 9 and 1 edges, three of them to both operands
    of one node, and pairs of their children feed a layer of sinks."""
    b = GraphBuilder()
    x = [b.input(1.0 + 0.125 * i) for i in range(12)]
    kids = []
    for k, edges in enumerate((30, 17, 9, 1)):
        hub = b.op(OP_ADD, x[k], x[k + 1])
        if edges > 1:
            kids.append(b.op(OP_SUB, hub, hub))
            edges -= 2
        kids += [b.op(OP_MUL, hub, x[(k + i) % 12]) for i in range(edges)]
    for i in range(0, len(kids) - 1, 3):
        b.op(OP_ADD, kids[i], kids[i + 1])
    return b.build()


GRAPHS = {
    "sinks": _sinks_graph,
    "single_edge": lambda: wl.chain(20, seed=4),
    "hub": _hub_graph,
}

#: (cycles, delivered, noc_deflections, eject_deflections, busy_cycles,
#: inject_blocked) on 2x2, as the engine gave them before the tables were
#: packed into words.
PINNED = {
    ("hub", "ooo"): (69, 152, 33, 24, 76, 33),
    ("hub", "inorder"): (69, 152, 31, 24, 76, 31),
    ("hub", "scan"): (69, 152, 33, 25, 76, 33),
    ("hub", "lru_flat"): (69, 152, 33, 25, 76, 33),
    ("single_edge", "ooo"): (50, 40, 0, 4, 20, 0),
    ("single_edge", "inorder"): (50, 40, 0, 4, 20, 0),
    ("single_edge", "scan"): (50, 40, 0, 4, 20, 0),
    ("single_edge", "lru_flat"): (50, 40, 0, 4, 20, 0),
    ("sinks", "ooo"): (13, 26, 5, 2, 13, 5),
    ("sinks", "inorder"): (13, 26, 5, 2, 13, 5),
    ("sinks", "scan"): (13, 26, 5, 2, 13, 5),
    ("sinks", "lru_flat"): (13, 26, 5, 2, 13, 5),
}

_STATS = ("cycles", "delivered", "noc_deflections", "eject_deflections",
          "busy_cycles", "inject_blocked")


@pytest.mark.parametrize("policy", POLICIES)
@pytest.mark.parametrize("graph", sorted(GRAPHS))
def test_fanout_shapes_run_cycle_exact(graph, policy):
    g = GRAPHS[graph]()
    runs = [repro.run(g, OverlayConfig(scheduler=policy, check_every=k),
                      nx=2, ny=2) for k in (None, 1)]
    stats = [tuple(getattr(r, k) for k in _STATS) for r in runs]
    assert all(r.done for r in runs)
    assert stats[0] == stats[1]
    assert stats[0] == PINNED[graph, policy]
    for r in runs:
        np.testing.assert_array_equal(r.values, reference_evaluate(g))
