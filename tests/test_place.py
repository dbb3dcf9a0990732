"""Placement subsystem (repro.place): identity bit-exactness across every
scheduler policy, cost-model/vmap consistency, annealer determinism under a
fixed PRNG key, annealed-beats-random on the fig1 workload family, and the
backend/device-count-aware check_every autotune."""
import numpy as np
import pytest

from repro import place
from repro.core import schedulers
from repro.core import workloads as wl
from repro.core.overlay import (
    OverlayConfig, resolve_check_every, simulate, simulate_batch,
)
from repro.core.criticality import criticality
from repro.core.partition import build_graph_memory, place_nodes

ALL_POLICIES = sorted(schedulers.REGISTRY)

#: small fig1-family graph: fast, but structured like the paper's workloads
G = wl.arrow_lu_graph(3, 6, 4, seed=5)

#: quick annealer budget for tests (the benchmarks use deeper ones)
ACFG = place.AnnealConfig(replicas=6, rounds=10, steps=192, seed=0)


def _stats(r):
    return (r.done, r.cycles, r.deflections, r.busy_cycles, r.delivered)


# ---------------------------------------------------------------------------
# Identity placement == the legacy direct-GraphMemory path, bit-exactly.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("sched", ALL_POLICIES)
def test_identity_placement_bit_identical(sched):
    wants = schedulers.get(sched).wants_criticality_order
    cfg = OverlayConfig(scheduler=sched, max_cycles=500_000)
    ref = simulate(build_graph_memory(G, 4, 4, criticality_order=wants), cfg)
    r = simulate(G, cfg, nx=4, ny=4)
    assert _stats(r) == _stats(ref), sched
    np.testing.assert_array_equal(r.values, ref.values)


def test_explicit_array_matches_strategy_name():
    node_pe = place.resolve(G, 4, 4, "clustered")
    via_array = build_graph_memory(G, 4, 4, placement=node_pe)
    via_name = build_graph_memory(G, 4, 4, placement="clustered")
    for field in ("opcode", "fanin", "fo_base", "fo_count", "valid",
                  "e_dst_pe", "e_dst_slot", "e_dst_opidx",
                  "node_pe", "node_slot"):
        np.testing.assert_array_equal(getattr(via_array, field),
                                      getattr(via_name, field), err_msg=field)


def test_assign_slots_is_the_partition_layout():
    gm = build_graph_memory(G, 4, 4, criticality_order=True)
    node_slot, local_counts = place.assign_slots(
        gm.node_pe, criticality(G, "height"), 16)
    np.testing.assert_array_equal(node_slot, gm.node_slot)
    np.testing.assert_array_equal(local_counts, gm.local_counts)


def _oracle_pack(g, nx, ny, node_pe, crit, min_lmax, min_emax):
    """The per-node loop ``build_graph_memory`` used to pack with, kept as
    the oracle of its array code: ``{field: array}`` of a GraphMemory."""
    num_pes = nx * ny
    node_slot, local_counts = place.assign_slots(node_pe, crit, num_pes)
    counts = np.bincount(node_pe, minlength=num_pes)
    ecounts = np.bincount(node_pe, weights=g.fanout_count(),
                          minlength=num_pes).astype(np.int64)
    words = max(1, -(-max(int(counts.max()), min_lmax) // 32))
    lmax = words * 32
    emax = max(1, int(ecounts.max()), min_emax)

    def per_node(arr, dtype):
        out = np.zeros((num_pes, lmax), dtype=dtype)
        out[node_pe, node_slot] = arr
        return out

    fo_base = np.zeros((num_pes, lmax), dtype=np.int32)
    e_dst_pe = np.zeros((num_pes, emax), dtype=np.int32)
    e_dst_slot = np.zeros((num_pes, emax), dtype=np.int32)
    e_dst_opidx = np.zeros((num_pes, emax), dtype=np.int8)
    cursor = np.zeros(num_pes, dtype=np.int64)
    ptr, dst, slt = g.fanout_ptr, g.fanout_dst, g.fanout_slot
    for v in np.lexsort((node_slot, node_pe)):
        p = node_pe[v]
        lo, hi = ptr[v], ptr[v + 1]
        k = hi - lo
        base = cursor[p]
        fo_base[p, node_slot[v]] = base
        if k:
            d = dst[lo:hi]
            e_dst_pe[p, base:base + k] = node_pe[d]
            e_dst_slot[p, base:base + k] = node_slot[d]
            e_dst_opidx[p, base:base + k] = slt[lo:hi]
            cursor[p] = base + k
    return dict(
        nx=nx, ny=ny, lmax=lmax, emax=emax, words=words,
        opcode=per_node(g.opcode, np.int8),
        fanin=per_node(g.fanin_count(), np.int8),
        init_value=per_node(g.initial_values, np.float32),
        fo_base=fo_base, fo_count=per_node(g.fanout_count(), np.int32),
        valid=per_node(True, bool),
        e_dst_pe=e_dst_pe, e_dst_slot=e_dst_slot, e_dst_opidx=e_dst_opidx,
        node_pe=node_pe.astype(np.int32), node_slot=node_slot,
        local_counts=local_counts)


#: explicit placement: every node on one of five PEs, eleven PEs left empty
EXPLICIT = np.random.default_rng(4).integers(0, 5, G.num_nodes).astype(np.int32)

PACK_VARIANTS = {
    "plain": {},
    "padded": {"min_lmax": 90, "min_emax": 400},
    "id_order": {"criticality_order": False},
    "id_order_padded": {"criticality_order": False, "min_lmax": 33,
                        "min_emax": 1},
}


@pytest.mark.parametrize("variant", sorted(PACK_VARIANTS))
@pytest.mark.parametrize("placement",
                         ["round_robin", "random", "clustered", "explicit"])
def test_pack_bit_identical_to_node_loop(placement, variant):
    kw = PACK_VARIANTS[variant]
    spec = EXPLICIT if placement == "explicit" else placement
    gm = build_graph_memory(G, 4, 4, placement=spec, seed=3, **kw)
    node_pe = (EXPLICIT if placement == "explicit"
               else place_nodes(G, 16, placement, 3))
    crit = (criticality(G, "height") if kw.get("criticality_order", True)
            else -np.arange(G.num_nodes, dtype=np.int64))
    want = _oracle_pack(G, 4, 4, node_pe, crit, kw.get("min_lmax", 0),
                        kw.get("min_emax", 0))
    for field, value in want.items():
        got = getattr(gm, field)
        if isinstance(value, np.ndarray):
            assert got.dtype == value.dtype, field
        np.testing.assert_array_equal(got, value, err_msg=field)


def test_bad_placements_rejected():
    with pytest.raises(ValueError, match="unknown placement strategy"):
        place.PlacementSpec(strategy="teleport")
    with pytest.raises(ValueError, match="outside the"):
        build_graph_memory(G, 2, 2, placement=np.full(G.num_nodes, 99))
    with pytest.raises(ValueError, match="node->PE"):
        build_graph_memory(G, 2, 2, placement=np.zeros(3, np.int32))
    with pytest.raises(TypeError):
        OverlayConfig(placement=3.14)


def test_simulate_batch_requires_uniform_placement():
    cfgs = [OverlayConfig(), OverlayConfig(placement="clustered")]
    with pytest.raises(ValueError, match="uniform placement"):
        simulate_batch(G, cfgs, nx=4, ny=4)


# ---------------------------------------------------------------------------
# Cost model: vmapped batch == per-candidate scoring; torus is one-way.
# ---------------------------------------------------------------------------

def test_torus_hops_unidirectional():
    nx = ny = 4
    # PE 0 -> its east neighbour (pe = x*ny + y, so +ny is one X hop).
    assert int(place.torus_hops(0, ny, nx, ny)) == 1
    # ... and back the "short way" must wrap the whole ring.
    assert int(place.torus_hops(ny, 0, nx, ny)) == nx - 1
    assert int(place.torus_hops(5, 5, nx, ny)) == 0


def test_batch_cost_matches_single():
    model = place.build_cost_model(G, 4, 4)
    rng = np.random.default_rng(0)
    cands = rng.integers(0, 16, size=(5, G.num_nodes)).astype(np.int32)
    batch = np.asarray(model.batch_cost(cands))
    solo = np.asarray([int(model.cost(c)) for c in cands])
    np.testing.assert_array_equal(batch, solo)
    assert batch.dtype == np.int64


def test_cost_prefers_local_edges():
    model = place.build_cost_model(G, 4, 4)
    all_one_pe = np.zeros(G.num_nodes, np.int32)       # zero traffic, max pile
    spread = place.resolve(G, 4, 4, "round_robin")
    assert int(model.traffic(all_one_pe)) == 0
    assert int(model.pressure(spread)) < int(model.pressure(all_one_pe))


# ---------------------------------------------------------------------------
# Annealer: deterministic, never worse than its init, beats random on cycles.
# ---------------------------------------------------------------------------

def test_anneal_deterministic_under_fixed_key():
    r1 = place.anneal_placement(G, 4, 4, ACFG)
    r2 = place.anneal_placement(G, 4, 4, ACFG)
    np.testing.assert_array_equal(r1.node_pe, r2.node_pe)
    assert r1.cost == r2.cost and r1.init_cost == r2.init_cost


def test_anneal_seeds_decorrelate():
    r1 = place.anneal_placement(G, 4, 4, ACFG)
    r2 = place.anneal_placement(
        G, 4, 4, place.AnnealConfig(replicas=ACFG.replicas, rounds=ACFG.rounds,
                                    steps=ACFG.steps, seed=7))
    assert (r1.node_pe != r2.node_pe).any()


def test_anneal_cost_never_worse_than_init():
    res = place.anneal_placement(G, 4, 4, ACFG)
    assert res.cost <= res.init_cost
    model = place.build_cost_model(G, 4, 4)
    assert int(model.cost(res.node_pe)) == res.cost  # reported == rescored


@pytest.mark.parametrize("blocks,bs,border,grid", [
    (3, 6, 4, (4, 4)),
    (2, 8, 6, (8, 8)),
])
def test_annealed_never_increases_cycles_vs_random(blocks, bs, border, grid):
    g = wl.arrow_lu_graph(blocks, bs, border, seed=3)
    nx, ny = grid
    ann = place.anneal_placement(g, nx, ny, ACFG)
    res = place.evaluate_placements(g, nx, ny, {
        "random": place.PlacementSpec(strategy="random", seed=ACFG.seed),
        "annealed": ann.node_pe,
    }, cfgs=OverlayConfig(max_cycles=500_000))
    assert res["random"].done and res["annealed"].done
    assert res["annealed"].cycles <= res["random"].cycles


def test_evaluate_placements_honors_spec_metric():
    # Slot ordering must follow each spec's own criticality metric (the
    # uniform-shape packing path must not silently fall back to "height").
    spec = place.PlacementSpec(strategy="clustered", metric="neg_slack")
    cfg = OverlayConfig(max_cycles=500_000)
    res = place.evaluate_placements(G, 4, 4, {"s": spec}, cfgs=cfg)["s"]
    ref = simulate(place.graph_memory(G, 4, 4, spec), cfg)
    assert _stats(res) == _stats(ref)
    np.testing.assert_array_equal(res.values, ref.values)


def test_evaluate_placements_sharded_matches_single_device():
    import jax
    from jax.sharding import Mesh

    mesh = Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
    cfgs = [OverlayConfig(max_cycles=500_000),
            OverlayConfig(select_latency=2, max_cycles=500_000)]
    pls = {"identity": None, "clustered": "clustered"}
    # Mixed layout preferences in one sweep would silently skew non-first
    # schedulers (one packed memory per placement) — must be rejected.
    with pytest.raises(ValueError, match="wants_criticality_order"):
        place.evaluate_placements(
            G, 4, 4, pls,
            cfgs=cfgs + [OverlayConfig(scheduler="inorder",
                                       max_cycles=500_000)])
    solo = place.evaluate_placements(G, 4, 4, pls, cfgs=cfgs)
    shard = place.evaluate_placements(G, 4, 4, pls, cfgs=cfgs, mesh=mesh)
    for name in pls:
        for a, b in zip(solo[name], shard[name]):
            assert _stats(a) == _stats(b), name
            np.testing.assert_array_equal(a.values, b.values)


def test_spec_threading_through_overlay_config():
    spec = place.PlacementSpec(strategy="anneal", anneal=ACFG)
    cfg = OverlayConfig(placement=spec, max_cycles=500_000)
    r = simulate(G, cfg, nx=4, ny=4)
    ref = simulate(
        build_graph_memory(G, 4, 4,
                           placement=place.anneal_placement(G, 4, 4, ACFG).node_pe),
        OverlayConfig(max_cycles=500_000))
    assert _stats(r) == _stats(ref)
    np.testing.assert_array_equal(r.values, ref.values)


# ---------------------------------------------------------------------------
# check_every autotune: keyed on backend + device count, not just size.
# ---------------------------------------------------------------------------

def test_check_every_keyed_on_backend_and_devices():
    cfg = OverlayConfig()
    # CPU, single device: the graph-size table (seed behavior, unchanged).
    assert resolve_check_every(cfg, 16, 16, 16, backend="cpu", num_devices=1) == 8
    assert resolve_check_every(cfg, 16, 16, 64, backend="cpu", num_devices=1) == 16
    assert resolve_check_every(cfg, 32, 32, 256, backend="cpu", num_devices=1) == 32
    # Multi-device mesh (e.g. the 8-fake-device CPU mesh): the chunk
    # amortizes cross-shard collectives, so depth wins at every size.
    for devices in (2, 8, 32):
        assert resolve_check_every(
            cfg, 16, 16, 16, backend="cpu", num_devices=devices) == 32
    # Single-device TPU: at least 16 even for small graphs.
    assert resolve_check_every(cfg, 16, 16, 16, backend="tpu", num_devices=1) == 16
    assert resolve_check_every(cfg, 32, 32, 256, backend="tpu", num_devices=1) == 32
    # Explicit check_every always wins.
    assert resolve_check_every(OverlayConfig(check_every=5), 16, 16, 16,
                               backend="tpu", num_devices=8) == 5
