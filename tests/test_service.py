"""repro.service + repro.run: content-hash caching (cross-process stable,
zero-simulation hits counter-asserted), vmapped multi-query anneal parity,
Pareto frontier determinism, the mixed-graph shape-class fix, and the
``repro.run`` dispatcher's bit-parity with all four legacy entry points."""
import dataclasses
import subprocess
import sys
import warnings

import numpy as np
import pytest

import repro
from repro.core import workloads as wl
from repro.core.overlay import OverlayConfig
from repro.core.partition import build_graph_memory
from repro.place.spec import IDENTITY, PlacementSpec
from repro.service import (PlacementQuery, PlacementService, ResultCache,
                           explore, graph_digest, query_key)

G = wl.arrow_lu_graph(2, 6, 4, seed=1)
NX = NY = 4
CFG = OverlayConfig(placement="anneal", max_cycles=200_000)


def _q(graph=G, nx=NX, ny=NY, objective="cycles", budget=2048, cfg=CFG):
    return PlacementQuery(graph=graph, nx=nx, ny=ny, objective=objective,
                          budget=budget, cfg=cfg)


# ---------------------------------------------------------------------------
# Content hashing
# ---------------------------------------------------------------------------

KEY_SCRIPT = r"""
import sys; sys.path.insert(0, "src")
from repro.core import workloads as wl
from repro.core.overlay import OverlayConfig
from repro.service import query_key
g = wl.arrow_lu_graph(2, 6, 4, seed=1)
cfg = OverlayConfig(placement="anneal", max_cycles=200_000)
print(query_key(g, 4, 4, cfg, "cycles"))
"""


def test_query_key_stable_across_processes():
    # No Python hash() anywhere in the pipeline: a fresh interpreter (own
    # PYTHONHASHSEED) must derive the identical int64 key.
    local = query_key(G, NX, NY, CFG, "cycles")
    out = subprocess.run([sys.executable, "-c", KEY_SCRIPT],
                         capture_output=True, text=True, check=True)
    assert int(out.stdout.strip()) == local
    assert isinstance(local, int) and np.int64(local) == local


def test_query_key_discriminates():
    base = query_key(G, NX, NY, CFG, "cycles")
    perturbed = dataclasses.replace(
        G, initial_values=G.initial_values + np.float32(1))
    assert query_key(perturbed, NX, NY, CFG, "cycles") != base
    assert query_key(G, NX, 8, CFG, "cycles") != base
    assert query_key(G, NX, NY, CFG, "cost") != base
    assert query_key(
        G, NX, NY, dataclasses.replace(CFG, scheduler="inorder"),
        "cycles") != base
    assert query_key(
        G, NX, NY, dataclasses.replace(CFG, placement="identity"),
        "cycles") != base
    assert graph_digest(perturbed) != graph_digest(G)


def test_query_key_ignores_execution_only_knobs():
    # engine and check_every change HOW the engine runs, never the bits it
    # produces — configs differing only there must share one cache entry.
    base = query_key(G, NX, NY, CFG, "cycles")
    for variant in (dataclasses.replace(CFG, engine="select"),
                    dataclasses.replace(CFG, check_every=1)):
        assert query_key(G, NX, NY, variant, "cycles") == base


# ---------------------------------------------------------------------------
# The cache contract: hits are free and bit-exact
# ---------------------------------------------------------------------------

def test_cache_hit_zero_simulations_bit_exact():
    svc = PlacementService()
    first = svc.query(_q())
    assert not first.cached and first.cycles is not None
    sims = svc.counters["simulations"]
    second = svc.query(_q())
    assert second.cached
    assert svc.counters["simulations"] == sims, "cache hit ran a simulation"
    assert second.cycles == first.cycles
    assert second.stats == first.stats
    np.testing.assert_array_equal(second.node_pe, first.node_pe)
    rep = svc.report()
    assert rep["cache_hits"] == 1 and rep["cache_misses"] == 1


def test_within_batch_duplicates_resolved_once():
    svc = PlacementService()
    a, b = svc.run_batch([_q(), _q()])
    assert a.key == b.key
    assert svc.counters["simulations"] == 1
    assert a.cycles == b.cycles
    np.testing.assert_array_equal(a.node_pe, b.node_pe)


def test_cost_objective_runs_zero_simulations():
    svc = PlacementService()
    r = svc.query(_q(objective="cost"))
    assert svc.counters["simulations"] == 0
    assert r.cycles is None and isinstance(r.cost, int)


def test_cache_disk_persistence(tmp_path):
    d = str(tmp_path / "svc")
    a = PlacementService(cache_dir=d).query(_q())
    svc2 = PlacementService(cache_dir=d)
    b = svc2.query(_q())
    assert b.cached and svc2.counters["simulations"] == 0
    assert svc2.cache.disk_hits == 1
    assert b.cycles == a.cycles and b.stats == a.stats
    np.testing.assert_array_equal(b.node_pe, a.node_pe)


def test_cache_lru_eviction_counted():
    cache = ResultCache(capacity=2)
    svc = PlacementService(cache=cache)
    for b in (2, 3, 4):
        svc.query(_q(graph=wl.arrow_lu_graph(b, 6, 4, seed=1)))
    assert cache.evictions == 1
    # evicted first entry misses again
    r = svc.query(_q(graph=wl.arrow_lu_graph(2, 6, 4, seed=1)))
    assert not r.cached


# ---------------------------------------------------------------------------
# Batched anneal fan-out == solo, row for row
# ---------------------------------------------------------------------------

def test_batched_anneal_rows_match_solo_queries():
    seeds = (0, 1, 2)

    def mk(s):
        return _q(cfg=OverlayConfig(
            placement=PlacementSpec(strategy="anneal", seed=s),
            max_cycles=200_000))

    svc = PlacementService()
    batched = svc.run_batch([mk(s) for s in seeds])
    assert svc.counters["batched_anneals"] == len(seeds)
    for s, b in zip(seeds, batched):
        solo = PlacementService().query(mk(s))
        np.testing.assert_array_equal(b.node_pe, solo.node_pe), s
        assert b.cycles == solo.cycles, s
        assert b.stats == solo.stats, s


# ---------------------------------------------------------------------------
# Explorer
# ---------------------------------------------------------------------------

def test_pareto_frontier_deterministic_and_nondominated():
    space = {"grid": ((2, 2), (4, 4)), "placement": ("identity", "anneal")}
    rec1 = explore(G, space=space, budget=2048, max_cycles=200_000)
    rec2 = explore(G, space=space, budget=2048, max_cycles=200_000)
    assert rec1["frontier"] == rec2["frontier"]
    assert rec1["points"] == rec2["points"]
    front = rec1["frontier"]
    assert front, "empty frontier"
    for p in front:
        assert not any(q["cycles"] <= p["cycles"]
                       and q["num_pes"] <= p["num_pes"] and q is not p
                       and (q["cycles"] < p["cycles"]
                            or q["num_pes"] < p["num_pes"])
                       for q in rec1["points"]), p["name"]


def test_explore_shares_service_cache():
    svc = PlacementService()
    space = {"scheduler": ("ooo",), "eject_policy": ("n_first",),
             "grid": ((2, 2),), "placement": ("identity",)}
    explore(G, space=space, service=svc)
    rec = explore(G, space=space, service=svc)
    assert all(p["cached"] for p in rec["points"])
    assert svc.counters["simulations"] == 1


# ---------------------------------------------------------------------------
# Mixed-graph shape classes: one jit entry per padded shape class
# ---------------------------------------------------------------------------

def test_mixed_graph_batch_compiles_once():
    from repro import place
    from repro.core.overlay import _run_batch_jit

    cfg = OverlayConfig(max_cycles=500_000)
    g_small = wl.arrow_lu_graph(2, 6, 4, seed=1)
    g_big = wl.arrow_lu_graph(3, 6, 4, seed=2)
    pes = [(g, place.resolve(g, NX, NY, "identity"))
           for g in (g_small, g_big)]
    lmax, emax = place.shape_class(pes, NX, NY)
    before = _run_batch_jit._cache_size()
    results = {}
    for g, pe in pes:
        res = place.evaluate_placements(
            g, NX, NY, {"identity": pe}, cfgs=cfg,
            min_lmax=lmax, min_emax=emax)
        results[g.num_nodes] = res["identity"].cycles
    assert _run_batch_jit._cache_size() - before <= 1, (
        "mixed-size graphs retraced the batched engine")
    # padding to the joint class must not change the answers
    for g, pe in pes:
        ref = place.evaluate_placements(g, NX, NY, {"identity": pe},
                                        cfgs=cfg)
        assert ref["identity"].cycles == results[g.num_nodes]


def test_service_stream_hit_rate():
    stream = wl.service_stream(n_queries=32, distinct=8, seed=0)
    names = [n for n, _ in stream]
    assert len(stream) == 32 and len(set(names)) == 8
    # every distinct graph appears, and >= 50% of the stream is repeats
    assert (len(stream) - len(set(names))) / len(stream) >= 0.5
    # deterministic replay
    again = wl.service_stream(n_queries=32, distinct=8, seed=0)
    assert names == [n for n, _ in again]
    for (_, a), (_, b) in zip(stream, again):
        np.testing.assert_array_equal(a.opcode, b.opcode)


# ---------------------------------------------------------------------------
# repro.run: one front door, four legacy spellings
# ---------------------------------------------------------------------------

POLICIES = ("ooo", "inorder")


def _mesh11():
    import jax

    return jax.make_mesh((1, 1), ("data", "model"))


def _stats(r):
    return (int(r.cycles), bool(r.done), int(r.delivered),
            int(r.deflections), int(r.busy_cycles))


@pytest.mark.parametrize("sched", POLICIES)
def test_run_matches_all_legacy_entry_points(sched):
    from repro.core import distributed, overlay

    cfg = OverlayConfig(scheduler=sched, max_cycles=200_000)
    gm = build_graph_memory(G, 2, 2, criticality_order=True)
    ref = repro.run(gm, cfg)
    with warnings.catch_warnings():
        warnings.simplefilter("error")  # wrappers must warn; run must not
        repro.run(gm, cfg)
        repro.run(gm, batch=[cfg])
    with pytest.deprecated_call():
        legacy = overlay.simulate(gm, cfg)
    assert _stats(legacy) == _stats(ref)
    with pytest.deprecated_call():
        legacy_b = overlay.simulate_batch(gm, [cfg])[0]
    assert _stats(legacy_b) == _stats(repro.run(gm, batch=[cfg])[0])
    np.testing.assert_array_equal(legacy.values, ref.values)

    mesh = _mesh11()
    run_sh = repro.run(gm, cfg, mesh=mesh)
    with pytest.deprecated_call():
        legacy_sh = distributed.simulate_sharded(gm, mesh, cfg)
    assert _stats(legacy_sh) == _stats(run_sh) == _stats(ref)
    run_bsh = repro.run(gm, batch=[cfg], mesh=mesh)[0]
    with pytest.deprecated_call():
        legacy_bsh = distributed.simulate_batch_sharded(gm, mesh, [cfg])[0]
    assert _stats(legacy_bsh) == _stats(run_bsh) == _stats(ref)


def test_run_accepts_raw_graph_with_grid():
    cfg = OverlayConfig(max_cycles=200_000)
    r = repro.run(G, cfg, nx=2, ny=2)
    gm = build_graph_memory(G, 2, 2, criticality_order=True)
    assert _stats(repro.run(gm, cfg)) == _stats(r)


def test_run_rejects_cfg_and_batch():
    gm = build_graph_memory(G, 2, 2)
    with pytest.raises(ValueError, match="either"):
        repro.run(gm, OverlayConfig(), batch=[OverlayConfig()])


# ---------------------------------------------------------------------------
# Uniform placement resolution (the use_pallas shim is gone; resolve() is
# the single normalization point)
# ---------------------------------------------------------------------------

def test_config_placement_normalized():
    from repro.place.spec import resolve

    assert OverlayConfig().placement is IDENTITY
    spec = OverlayConfig(placement="anneal").placement
    assert isinstance(spec, PlacementSpec) and spec.strategy == "anneal"
    explicit = PlacementSpec(strategy="anneal", seed=7)
    assert OverlayConfig(placement=explicit).placement is explicit
    assert resolve(None) is IDENTITY
    with pytest.raises(TypeError):
        resolve(42)
    with pytest.raises(TypeError):
        OverlayConfig(use_pallas=True)
