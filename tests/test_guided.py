"""Surrogate-guided annealing (repro.surrogate.delta + place.anneal):
incremental move features are bit-exact against batch recompute, the
open-gate guided kernel reproduces the unguided annealer bit-for-bit, guided
searches are deterministic with exact cost-evaluation counters, the quotient
guide's coarse-level features equal the fine features of the projected
placement, and the guide knobs thread through PlacementSpec/resolve."""
import dataclasses

import jax
import numpy as np
import pytest

from repro import place, surrogate
from repro.core import workloads as wl
from repro.core.overlay import OverlayConfig
from repro.surrogate import delta as sd

G = wl.arrow_lu_graph(2, 6, 4, seed=3)
NX, NY = 4, 5                      # non-square: catches x/y coordinate swaps
ACFG = place.AnnealConfig(replicas=6, rounds=8, steps=128, seed=0)
CFG = OverlayConfig(max_cycles=200_000)


@pytest.fixture(scope="module")
def model():
    m, _, _ = surrogate.fit_from_sim(G, NX, NY, cfg=CFG, n_train=12, seed=0)
    return m


# ---------------------------------------------------------------------------
# Incremental features (surrogate.delta).
# ---------------------------------------------------------------------------

def test_delta_features_match_batch_recompute_bit_exactly(model):
    guide = sd.build_guide(model)
    ga = sd.guide_arrays(guide)
    ex = guide.extractor
    rng = np.random.default_rng(7)
    pe = rng.integers(0, NX * NY, size=G.num_nodes).astype(np.int32)
    with jax.enable_x64(True):
        st = sd.state_init(ga, pe, nx=NX, ny=NY)
        np.testing.assert_array_equal(
            np.asarray(st.feats), ex.features_batch(pe)[0].astype(np.int64))
        for k in range(150):
            i = int(rng.integers(0, G.num_nodes))
            q = int(rng.integers(0, NX * NY))
            st, dscore = sd.apply_move(ga, st, pe, i, np.int32(q),
                                       nx=NX, ny=NY)
            pe = pe.copy()
            pe[i] = q
            if k % 50 == 49:   # carried state never drifts from recompute
                np.testing.assert_array_equal(
                    np.asarray(st.feats),
                    ex.features_batch(pe)[0].astype(np.int64))


def test_delta_score_is_quantized_prediction_delta(model):
    guide = sd.build_guide(model)
    ga = sd.guide_arrays(guide)
    rng = np.random.default_rng(3)
    pe = rng.integers(0, NX * NY, size=G.num_nodes).astype(np.int32)
    pe2 = pe.copy()
    pe2[11] = (pe[11] + 3) % (NX * NY)
    with jax.enable_x64(True):
        st = sd.state_init(ga, pe, nx=NX, ny=NY)
        _, dscore = sd.apply_move(ga, st, pe, 11, np.int32(pe2[11]),
                                  nx=NX, ny=NY)
    f1 = model.extractor.features_batch(pe)[0].astype(np.int64)
    f2 = model.extractor.features_batch(pe2)[0].astype(np.int64)
    assert int(dscore) == int(np.sum(guide.gamma_q * (f2 - f1)))
    # ... and it tracks the float model's predicted delta within the exact
    # quantization bound: each coefficient is off by <= 0.5/GUIDE_SCALE.
    pred = model.predict_batch(np.stack([pe, pe2]))
    bound = 0.5 * np.abs(f2 - f1).sum() / sd.GUIDE_SCALE + 1e-9
    assert int(dscore) / sd.GUIDE_SCALE == pytest.approx(
        pred[1] - pred[0], abs=bound)


def test_quotient_guide_features_equal_projected_fine(model):
    guide = sd.build_guide(model)
    clusters = place.cluster_nodes(G, 8)
    cguide = guide.coarsen(clusters)
    c = int(clusters.max()) + 1
    rng = np.random.default_rng(5)
    cpe = rng.integers(0, NX * NY, size=(4, c)).astype(np.int32)
    np.testing.assert_array_equal(
        cguide.extractor.features_batch(cpe),
        guide.extractor.features_batch(cpe[:, clusters]))
    np.testing.assert_array_equal(cguide.gamma_q, guide.gamma_q)


def test_quantize_margin():
    assert sd.quantize_margin(0.0) == 0
    assert sd.quantize_margin(1.0) == sd.GUIDE_SCALE
    assert sd.quantize_margin(float("inf")) == np.iinfo(np.int64).max
    assert sd.quantize_margin(float("-inf")) == np.iinfo(np.int64).min


# ---------------------------------------------------------------------------
# Guided annealer.
# ---------------------------------------------------------------------------

def test_open_gate_reproduces_unguided_bit_exactly(model):
    plain = place.anneal_placement(G, NX, NY, ACFG)
    guided = place.anneal_placement(G, NX, NY, ACFG, guide=model,
                                    guide_margin=float("inf"))
    np.testing.assert_array_equal(plain.node_pe, guided.node_pe)
    assert plain.cost == guided.cost
    np.testing.assert_array_equal(plain.replica_costs, guided.replica_costs)
    # With the gate wide open every proposal reaches the cost rule.
    assert guided.cost_evals == guided.proposals
    assert guided.proposals == ACFG.replicas * ACFG.rounds * ACFG.steps


def test_guided_deterministic_with_exact_counters(model):
    a = place.anneal_placement(G, NX, NY, ACFG, guide=model, guide_margin=0.0)
    b = place.anneal_placement(G, NX, NY, ACFG, guide=model, guide_margin=0.0)
    np.testing.assert_array_equal(a.node_pe, b.node_pe)
    assert (a.cost, a.cost_evals) == (b.cost, b.cost_evals)
    assert isinstance(a, place.GuidedPlacementResult)
    assert 0 < a.cost_evals < a.proposals   # the gate actually filters
    assert a.eval_ratio == a.cost_evals / a.proposals
    assert a.cost <= a.init_cost            # best-so-far includes the init


def test_guide_every_skips_gate_on_off_steps(model):
    every = place.anneal_placement(G, NX, NY, ACFG, guide=model,
                                   guide_margin=0.0, guide_every=1)
    sparse = place.anneal_placement(G, NX, NY, ACFG, guide=model,
                                    guide_margin=0.0, guide_every=4)
    # Ungated proposals always reach the cost rule, so gating every 4th
    # proposal evaluates strictly more than gating every proposal.
    assert sparse.cost_evals > every.cost_evals
    assert sparse.cost_evals >= (3 * sparse.proposals) // 4


def test_guide_graph_grid_mismatch_raises(model):
    other = wl.arrow_lu_graph(2, 5, 3, seed=1)
    with pytest.raises(ValueError, match="guide was built"):
        place.anneal_placement(other, NX, NY, ACFG, guide=model)
    with pytest.raises(ValueError, match="guide was built"):
        place.anneal_placement(G, NY, NX, ACFG, guide=model)
    with pytest.raises(ValueError, match="guide_every"):
        place.anneal_placement(G, NX, NY, ACFG, guide=model, guide_every=0)


def test_multilevel_guided_identity_open_gate_matches_plain(model):
    plain = place.anneal_placement(G, NX, NY, ACFG)
    ml = place.multilevel_anneal(
        G, NX, NY, ACFG, clusters=np.arange(G.num_nodes), refine=None,
        guide=model, guide_margin=float("inf"))
    np.testing.assert_array_equal(ml.node_pe, plain.node_pe)
    assert ml.coarse.cost == plain.cost


def test_multilevel_guided_runs_and_is_deterministic(model):
    a = place.multilevel_anneal(G, NX, NY, ACFG, ratio=8, guide=model,
                                guide_margin=0.0)
    b = place.multilevel_anneal(G, NX, NY, ACFG, ratio=8, guide=model,
                                guide_margin=0.0)
    np.testing.assert_array_equal(a.node_pe, b.node_pe)
    assert isinstance(a.coarse, place.GuidedPlacementResult)
    assert isinstance(a.refined, place.GuidedPlacementResult)
    assert a.coarse.cost_evals < a.coarse.proposals


def test_int64_thresholds_survive_ambient_x32():
    # Two acceptance thresholds both far above any possible move delta on
    # this graph must behave identically — they would wrap to different
    # int32 values if the threshold array were converted outside scoped x64.
    big = dataclasses.replace(ACFG, replicas=2, rounds=2, steps=64,
                              t_max=3e9)
    huge = dataclasses.replace(big, t_max=1e12)
    a = place.anneal_placement(G, NX, NY, big)
    b = place.anneal_placement(G, NX, NY, huge)
    np.testing.assert_array_equal(a.node_pe, b.node_pe)
    assert a.cost == b.cost


# ---------------------------------------------------------------------------
# Spec threading.
# ---------------------------------------------------------------------------

def test_spec_validation():
    with pytest.raises(ValueError, match="guide"):
        place.PlacementSpec(strategy="anneal", guide="bogus")
    with pytest.raises(ValueError, match="guide_every"):
        place.PlacementSpec(guide_every=0)
    with pytest.raises(ValueError, match="guide_train"):
        place.PlacementSpec(guide_train=1)
    # A guide on a non-search strategy would be silently ignored — reject.
    with pytest.raises(ValueError, match="search strategy"):
        place.PlacementSpec(guide="surrogate")
    with pytest.raises(ValueError, match="search strategy"):
        place.PlacementSpec(strategy="random", guide="surrogate")
    place.PlacementSpec(strategy="multilevel", guide="surrogate")  # fine


def test_resolve_guided_spec_deterministic_and_uses_prefit(model):
    spec = place.PlacementSpec(strategy="anneal", guide="surrogate",
                               anneal=ACFG, guide_margin=0.0, guide_train=8)
    via_prefit = place.resolve(G, NX, NY, spec, guide_model=model)
    direct = place.anneal_placement(G, NX, NY, ACFG, guide=model,
                                    guide_margin=0.0)
    np.testing.assert_array_equal(via_prefit, direct.node_pe)
    # Auto-fit path: deterministic end to end (fit seeds from spec.seed).
    a = place.resolve(G, NX, NY, spec)
    b = place.resolve(G, NX, NY, spec)
    np.testing.assert_array_equal(a, b)
