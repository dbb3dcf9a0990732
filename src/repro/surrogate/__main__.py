"""Fast surrogate smoke: ``python -m repro.surrogate [--smoke]``.

Runs the whole subsystem end to end on a small fig1-family workload in well
under a minute and asserts its contracts:

  * fixed-key fit -> bit-identical coefficients across two fits (the
    determinism CI leans on);
  * in-sample rank quality: Spearman >= 0.8 between predictions and
    simulated cycles on the training set;
  * pruning: ``evaluate_placements(prune="surrogate", keep_top=k)`` returns
    exactly k simulated candidates, and the best of them is close to the
    exhaustive best;
  * multilevel placement: identity-coarsened anneal reproduces the plain
    annealer bit-exactly, and a coarse-annealed placement beats round-robin
    on simulated cycles;
  * guided annealing: the incremental delta features match a batch
    recompute bit-exactly, the open-gate (margin = inf) guided kernel
    reproduces the unguided annealer bit-for-bit, and a margin-0 gate
    filters proposals (cost_evals < proposals) deterministically.

CI runs this as a cheap gate next to the tier-1 tests.
"""
from __future__ import annotations

import sys


def main() -> int:
    import numpy as np

    from repro import place, surrogate
    from repro.core import workloads as wl
    from repro.core.overlay import OverlayConfig

    g = wl.arrow_lu_graph(2, 8, 6, seed=3)
    nx = ny = 8
    cfg = OverlayConfig(max_cycles=200_000)

    # 1. Determinism: same key, same data -> bit-identical coefficients.
    m1, placements, cycles = surrogate.fit_from_sim(
        g, nx, ny, cfg=cfg, n_train=24, seed=0)
    m2 = surrogate.fit(g, nx, ny, placements, cycles)
    np.testing.assert_array_equal(m1.beta, m2.beta)
    np.testing.assert_array_equal(m1.mu, m2.mu)

    # 2. In-sample rank quality.
    rho = surrogate.spearman(m1.predict_batch(placements), cycles)
    assert rho >= 0.8, f"in-sample spearman {rho:.3f} < 0.8"

    # 3. Pruned evaluation: k simulated candidates, near-exhaustive best.
    cands = surrogate.sample_placements(g, nx, ny, 16, seed=7)
    names = {f"cand{i}": p for i, p in enumerate(cands)}
    full = place.evaluate_placements(g, nx, ny, names, cfgs=cfg)
    pruned = place.evaluate_placements(
        g, nx, ny, names, cfgs=cfg, prune="surrogate", keep_top=4,
        surrogate=m1)
    assert len(pruned) == 4 and set(pruned) <= set(full)
    best_full = min(r.cycles for r in full.values())
    best_pruned = min(r.cycles for r in pruned.values())
    assert best_pruned <= 1.10 * best_full, (best_pruned, best_full)

    # 4. Multilevel: identity clusters == plain annealer, bit-exactly;
    #    coarse-annealed beats round-robin on simulated cycles.
    acfg = place.AnnealConfig(replicas=6, rounds=12, steps=256, seed=0)
    plain = place.anneal_placement(g, nx, ny, acfg)
    ident = place.multilevel_anneal(
        g, nx, ny, acfg, clusters=np.arange(g.num_nodes), refine=None)
    np.testing.assert_array_equal(ident.node_pe, plain.node_pe)
    ml = place.multilevel_anneal(
        g, nx, ny, place.AnnealConfig(replicas=8, rounds=16, steps=384, seed=0),
        ratio=8,
        refine=place.AnnealConfig(replicas=6, rounds=12, steps=512, seed=0))
    res = place.evaluate_placements(g, nx, ny, {
        "round_robin": "round_robin", "multilevel": ml.node_pe}, cfgs=cfg)
    rr, mlr = res["round_robin"], res["multilevel"]
    assert rr.done and mlr.done
    assert mlr.cycles < rr.cycles, (mlr.cycles, rr.cycles)

    # 5. Guided annealing: delta features == batch recompute bit-exactly
    #    after a random move sequence; open gate == unguided bit-exactly;
    #    a margin-0 gate actually filters, deterministically.
    import jax

    from repro.surrogate import delta as sd

    guide = sd.build_guide(m1)
    ga = sd.guide_arrays(guide)
    rng = np.random.default_rng(11)
    pe = rng.integers(0, nx * ny, size=g.num_nodes).astype(np.int32)
    with jax.enable_x64(True):
        st = sd.state_init(ga, pe, nx=nx, ny=ny)
        for _ in range(64):
            i = int(rng.integers(0, g.num_nodes))
            q = int(rng.integers(0, nx * ny))
            st, _ = sd.apply_move(ga, st, pe, i, np.int32(q), nx=nx, ny=ny)
            pe[i] = q
        np.testing.assert_array_equal(
            np.asarray(st.feats),
            m1.extractor.features_batch(pe)[0].astype(np.int64))
    open_gate = place.anneal_placement(g, nx, ny, acfg, guide=m1,
                                       guide_margin=float("inf"))
    np.testing.assert_array_equal(open_gate.node_pe, plain.node_pe)
    assert open_gate.cost_evals == open_gate.proposals
    g1 = place.anneal_placement(g, nx, ny, acfg, guide=m1, guide_margin=0.0)
    g2 = place.anneal_placement(g, nx, ny, acfg, guide=m1, guide_margin=0.0)
    np.testing.assert_array_equal(g1.node_pe, g2.node_pe)
    assert 0 < g1.cost_evals < g1.proposals
    assert g1.cost <= g1.init_cost

    print(f"surrogate smoke OK: spearman={rho:.3f}, "
          f"pruned best {best_pruned} vs exhaustive {best_full} "
          f"({len(pruned)}/{len(full)} sims), "
          f"multilevel {mlr.cycles} < round_robin {rr.cycles} cycles "
          f"({ml.num_clusters} clusters for {g.num_nodes} nodes), "
          f"guided gate pass-rate {g1.eval_ratio:.2f}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
