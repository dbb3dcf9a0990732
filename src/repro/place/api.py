"""Placement resolution + overlay integration entry points.

This is the layer the simulator and the benchmarks talk to:

  * :func:`resolve` turns a :class:`~repro.place.spec.PlacementSpec` (or a
    strategy name, or ``None`` = identity) into a concrete ``[N]`` node -> PE
    vector;
  * :func:`graph_memory` / :func:`graph_memory_for_config` pack a placed
    graph into the :class:`~repro.core.partition.GraphMemory` the engines
    consume (criticality-sorted slots via :mod:`repro.place.slots`);
  * :func:`evaluate_placements` scores candidate placements by *simulated
    cycle count* — single device or sharded over a mesh, batching the config
    axis through ``simulate_batch`` / ``simulate_batch_sharded``;
  * :func:`config_hillclimb` is the greedy coordinate-descent search over
    (placement x scheduler x select latency x eject capacity) that
    ``benchmarks/hillclimb.py --overlay`` fronts.

Heavyweight imports (overlay, distributed) are deferred into the functions:
``core.overlay`` itself imports this package for placement threading, so the
module level must stay cycle-free.
"""
from __future__ import annotations

import time

import jax
import numpy as np

from .. import SPAN_PLACE
from ..core.graph import DataflowGraph
from .anneal import anneal_placement
from .spec import PlacementSpec
from .spec import resolve as resolve_spec


def resolve(g: DataflowGraph, nx: int, ny: int, placement=None, *,
            guide_model=None) -> np.ndarray:
    """[N] node -> PE vector for ``placement`` on the ``nx x ny`` grid.

    ``placement`` is a PlacementSpec, a strategy name, an explicit [N] array
    (returned as-is), or ``None`` (identity = the partitioner's default
    round-robin — the layout all committed benchmark numbers use).

    A spec with ``guide="surrogate"`` runs the search with the two-stage
    surrogate accept; ``guide_model`` supplies a prefitted
    :class:`~repro.surrogate.model.SurrogateModel` for it (must match this
    graph and grid), otherwise one is fitted on the spot from
    ``spec.guide_train`` self-generated simulated placements.
    """
    if isinstance(placement, np.ndarray):
        return placement.astype(np.int32)
    from ..core import partition

    spec = resolve_spec(placement)
    num_pes = nx * ny
    guide = None
    if spec.guide == "surrogate":  # spec validation pins strategy to a search
        guide = guide_model
        if guide is None:
            from .. import surrogate as sg

            guide, _, _ = sg.fit_from_sim(
                g, nx, ny, n_train=spec.guide_train, seed=spec.seed,
                metric=spec.metric,
                crit_scale=spec.anneal_config.crit_scale)
    if spec.strategy == "anneal":
        init = None  # anneal_placement defaults to random-from-seed
        if spec.init != "random":
            init = resolve(g, nx, ny, PlacementSpec(strategy=spec.init,
                                                    seed=spec.seed))
        return anneal_placement(
            g, nx, ny, spec.anneal_config, metric=spec.metric,
            init=init, guide=guide, guide_every=spec.guide_every,
            guide_margin=spec.guide_margin).node_pe
    if spec.strategy == "multilevel":
        from .coarsen import multilevel_anneal

        return multilevel_anneal(
            g, nx, ny, spec.anneal_config, ratio=spec.coarsen_ratio,
            refine=spec.refine if spec.refine is not None else "auto",
            metric=spec.metric, guide=guide, guide_every=spec.guide_every,
            guide_margin=spec.guide_margin).node_pe
    strategy = "round_robin" if spec.strategy == "identity" else spec.strategy
    return partition.place_nodes(g, num_pes, strategy, seed=spec.seed)


def graph_memory(g: DataflowGraph, nx: int, ny: int, placement=None, *,
                 criticality_order: bool = True, metric: str | None = None):
    """Resolve ``placement`` and pack the per-PE graph memory."""
    from ..core import partition

    spec = (resolve_spec(placement)
            if not isinstance(placement, np.ndarray) else None)
    with jax.profiler.TraceAnnotation(SPAN_PLACE):
        node_pe = resolve(g, nx, ny, placement)
    return partition.build_graph_memory(
        g, nx, ny, placement=node_pe,
        metric=metric or (spec.metric if spec else "height"),
        criticality_order=criticality_order)


def graph_memory_for_config(g: DataflowGraph, nx: int, ny: int, cfg):
    """GraphMemory for an :class:`~repro.core.overlay.OverlayConfig`:
    honors ``cfg.placement`` and the scheduler's preferred memory layout."""
    from ..core import schedulers

    wants = schedulers.get(cfg.scheduler).wants_criticality_order
    return graph_memory(g, nx, ny, cfg.placement, criticality_order=wants)


def uniform_graph_memories(g: DataflowGraph, nx: int, ny: int, node_pes,
                           *, criticality_order: bool = True,
                           metric: str = "height",
                           pad_lmax: bool = True,
                           min_lmax: int = 0, min_emax: int = 0) -> list:
    """Pack one GraphMemory per ``[N]`` node -> PE vector, all with identical
    array shapes.

    Slot depth (``lmax``) and per-PE edge capacity (``emax``) depend on the
    placement, so naively packed candidate memories differ in shape and every
    ``jax.jit``-ed engine call retraces — scoring k candidates used to
    compile k times. Padding every memory to the candidate-set maxima makes
    the shapes (and thus the jit cache key) identical, so the whole set runs
    through ONE compiled program.

    ``pad_lmax=False`` keeps each memory's own slot depth (only ``emax`` is
    unified) — needed when a scheduler *models* latency from the memory depth
    (the ``scan`` policy's word-count sweep), where padding would change
    cycle counts.

    ``metric`` is one criticality metric for the whole set or one per
    placement (slot ordering only — it never moves the unified shapes).

    ``min_lmax`` / ``min_emax`` raise the padding floor beyond this set's
    own maxima. This is how *different graphs* share one jit cache entry:
    the service batch executor computes the shape maxima across a whole
    query group (:func:`shape_class`) and packs every graph's memories to
    that shared class, so mixed-graph query batches compile once per shape
    class instead of once per graph. Padding never moves cycle counts
    (asserted in tests — empty slots/edges are inert).
    """
    from ..core.partition import build_graph_memory, packed_shape

    node_pes = [np.asarray(p, dtype=np.int32) for p in node_pes]
    metrics = ([metric] * len(node_pes) if isinstance(metric, str)
               else list(metric))
    if len(metrics) != len(node_pes):
        raise ValueError(
            f"need one metric or one per placement; got {len(metrics)} "
            f"for {len(node_pes)} placements")
    # Shapes come from the packer's own derivation (partition.packed_shape),
    # so the identical-shapes guarantee cannot drift from the packing rule.
    shapes = [packed_shape(g, pe, nx * ny) for pe in node_pes]
    lmax = max([l for l, _ in shapes] + [min_lmax, 1])
    emax = max([e for _, e in shapes] + [min_emax, 1])
    return [build_graph_memory(
        g, nx, ny, placement=pe, metric=m,
        criticality_order=criticality_order,
        min_lmax=lmax if pad_lmax else 0, min_emax=emax)
        for pe, m in zip(node_pes, metrics)]


def shape_class(graphs_and_pes, nx: int, ny: int) -> tuple[int, int]:
    """Shared ``(lmax, emax)`` padding floor for a mixed-graph query group.

    ``graphs_and_pes`` is an iterable of ``(DataflowGraph, [N] node_pe)``
    pairs. The returned maxima, fed to :func:`uniform_graph_memories` (or
    :func:`evaluate_placements`) as ``min_lmax`` / ``min_emax``, put every
    graph's packed memory in ONE padded shape class, so the batched engine's
    jit cache holds one entry for the whole group — the shape-churn fix for
    query batches that mix graphs.
    """
    from ..core.partition import packed_shape

    lmax, emax = 1, 1
    for g, pe in graphs_and_pes:
        l, e = packed_shape(g, np.asarray(pe, dtype=np.int32), nx * ny)
        lmax, emax = max(lmax, l), max(emax, e)
    return lmax, emax


def _latency_depends_on_words(cfg_list) -> bool:
    """True when any config's exposed select latency is a function of the
    RDY word count (e.g. the ``scan`` policy) — lmax padding would then be a
    *model* change, not just an engine one."""
    from ..core import schedulers

    return any(schedulers.get(c.scheduler).sel_lat(c, 1)
               != schedulers.get(c.scheduler).sel_lat(c, 2)
               for c in cfg_list)


def simulate_placements(g: DataflowGraph, nx: int, ny: int, node_pes, cfg=None,
                        *, mesh=None, criticality_order: bool = True,
                        metric: str = "height") -> list:
    """Simulated :class:`~repro.core.overlay.SimResult` per ``[N]`` vector.

    The candidate memories are shape-unified (:func:`uniform_graph_memories`)
    so the whole set executes through one compiled program — this is the bulk
    evaluation path the surrogate training set is generated with.
    """
    from ..core import distributed, overlay

    cfg = cfg or overlay.OverlayConfig()
    gms = uniform_graph_memories(
        g, nx, ny, node_pes, criticality_order=criticality_order,
        metric=metric, pad_lmax=not _latency_depends_on_words([cfg]))
    out = []
    for gm in gms:
        if mesh is None:
            out.append(overlay._simulate_batch(gm, [cfg])[0])
        else:
            out.append(distributed._simulate_batch_sharded(gm, mesh, [cfg])[0])
    return out


def evaluate_placements(g: DataflowGraph, nx: int, ny: int, placements,
                        cfgs=None, mesh=None, *, prune: str | None = None,
                        keep_top: int = 8, surrogate=None,
                        surrogate_train: int = 24,
                        min_lmax: int = 0, min_emax: int = 0) -> dict:
    """Score candidate placements by simulated cycle count.

    Args:
      placements: ``{name: spec | strategy | [N] array}``.
      cfgs: one OverlayConfig, a sequence of them (swept per placement via
        the batched engine), or None for the default config.
      mesh: optional ``jax.sharding.Mesh`` — evaluation then runs through
        ``simulate_sharded`` / ``simulate_batch_sharded`` with the PE grid
        tiled over the mesh (placement evaluation for overlays larger than
        one device).
      prune: ``"surrogate"`` ranks every candidate with the cheap
        cycle-prediction model from :mod:`repro.surrogate` and simulates only
        the ``keep_top`` best-predicted ones (the returned dict then contains
        just those names). ``surrogate`` supplies a fitted
        :class:`~repro.surrogate.model.SurrogateModel` (it must have been
        built for this graph and grid — a mismatch raises); ``None`` fits one
        on the spot from ``surrogate_train`` self-generated simulated
        placements (``repro.surrogate.fit_from_sim``). With a config *sweep*,
        the ranking (and any on-the-spot fit) follows ``cfg_list[0]`` only —
        one pruned candidate set serves every config, so a placement that
        excels only under a later config can be pruned away; prune per
        config in separate calls when that matters.
      min_lmax, min_emax: raise the candidate memories' padding floor so
        *separate* calls over different graphs land in one padded shape
        class and reuse one compiled program (see :func:`shape_class`).

    Returns:
      ``{name: SimResult}`` (or ``{name: [SimResult, ...]}`` with a config
      sweep). Candidate memories are shape-unified
      (:func:`uniform_graph_memories`) so the batched engine compiles once
      for the whole candidate set, not once per placement.
    """
    from ..core import distributed, overlay, schedulers

    single = cfgs is None or not isinstance(cfgs, (list, tuple))
    cfg_list = [cfgs or overlay.OverlayConfig()] if single else list(cfgs)
    wants_set = {schedulers.get(c.scheduler).wants_criticality_order
                 for c in cfg_list}
    if len(wants_set) != 1:
        # One packed memory per placement serves the whole sweep; mixed
        # layout preferences would silently skew non-first schedulers.
        raise ValueError(
            "evaluate_placements needs schedulers with a uniform "
            "wants_criticality_order per call; split the config sweep by "
            "memory layout")
    wants = wants_set.pop()

    names = list(placements)
    node_pes = [resolve(g, nx, ny, placements[k]) for k in names]
    # Slot ordering honors each spec's own criticality metric (explicit
    # arrays have no spec and take the default), exactly like graph_memory.
    metrics = [resolve_spec(placements[k]).metric
               if not isinstance(placements[k], np.ndarray) else "height"
               for k in names]

    if prune is not None:
        if prune != "surrogate":
            raise ValueError(f"unknown prune mode {prune!r}; "
                             f"known: 'surrogate'")
        from .. import surrogate as sg

        model = surrogate
        if model is None:
            # mesh rides along: an overlay that needs the sharded path for
            # candidate sims needs it for the training sims too.
            model, _, _ = sg.fit_from_sim(
                g, nx, ny, cfg=cfg_list[0], n_train=surrogate_train,
                mesh=mesh)
        keep = model.rank(np.stack(node_pes))[:max(1, keep_top)]
        names = [names[i] for i in keep]
        node_pes = [node_pes[i] for i in keep]
        metrics = [metrics[i] for i in keep]

    gms = uniform_graph_memories(
        g, nx, ny, node_pes, criticality_order=wants, metric=metrics,
        pad_lmax=not _latency_depends_on_words(cfg_list),
        min_lmax=min_lmax, min_emax=min_emax)
    # The memories are already placed, so cfg.placement is dead weight here —
    # but it is a jit *static* argument, and two sweeps differing only in the
    # spec they were resolved from would needlessly compile twice. Strip it
    # to the canonical identity so equal-shape candidate sets share one
    # compiled program no matter which placement specs produced them.
    import dataclasses as _dc
    cfg_list = [_dc.replace(c, placement=None) for c in cfg_list]
    out = {}
    for name, gm in zip(names, gms):
        if mesh is None:
            res = overlay._simulate_batch(gm, cfg_list)
        else:
            res = distributed._simulate_batch_sharded(gm, mesh, cfg_list)
        out[name] = res[0] if single else res
    return out


# ---------------------------------------------------------------------------
# Greedy coordinate-descent over the overlay config space (incl. placement).
# ---------------------------------------------------------------------------

#: Axes of the overlay-config search space; ``scheduler`` is filled from the
#: policy registry at call time.
HILLCLIMB_SPACE = {
    "placement": ["round_robin", "clustered", "bulk_clustered",
                  "critical_chain", "anneal"],
    "scheduler": None,
    "select_latency": [None, 1, 2, 4],
    "eject_capacity": [1, 2],
}


def config_hillclimb(g: DataflowGraph, nx: int, ny: int, *,
                     max_cycles: int = 4_000_000, seed: int = 0,
                     space: dict | None = None) -> dict:
    """Greedy coordinate descent, one batched program per neighborhood group.

    Each step proposes every single-axis change to the current config;
    unseen neighbors sharing a (placement, eject capacity, memory layout)
    triple evaluate through ONE ``simulate_batch`` call. Placement axes
    resolve through :func:`resolve` (so ``"anneal"`` runs the placer once
    and reuses the result). Returns a machine-readable record:
    trajectory, best config, best cycles, evaluation count, wall seconds.
    """
    from ..core import schedulers
    from ..core.overlay import OverlayConfig, _simulate_batch

    space = dict(space or HILLCLIMB_SPACE)
    if space.get("scheduler") is None:
        space["scheduler"] = sorted(schedulers.REGISTRY)

    placed: dict = {}    # strategy -> node_pe
    gms: dict = {}       # (strategy, wants_criticality_order) -> GraphMemory

    def gm_for(strategy, wants):
        key = (strategy, wants)
        if key not in gms:
            if strategy not in placed:
                placed[strategy] = resolve(
                    g, nx, ny, PlacementSpec(strategy=strategy, seed=seed))
            gms[key] = graph_memory(g, nx, ny, placed[strategy],
                                    criticality_order=wants)
        return gms[key]

    n_evals = [0]
    seen: dict = {}  # config tuple -> cycles (configs revisit across steps)

    def evaluate(points):
        """[{axis: value}] -> [cycles] (inf when the config never finishes,
        so the search just steps around it)."""
        key = lambda pt: tuple(sorted(pt.items(), key=lambda kv: kv[0]))
        cycles = [seen.get(key(pt)) for pt in points]
        groups: dict = {}
        for i, pt in enumerate(points):
            if cycles[i] is None:
                wants = schedulers.get(pt["scheduler"]).wants_criticality_order
                groups.setdefault(
                    (pt["placement"], pt["eject_capacity"], wants), []).append(i)
        for (strategy, eject, wants), idxs in groups.items():
            n_evals[0] += len(idxs)
            cfgs = [OverlayConfig(scheduler=points[i]["scheduler"],
                                  select_latency=points[i]["select_latency"],
                                  eject_capacity=eject,
                                  max_cycles=max_cycles) for i in idxs]
            for i, r in zip(idxs, _simulate_batch(gm_for(strategy, wants),
                                                  cfgs)):
                c = r.cycles if r.done else float("inf")
                cycles[i] = seen[key(points[i])] = c
        return cycles

    def _finite(c):
        return None if c == float("inf") else c

    current = dict(placement="round_robin", scheduler="ooo",
                   select_latency=None, eject_capacity=1)
    t0 = time.time()
    best = evaluate([current])[0]
    trajectory = [{"config": dict(current), "cycles": _finite(best)}]
    while True:
        neighbors = []
        for field, values in space.items():
            for v in values:
                if v != current[field]:
                    neighbors.append(dict(current, **{field: v}))
        res = evaluate(neighbors)
        j = min(range(len(neighbors)), key=res.__getitem__)
        if res[j] >= best:
            break
        current, best = neighbors[j], res[j]
        trajectory.append({"config": dict(current), "cycles": _finite(best)})

    return {
        "space": {k: [str(v) for v in vs] for k, vs in space.items()},
        "trajectory": trajectory,
        "best_config": current,
        "best_cycles": _finite(best),
        "evaluations": n_evals[0],
        "wall_s": round(time.time() - t0, 3),
    }
