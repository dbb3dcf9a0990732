"""Batched parallel-tempering placer: the whole search is one XLA program.

Search shape
------------
``replicas`` candidate placements evolve side by side (one ``jax.vmap`` over
the replica axis). Each replica runs *threshold accepting* — the
deterministic simulated-annealing variant: a proposed single-node move is
accepted iff its integer cost delta is ``<= threshold[r]`` — with thresholds
laddered geometrically from ``t_max`` (hot, explores) down to 0 (cold, pure
greedy). Every round (``steps`` proposals per replica under ``lax.scan``) a
parallel-tempering exchange runs across adjacent ladder rungs: the lower-cost
configuration migrates toward the cold end (the deterministic limit of the
classic Metropolis swap rule), so discoveries made while hot get polished
greedily without restarts.

Determinism
-----------
Costs, deltas, and accept decisions are all int64 arithmetic on int32 tables
(:mod:`repro.place.cost`), and proposals come from the counter-based JAX
PRNG, so for a fixed :class:`repro.place.spec.AnnealConfig` the result is
bit-identical across runs, machines, and backends. That is what lets
``BENCH_overlay.json`` gate *cycle counts of annealed placements* in CI.
Every search runs inside :func:`search_scope`, which also pins the PRNG's
bit stream across JAX releases.

Move evaluation is O(degree), not O(E): moving node ``v`` only re-prices the
edges incident to ``v`` (gathered from a padded host-built incidence table)
plus a two-PE load update — the carried per-PE load vector makes the
quadratic pressure delta ``2 w (load[q] - load[p] + w)``.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..core.graph import DataflowGraph
from .cost import CostModel, build_cost_model, edge_endpoints, torus_hops
from .spec import AnnealConfig


@contextlib.contextmanager
def search_scope():
    """Scoped JAX config of every placement-search program (and of the
    surrogate's training-set sampling).

    x64: cost totals are int64 sums of squared loads — they must not wrap on
    big graphs, and callers shouldn't need global ``jax_enable_x64``.
    Non-partitionable threefry: JAX 0.5 made the partitionable variant the
    default, which draws different bits from the same key, and so moves
    every annealed placement and the cycle counts tracked for it. The
    searches keep the stream they were recorded with.
    """
    with jax.enable_x64(True), jax.threefry_partitionable(False):
        yield


@dataclasses.dataclass(frozen=True)
class PlacementResult:
    """Best placement found plus search diagnostics."""

    node_pe: np.ndarray        # [N] int32 node -> PE
    cost: int                  # integer model cost of node_pe
    init_cost: int             # cost of the initial placement
    replica_costs: np.ndarray  # [R] per-replica best costs (ladder health)

    @property
    def improvement(self) -> float:
        return 1.0 - self.cost / max(1, self.init_cost)


@dataclasses.dataclass(frozen=True)
class GuidedPlacementResult(PlacementResult):
    """:class:`PlacementResult` of a surrogate-guided search.

    ``cost_evals`` counts the proposals that passed the surrogate gate and
    therefore reached the integer cost/accept rule (ungated proposals under
    ``guide_every > 1`` count too); ``proposals`` is the total budget
    ``replicas * rounds * steps`` — what an *unguided* run of the same
    config would have cost-evaluated. Both are exact deterministic integers,
    so the BENCH ``guided`` section CI-gates the ratio.

    The counter is an *accounting* metric — the proxy-in-the-loop claim for
    systems where evaluating the true cost dominates. Inside this
    branchless jitted kernel every delta is still computed, and each
    proposal additionally pays the O(degree)+O(P) surrogate update, so
    guided wall-clock per proposal is higher, not lower.
    """

    cost_evals: int = 0
    proposals: int = 0

    @property
    def eval_ratio(self) -> float:
        return self.cost_evals / max(1, self.proposals)


def incidence_table(g: DataflowGraph, w_edge: np.ndarray):
    """Padded per-node incident-edge table for O(degree) move deltas.

    Returns ([N, D] neighbor node, [N, D] int32 edge weight — 0 marks
    padding, [N, D] bool "node is the edge source"). D = max total degree
    (fanin <= 2, fanout unbounded).
    """
    src, dst = edge_endpoints(g)
    return incidence_from_edges(src, dst, w_edge, g.num_nodes)


def incidence_layout(src: np.ndarray, dst: np.ndarray, n: int):
    """Shared incidence layout: each edge appears once per endpoint.

    Returns ``(owner, pos, order, d_max)`` — the owning node of each
    (sorted) entry, its position within the owner's row, the sort
    permutation over the doubled ``[src; dst]`` edge list, and the padded
    row width. Both the weight tables (:func:`incidence_from_edges`) and
    arbitrary per-edge payloads (:func:`incidence_payload`) scatter through
    this one layout, so their entries line up index-for-index.
    """
    src = np.asarray(src, dtype=np.int64)
    dst = np.asarray(dst, dtype=np.int64)
    owner = np.concatenate([src, dst])
    order = np.argsort(owner, kind="stable")
    owner = owner[order]
    m = owner.shape[0]
    # Position of each entry within its owner's group (same trick as the
    # slot assigner): running index minus the group's start index.
    starts = np.zeros(m, dtype=np.int64)
    if m:
        group_start = np.r_[0, np.flatnonzero(np.diff(owner)) + 1]
        starts[group_start] = group_start
        starts = np.maximum.accumulate(starts)
    pos = np.arange(m) - starts
    d_max = max(1, int(pos.max(initial=0)) + 1)
    return owner, pos, order, d_max


def incidence_from_edges(src: np.ndarray, dst: np.ndarray,
                         w_edge: np.ndarray, n: int, *, layout=None):
    """:func:`incidence_table` over flat ``(src, dst)`` edge arrays.

    The annealer itself only needs incident-edge tables, not a
    :class:`DataflowGraph` — this is the entry point the multilevel
    coarsener (:mod:`repro.place.coarsen`) uses to anneal *cluster*-level
    quotient graphs with the very same jitted search kernel.
    """
    w_edge = np.asarray(w_edge, dtype=np.int32)
    other = np.concatenate([np.asarray(dst, np.int64),
                            np.asarray(src, np.int64)]).astype(np.int32)
    w = np.concatenate([w_edge, w_edge])
    out = np.concatenate([np.ones(len(w_edge), bool),
                          np.zeros(len(w_edge), bool)])

    owner, pos, order, d_max = layout or incidence_layout(src, dst, n)
    nbr = np.zeros((n, d_max), dtype=np.int32)
    w_pad = np.zeros((n, d_max), dtype=np.int32)
    is_out = np.zeros((n, d_max), dtype=bool)
    nbr[owner, pos] = other[order]
    w_pad[owner, pos] = w[order]
    is_out[owner, pos] = out[order]
    return nbr, w_pad, is_out


def incidence_payload(src: np.ndarray, dst: np.ndarray,
                      values: np.ndarray, n: int, *, layout=None) -> np.ndarray:
    """[N, D] per-incident-edge payload table in the exact layout of
    :func:`incidence_from_edges` (0 marks padding) — the guided annealer
    uses it to ride critical-edge / multiplicity tables alongside the
    weights."""
    values = np.asarray(values)
    owner, pos, order, d_max = layout or incidence_layout(src, dst, n)
    out = np.zeros((n, d_max), dtype=values.dtype)
    out[owner, pos] = np.concatenate([values, values])[order]
    return out


def _pe_loads(pe, w_node, num_pes: int):
    """[P] int64 criticality-weighted item load per PE."""
    return jnp.zeros(num_pes, jnp.int64).at[pe].add(w_node.astype(jnp.int64))


def _placement_cost(pe, nbr, w_inc, is_out, w_node, pw, nx: int, ny: int):
    """Full integer model cost of one [N] placement (traffic + pressure).

    Each incidence entry appears once per endpoint; out-edges only, so
    every edge is counted exactly once.
    """
    n = pe.shape[0]
    nbr_pe = pe[jnp.clip(nbr, 0, n - 1)]
    hop = torus_hops(pe[:, None], nbr_pe, nx, ny)
    traffic = jnp.sum(jnp.where(is_out, w_inc, 0).astype(jnp.int64)
                      * hop.astype(jnp.int64))
    loads = _pe_loads(pe, w_node, nx * ny)
    return traffic + pw * jnp.sum(loads * loads)


def _move_delta(pe, load, i, q, nbr, w_inc, is_out, w_node, pw,
                nx: int, ny: int):
    """O(degree) integer cost delta of moving item ``i`` to PE ``q``.

    Returns ``(delta, p, wn)`` — the delta, the item's current PE, and its
    int64 weight (what the accept commit needs). Shared by the plain and
    guided kernels so their objectives cannot drift apart; both pinned
    bit-exact by the open-gate equivalence test.
    """
    p = pe[i]
    nb, wv, out = nbr[i], w_inc[i], is_out[i]
    nbr_pe = pe[nb]
    old_h = jnp.where(out, torus_hops(p, nbr_pe, nx, ny),
                      torus_hops(nbr_pe, p, nx, ny))
    new_h = jnp.where(out, torus_hops(q, nbr_pe, nx, ny),
                      torus_hops(nbr_pe, q, nx, ny))
    d_traffic = jnp.sum(wv.astype(jnp.int64)
                        * (new_h - old_h).astype(jnp.int64))
    wn = w_node[i].astype(jnp.int64)
    d_pressure = 2 * wn * (load[q] - load[p] + wn)
    return d_traffic + pw * d_pressure, p, wn


def _pt_take(costs, parity):
    """[R] replica-permutation indices of one parallel-tempering exchange:
    the lower-cost configuration of each adjacent ladder pair migrates
    toward the cold (low-r) end. Shared by the plain and guided kernels so
    their swap rules cannot drift apart."""
    r = jnp.arange(costs.shape[0])
    off = r - parity
    partner = jnp.where(off < 0, r,
                        jnp.where(off % 2 == 0, r + 1, r - 1))
    partner = jnp.clip(partner, 0, costs.shape[0] - 1)
    lo = jnp.minimum(r, partner)
    hi = jnp.maximum(r, partner)
    swap = (partner != r) & (costs[hi] < costs[lo])
    return jnp.where(swap, partner, r)


def _thresholds(acfg: AnnealConfig) -> np.ndarray:
    """[R] int64 acceptance thresholds: 0 (greedy) then geometric to t_max."""
    r = acfg.replicas
    t = float(acfg.t_max)
    if r == 1 or t <= 0:
        return np.zeros(r, dtype=np.int64)
    if r == 2:
        ladder = np.array([t])          # single hot rung sits AT t_max
    else:
        ladder = np.geomspace(min(2.0, t), t, r - 1)
    return np.concatenate([[0], np.rint(ladder).astype(np.int64)])


@functools.partial(jax.jit, static_argnames=("nx", "ny", "rounds", "steps",
                                             "pressure_weight"))
def _anneal_jit(init_pe, nbr, w_inc, is_out, w_node, thresholds, key,
                *, nx: int, ny: int, rounds: int, steps: int,
                pressure_weight: int):
    R = thresholds.shape[0]
    N = init_pe.shape[0]
    P = nx * ny
    pw = jnp.int64(pressure_weight)

    def loads_of(pe):
        return _pe_loads(pe, w_node, P)

    def full_cost(pe):
        return _placement_cost(pe, nbr, w_inc, is_out, w_node, pw, nx, ny)

    def propose(st, key, thresh):
        pe, load, cost = st
        k1, k2 = jax.random.split(key)
        # int32 dtype pinned: the drawn sequence must not depend on the
        # ambient x64 mode (bit-determinism contract).
        i = jax.random.randint(k1, (), 0, N, dtype=jnp.int32)
        q = jax.random.randint(k2, (), 0, P, dtype=jnp.int32)
        delta, p, wn = _move_delta(pe, load, i, q, nbr, w_inc, is_out,
                                   w_node, pw, nx, ny)
        accept = (delta <= thresh) & (p != q)
        pe = pe.at[i].set(jnp.where(accept, q, p))
        load = load.at[p].add(jnp.where(accept, -wn, 0))
        load = load.at[q].add(jnp.where(accept, wn, 0))
        return (pe, load, cost + jnp.where(accept, delta, jnp.int64(0)))

    def sweep(st_keys, _):
        st, keys = st_keys
        new_keys = jax.vmap(lambda k: jax.random.split(k, 2))(keys)
        step_keys, keys = new_keys[:, 0], new_keys[:, 1]
        st = jax.vmap(propose)(st, step_keys, thresholds)
        return (st, keys), None

    def pt_swap(st, costs, parity):
        take = _pt_take(costs, parity)
        return jax.tree.map(lambda a: a[take], st), costs[take]

    def round_body(carry, parity):
        st, keys, best_pe, best_cost = carry
        (st, keys), _ = jax.lax.scan(sweep, (st, keys), None, length=steps)
        pe, load, cost = st
        better = cost < best_cost
        best_cost = jnp.where(better, cost, best_cost)
        best_pe = jnp.where(better[:, None], pe, best_pe)
        pe, cost = pt_swap(pe, cost, parity)
        load = jax.vmap(loads_of)(pe)
        return ((pe, load, cost), keys, best_pe, best_cost), None

    pe0 = jnp.broadcast_to(init_pe, (R, N)).astype(jnp.int32)
    load0 = jax.vmap(loads_of)(pe0)
    cost0 = jax.vmap(full_cost)(pe0)
    keys = jax.random.split(key, R)
    carry = ((pe0, load0, cost0), keys, pe0, cost0)
    parities = jnp.arange(rounds, dtype=jnp.int32) % 2
    (_, _, best_pe, best_cost), _ = jax.lax.scan(round_body, carry, parities)
    return best_pe, best_cost, cost0[0]


@functools.partial(jax.jit, static_argnames=("nx", "ny", "rounds", "steps",
                                             "pressure_weight", "guide_every"))
def _anneal_guided_jit(init_pe, nbr, w_inc, is_out, w_node, thresholds, key,
                       ga, q_margin, *, nx: int, ny: int, rounds: int,
                       steps: int, pressure_weight: int, guide_every: int):
    """Two-stage-accept variant of :func:`_anneal_jit`.

    Every proposal is first scored by the integer-quantized surrogate via an
    O(degree) incremental feature delta (:mod:`repro.surrogate.delta`); only
    proposals the surrogate rates promising (``dscore <= q_margin``, on
    steps selected by ``guide_every``) proceed to the usual integer
    cost/accept rule. The PRNG stream, cost arithmetic, best tracking, and
    PT exchange are identical to the unguided kernel, so with the gate wide
    open (``q_margin = int64 max``) the trajectory reproduces
    :func:`_anneal_jit` bit-for-bit (pinned in ``tests/test_guided.py``).
    """
    from ..surrogate.delta import apply_move, state_init

    R = thresholds.shape[0]
    N = init_pe.shape[0]
    P = nx * ny
    pw = jnp.int64(pressure_weight)

    def loads_of(pe):
        return _pe_loads(pe, w_node, P)

    def full_cost(pe):
        return _placement_cost(pe, nbr, w_inc, is_out, w_node, pw, nx, ny)

    def propose(st, key, thresh, j):
        pe, load, cost, gst, evals = st
        k1, k2 = jax.random.split(key)
        i = jax.random.randint(k1, (), 0, N, dtype=jnp.int32)
        q = jax.random.randint(k2, (), 0, P, dtype=jnp.int32)

        # Stage 1 — surrogate gate: exact incremental features, quantized
        # predicted-cycle delta. Gate-rejected proposals are dead on
        # arrival: the cost rule cannot accept them, and they don't count
        # as full-cost evaluations. (The branchless jitted kernel still
        # *computes* every delta — the counter is the accounting metric for
        # systems where the true cost evaluation is the scarce resource,
        # not a wall-clock claim about this kernel.)
        gst_new, dscore = apply_move(ga, gst, pe, i, q, nx=nx, ny=ny)
        gated = (j % guide_every) == 0
        promising = jnp.where(gated, dscore <= q_margin, True)

        # Stage 2 — the unguided kernel's integer cost/threshold accept.
        delta, p, wn = _move_delta(pe, load, i, q, nbr, w_inc, is_out,
                                   w_node, pw, nx, ny)
        accept = promising & (delta <= thresh) & (p != q)
        pe = pe.at[i].set(jnp.where(accept, q, p))
        load = load.at[p].add(jnp.where(accept, -wn, 0))
        load = load.at[q].add(jnp.where(accept, wn, 0))
        cost = cost + jnp.where(accept, delta, jnp.int64(0))
        gst = jax.tree.map(lambda a, b: jnp.where(accept, a, b), gst_new, gst)
        evals = evals + promising.astype(jnp.int64)
        return (pe, load, cost, gst, evals)

    def sweep(st_keys, j):
        st, keys = st_keys
        new_keys = jax.vmap(lambda k: jax.random.split(k, 2))(keys)
        step_keys, keys = new_keys[:, 0], new_keys[:, 1]
        st = jax.vmap(propose, in_axes=(0, 0, 0, None))(
            st, step_keys, thresholds, j)
        return (st, keys), None

    def round_body(carry, parity):
        st, keys, best_pe, best_cost = carry
        (st, keys), _ = jax.lax.scan(sweep, (st, keys),
                                     jnp.arange(steps, dtype=jnp.int32))
        pe, load, cost, gst, evals = st
        better = cost < best_cost
        best_cost = jnp.where(better, cost, best_cost)
        best_pe = jnp.where(better[:, None], pe, best_pe)
        take = _pt_take(cost, parity)
        pe, cost = pe[take], cost[take]
        gst = jax.tree.map(lambda a: a[take], gst)
        load = jax.vmap(loads_of)(pe)
        # evals stays un-permuted: the counters belong to the ladder rungs,
        # not the migrating configurations (their sum is invariant anyway).
        return ((pe, load, cost, gst, evals), keys, best_pe, best_cost), None

    pe0 = jnp.broadcast_to(init_pe, (R, N)).astype(jnp.int32)
    load0 = jax.vmap(loads_of)(pe0)
    cost0 = jax.vmap(full_cost)(pe0)
    gst0 = jax.vmap(lambda pe: state_init(ga, pe, nx=nx, ny=ny))(pe0)
    evals0 = jnp.zeros(R, jnp.int64)
    keys = jax.random.split(key, R)
    carry = ((pe0, load0, cost0, gst0, evals0), keys, pe0, cost0)
    parities = jnp.arange(rounds, dtype=jnp.int32) % 2
    (st, _, best_pe, best_cost), _ = jax.lax.scan(round_body, carry, parities)
    return best_pe, best_cost, cost0[0], st[4]


def anneal_tables(
    n: int,
    nx: int,
    ny: int,
    src: np.ndarray,
    dst: np.ndarray,
    w_edge: np.ndarray,
    w_node: np.ndarray,
    acfg: AnnealConfig | None = None,
    *,
    init: np.ndarray | None = None,
    guide=None,
    guide_every: int = 1,
    guide_margin: float = 0.0,
) -> PlacementResult:
    """Anneal an ``[n]`` item -> PE placement from flat integer edge tables.

    ``n`` items (graph nodes — or node *clusters* in the multilevel pipeline)
    connected by ``(src, dst)`` edges of weight ``w_edge``, with per-item
    weights ``w_node``, are placed on the ``nx x ny`` torus. This is the
    graph-free core of :func:`anneal_placement`: same jitted kernel, same
    determinism contract, no :class:`DataflowGraph` needed.

    ``guide`` switches on the two-stage surrogate accept: a
    :class:`repro.surrogate.delta.Guide` (or a fitted
    :class:`~repro.surrogate.model.SurrogateModel`, converted on the spot)
    built for the *same* ``n`` items on the same grid — its extractor may
    weight edges its own way, but it must describe this item set. Proposals
    whose quantized predicted-cycle delta exceeds ``guide_margin`` (in
    predicted cycles; ``inf`` disables the gate) are rejected before the
    integer cost rule; ``guide_every=k`` applies the gate on every k-th
    proposal of a sweep only. Guided searches return a
    :class:`GuidedPlacementResult` carrying the exact cost-evaluation count.
    """
    acfg = acfg or AnnealConfig()
    num_pes = nx * ny
    if init is None:
        rng = np.random.default_rng(acfg.seed)
        init = rng.integers(0, num_pes, size=n).astype(np.int32)
    init = np.asarray(init, dtype=np.int32)
    if init.shape != (n,):
        raise ValueError(f"init must be [{n}] item->PE, got {init.shape}")
    if init.size and (init.min() < 0 or init.max() >= num_pes):
        raise ValueError("init placement references PEs outside the grid")

    nbr, w_inc, is_out = incidence_from_edges(src, dst, w_edge, n)
    # Host numpy throughout: the arrays cross into jax at the jit boundary,
    # inside the scoped x64 below — an eager jnp.asarray here would silently
    # truncate the int64 thresholds to int32 when ambient x64 is off.
    args = (init, nbr, w_inc, is_out, np.asarray(w_node, np.int32),
            _thresholds(acfg), jax.random.key(acfg.seed))
    knobs = dict(nx=nx, ny=ny, rounds=acfg.rounds, steps=acfg.steps,
                 pressure_weight=acfg.pressure_weight)
    if guide is None:
        with search_scope():
            best_pe, best_cost, init_cost = _anneal_jit(*args, **knobs)
        evals = None
    else:
        from ..surrogate.delta import (Guide, build_guide, guide_arrays,
                                       quantize_margin)

        if not isinstance(guide, Guide):
            guide = build_guide(guide)
        ex = guide.extractor
        if ex.num_items != n or (ex.nx, ex.ny) != (nx, ny):
            raise ValueError(
                f"guide was built for {ex.num_items} items on a "
                f"{ex.nx}x{ex.ny} grid; this search places {n} items on "
                f"{nx}x{ny}")
        if guide_every < 1:
            raise ValueError(f"guide_every must be >= 1, got {guide_every}")
        with search_scope():
            best_pe, best_cost, init_cost, evals = _anneal_guided_jit(
                *args, guide_arrays(guide),
                np.int64(quantize_margin(guide_margin)),
                guide_every=int(guide_every), **knobs)
    best_pe = np.asarray(best_pe)
    best_cost = np.asarray(best_cost)
    b = int(best_cost.argmin())
    fields = dict(
        node_pe=best_pe[b].astype(np.int32),
        cost=int(best_cost[b]),
        init_cost=int(init_cost),
        replica_costs=best_cost.astype(np.int64),
    )
    if guide is None:
        return PlacementResult(**fields)
    return GuidedPlacementResult(
        **fields, cost_evals=int(np.asarray(evals).sum()),
        proposals=acfg.replicas * acfg.rounds * acfg.steps)


def anneal_tables_many(
    n: int,
    nx: int,
    ny: int,
    src: np.ndarray,
    dst: np.ndarray,
    w_edge: np.ndarray,
    w_node: np.ndarray,
    acfgs,
    *,
    inits=None,
) -> list[PlacementResult]:
    """Run MANY independent anneals of one item set as a single XLA program.

    The service batch executor's fan-out: ``Q`` queries that share the graph
    tables and grid (typically differing in ``seed`` / ``t_max``) vmap over
    the query axis of the same jitted kernel — one compile, one dispatch,
    ``Q x replicas`` ladders in flight. Every element is bit-identical to a
    solo :func:`anneal_tables` call with the same config (integer cost
    arithmetic and the counter-based PRNG are exact under vmap; asserted in
    ``tests/test_service.py``).

    Static kernel knobs must be uniform across ``acfgs``: ``replicas``,
    ``rounds``, ``steps``, ``pressure_weight`` (they shape the program).
    Per-query values may vary: ``seed`` (init + proposal stream) and
    ``t_max`` (thresholds ride in as data). Guided anneals don't batch —
    resolve those queries solo.
    """
    acfgs = list(acfgs)
    if not acfgs:
        return []
    statics = {(a.replicas, a.rounds, a.steps, a.pressure_weight)
               for a in acfgs}
    if len(statics) != 1:
        raise ValueError(
            f"anneal_tables_many needs uniform (replicas, rounds, steps, "
            f"pressure_weight) across the batch — they shape the compiled "
            f"kernel; got {sorted(statics)}. Group queries by these knobs.")
    num_pes = nx * ny
    if inits is None:
        inits = [None] * len(acfgs)
    init_rows = []
    for a, init in zip(acfgs, inits):
        if init is None:
            rng = np.random.default_rng(a.seed)
            init = rng.integers(0, num_pes, size=n).astype(np.int32)
        init = np.asarray(init, dtype=np.int32)
        if init.shape != (n,):
            raise ValueError(f"init must be [{n}] item->PE, got {init.shape}")
        if init.size and (init.min() < 0 or init.max() >= num_pes):
            raise ValueError("init placement references PEs outside the grid")
        init_rows.append(init)

    nbr, w_inc, is_out = incidence_from_edges(src, dst, w_edge, n)
    init_pes = np.stack(init_rows)
    thresholds = np.stack([_thresholds(a) for a in acfgs])
    keys = jnp.stack([jax.random.key(a.seed) for a in acfgs])
    run1 = functools.partial(
        _anneal_jit, nx=nx, ny=ny, rounds=acfgs[0].rounds,
        steps=acfgs[0].steps, pressure_weight=acfgs[0].pressure_weight)
    w_node = np.asarray(w_node, np.int32)
    with search_scope():
        best_pe, best_cost, init_cost = jax.vmap(
            run1, in_axes=(0, None, None, None, None, 0, 0))(
                init_pes, nbr, w_inc, is_out, w_node, thresholds, keys)
    best_pe = np.asarray(best_pe)
    best_cost = np.asarray(best_cost)
    init_cost = np.asarray(init_cost)
    out = []
    for q in range(len(acfgs)):
        b = int(best_cost[q].argmin())
        out.append(PlacementResult(
            node_pe=best_pe[q, b].astype(np.int32),
            cost=int(best_cost[q, b]),
            init_cost=int(init_cost[q]),
            replica_costs=best_cost[q].astype(np.int64)))
    return out


def anneal_placements(
    g: DataflowGraph,
    nx: int,
    ny: int,
    acfgs,
    *,
    metric: str = "height",
    inits=None,
    model: CostModel | None = None,
) -> list[PlacementResult]:
    """Many independent :func:`anneal_placement` searches, one XLA program.

    All queries share one cost model (so ``metric`` and the configs'
    ``crit_scale`` must be uniform — the weight tables are data to the
    vmapped kernel, but a per-query metric would mean per-query tables and
    defeat the sharing). See :func:`anneal_tables_many` for the uniformity
    contract and the bit-exactness guarantee vs solo runs.
    """
    acfgs = [a or AnnealConfig() for a in acfgs]
    if not acfgs:
        return []
    crits = {a.crit_scale for a in acfgs}
    pws = {a.pressure_weight for a in acfgs}
    if model is None and (len(crits) != 1 or len(pws) != 1):
        raise ValueError(
            f"anneal_placements shares one cost model: crit_scale/"
            f"pressure_weight must be uniform, got {crits}/{pws}")
    model = model or build_cost_model(
        g, nx, ny, metric=metric, crit_scale=acfgs[0].crit_scale,
        pressure_weight=acfgs[0].pressure_weight)
    src, dst = edge_endpoints(g)
    return anneal_tables_many(
        g.num_nodes, nx, ny, src, dst, np.asarray(model.w_edge),
        np.asarray(model.w_node), acfgs, inits=inits)


def anneal_placement(
    g: DataflowGraph,
    nx: int,
    ny: int,
    acfg: AnnealConfig | None = None,
    *,
    metric: str = "height",
    init: np.ndarray | None = None,
    model: CostModel | None = None,
    guide=None,
    guide_every: int = 1,
    guide_margin: float = 0.0,
) -> PlacementResult:
    """Search a node -> PE placement for ``g`` on the ``nx x ny`` torus.

    ``init`` defaults to a uniform-random placement drawn from
    ``acfg.seed`` — the baseline the annealer is guaranteed (by best-so-far
    tracking that includes the init) to never score worse than. ``guide``
    (a fitted :class:`~repro.surrogate.model.SurrogateModel` or a prebuilt
    :class:`~repro.surrogate.delta.Guide` for this graph and grid) switches
    on the two-stage surrogate accept — see :func:`anneal_tables`.
    """
    acfg = acfg or AnnealConfig()
    model = model or build_cost_model(
        g, nx, ny, metric=metric, crit_scale=acfg.crit_scale,
        pressure_weight=acfg.pressure_weight)
    src, dst = edge_endpoints(g)
    return anneal_tables(
        g.num_nodes, nx, ny, src, dst, np.asarray(model.w_edge),
        np.asarray(model.w_node), acfg, init=init, guide=guide,
        guide_every=guide_every, guide_margin=guide_margin)
