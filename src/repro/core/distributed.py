"""Multi-device overlay execution: the Hoplite torus mapped onto the ICI torus.

The per-PE layout of :mod:`repro.core.overlay` makes every per-cycle update
local to a PE row, so the whole simulator runs under ``shard_map``: the PE
grid [nx, ny] is tiled over the ("data", "model") mesh axes, torus link
shifts become *local roll + ppermute edge exchange* (a collective-permute IS
a NoC hop on the physical ICI torus — the paper's topology maps 1:1), and
the termination predicate is a psum-reduced flag.

This is the production path for overlays larger than one device and the
distribution showcase for the multi-pod dry-run (see tests + dryrun).

Scheduling is delegated to :mod:`repro.core.schedulers` through the same
protocol the single-device engine uses, so every registered policy (``ooo``,
``inorder``, ``scan``, ``lru_flat``, and any future registration) runs under
shard_map with no changes here.
"""
from __future__ import annotations

import dataclasses
import functools
import warnings

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from .. import SPAN_DISPATCH, SPAN_TO_DEVICE, SPAN_UNPACK
from . import bitvec, overlay, schedulers
from .partition import GraphMemory

def _shard_shift(axis_name: str, axis_idx: int, n: int):
    """Torus shift by +1 along array axis ``axis_idx`` where that axis is
    sharded ``n``-way over mesh axis ``axis_name``: local roll + ppermute of
    the edge slice to the next shard (wrap-around = the torus link). After
    the local roll, local row 0 holds the old local *last* row — exactly the
    edge owed to the next shard; every shard receives its predecessor's."""

    def shift(pkt: dict) -> dict:
        out = {}
        for k, v in pkt.items():
            rolled = jnp.roll(v, 1, axis=axis_idx)
            if n == 1:
                out[k] = rolled
                continue
            edge = jax.lax.slice_in_dim(rolled, 0, 1, axis=axis_idx)
            perm = [(i, (i + 1) % n) for i in range(n)]
            recv = jax.lax.ppermute(edge, axis_name, perm)
            out[k] = jax.lax.dynamic_update_slice_in_dim(
                rolled, recv, 0, axis=axis_idx)
        return out

    return shift


def _mk_chunk(K, mk_cycle, all_reduce, *, batched):
    """Per-shard chunk step: K shard-local cycles, one reduction per chunk."""
    chunk = overlay.make_chunk_fn(mk_cycle(lambda x: x), K, all_reduce)
    return jax.vmap(chunk) if batched else chunk


def _gather_telem(telem: dict, axis_x: str, axis_y: str) -> dict:
    """Reassemble shard-local telemetry traces into the global PE grid.

    Every telemetry leaf keeps its grid dims as the LAST TWO axes (bucketed
    traces are [NB, nx, ny], per-PE totals [nx, ny]; the batched engine adds
    a leading config axis), so one tiled all_gather per mesh axis rebuilds
    the replicated global trace — accumulation is purely PE-local, hence the
    gathered result is bit-identical to a single-device run."""

    def gather(leaf):
        leaf = jax.lax.all_gather(leaf, axis_y, axis=leaf.ndim - 1, tiled=True)
        return jax.lax.all_gather(leaf, axis_x, axis=leaf.ndim - 2, tiled=True)

    return {k: gather(v) for k, v in telem.items()}


def _mk_all_reduce(axis_x: str, axis_y: str):
    def all_reduce(x):
        if x.dtype == jnp.bool_:  # logical AND across shards
            return jax.lax.pmin(
                x.astype(jnp.int32), (axis_x, axis_y)).astype(jnp.bool_)
        return jax.lax.psum(x, (axis_x, axis_y))

    return all_reduce


def _simulate_sharded(gm: GraphMemory, mesh: Mesh,
                      cfg: overlay.OverlayConfig | None = None,
                      axis_x: str = "data", axis_y: str = "model",
                      nx: int | None = None, ny: int | None = None):
    """Run the overlay with the PE grid sharded over ``mesh``.

    nx must divide by mesh.shape[axis_x], ny by mesh.shape[axis_y].
    Returns the same SimResult as overlay.simulate. Accepts a packed
    :class:`GraphMemory` or a raw ``DataflowGraph`` plus ``nx``/``ny`` (the
    graph is then placed per ``cfg.placement`` — see :mod:`repro.place`).

    The stepping is chunked (``cfg.check_every``; the autotune sees the mesh
    size, so multi-device runs default to deep 32-cycle chunks): the cycle
    body inside a chunk keeps every predicate and stat shard-local, and the
    cross-shard psum/pmin runs once per chunk on the stacked done trace
    and the stat deltas — two collectives per ``check_every`` cycles instead
    of ~seven per cycle. ``check_every=1`` is the legacy per-cycle engine.
    """
    cfg = cfg or overlay.OverlayConfig()
    gm = overlay._as_memory(gm, cfg, nx, ny)
    sched = schedulers.get(cfg.scheduler)
    with jax.profiler.TraceAnnotation(SPAN_TO_DEVICE):
        g = overlay.device_graph(gm)
    K = overlay.resolve_check_every(cfg, gm.nx, gm.ny, g["opcode"].shape[2],
                                    num_devices=mesh.size)

    def spec_for(leaf):
        return P(axis_x, axis_y, *([None] * (leaf.ndim - 2)))

    nsx = mesh.shape[axis_x]
    nsy = mesh.shape[axis_y]

    @functools.partial(jax.shard_map, mesh=mesh,
                       in_specs=(jax.tree.map(spec_for, dict(g)),),
                       out_specs=P(),
                       check_vma=False)
    def run(gl):
        state = overlay.init_state(gl, cfg, scheduler=sched)
        nx_loc = gl["opcode"].shape[0]
        ny_loc = gl["opcode"].shape[1]
        all_reduce = _mk_all_reduce(axis_x, axis_y)

        def mk_cycle(reduce):
            return overlay.make_cycle_fn(
                gl, cfg,
                scheduler=sched,
                shift_e=_shard_shift(axis_x, 0, nsx),
                shift_s=_shard_shift(axis_y, 1, nsy),
                all_reduce=reduce,
                x0=jax.lax.axis_index(axis_x) * nx_loc,
                y0=jax.lax.axis_index(axis_y) * ny_loc,
                global_ny=gm.ny,
            )

        def cond(s):
            return (~s["done"]) & (s["cycle"] < cfg.max_cycles)

        if K > 1:
            # Guard-free chunks while a whole chunk fits the budget; the
            # per-cycle engine (with its per-cycle collectives) only runs
            # the < K tail cycles.
            chunk = _mk_chunk(K, mk_cycle, all_reduce, batched=False)
            state = jax.lax.while_loop(
                lambda s: (~s["done"]) & (s["cycle"] + K <= cfg.max_cycles),
                chunk, state)
        final = jax.lax.while_loop(cond, mk_cycle(all_reduce), state)
        # return per-shard values gathered to replicated full grid
        out = {
            "value": jax.lax.all_gather(final["value"], axis_y, axis=1, tiled=True),
            "cycle": final["cycle"],
            "done": final["done"],
        }
        for k in overlay.stat_keys(final):
            out[k] = final[k]
        out["value"] = jax.lax.all_gather(out["value"], axis_x, axis=0, tiled=True)
        if "telem" in final:
            out["telem"] = _gather_telem(final["telem"], axis_x, axis_y)
        return out

    with jax.profiler.TraceAnnotation(SPAN_DISPATCH):
        final = run(dict(g))
    with jax.profiler.TraceAnnotation(SPAN_UNPACK):
        return overlay._unpack_result(final, gm, cfg=cfg)


def simulate_sharded(gm: GraphMemory, mesh: Mesh,
                     cfg: overlay.OverlayConfig | None = None,
                     axis_x: str = "data", axis_y: str = "model",
                     nx: int | None = None, ny: int | None = None):
    """DEPRECATED: use :func:`repro.run` with ``mesh=mesh``."""
    warnings.warn(
        "distributed.simulate_sharded is deprecated; use "
        "repro.run(gm, cfg, mesh=mesh, nx=, ny=)",
        DeprecationWarning, stacklevel=2)
    return _simulate_sharded(gm, mesh, cfg, axis_x, axis_y, nx, ny)


def _simulate_batch_sharded(gm: GraphMemory, mesh: Mesh,
                            cfgs, axis_x: str = "data", axis_y: str = "model",
                            nx: int | None = None, ny: int | None = None):
    """Multi-config sweep of a sharded overlay: vmap inside shard_map.

    One XLA program runs every config of ``cfgs`` (scheduler / select latency
    / cycle budget may vary; ``eject_capacity``, ``eject_policy``,
    ``engine``, ``placement`` and ``telemetry`` must be uniform) with the
    PE grid
    tiled over ``mesh`` — the batched counterpart
    of :func:`simulate_sharded` for overlays larger than one device, and the
    sharded counterpart of :func:`repro.core.overlay.simulate_batch`. The
    cycle body is vmapped over the stacked config axis; torus ppermutes and
    the once-per-chunk psum/pmin become batched collectives. Results are
    element-wise identical to serial :func:`simulate_sharded` runs.
    """
    cfgs = list(cfgs)
    if not cfgs:
        return []
    eject = {c.eject_capacity for c in cfgs}
    if len(eject) != 1:
        raise ValueError(
            f"simulate_batch_sharded needs a uniform eject_capacity, got {eject}")
    policy = {c.eject_policy for c in cfgs}
    if len(policy) != 1:
        raise ValueError(
            f"simulate_batch_sharded needs a uniform eject_policy, got {policy}")
    engines = {c.engine for c in cfgs}
    if len(engines) != 1:
        raise ValueError(
            f"simulate_batch_sharded needs a uniform engine "
            f"('jnp' | 'select'), got {engines}")
    placements = {c.placement for c in cfgs}
    if len(placements) != 1:
        raise ValueError(
            f"simulate_batch_sharded needs a uniform placement, got {placements}")
    telems = {c.telemetry for c in cfgs}
    if len(telems) != 1:
        raise ValueError(
            f"simulate_batch_sharded needs a uniform telemetry spec (it "
            f"shapes the traced state), got {telems}")
    if not isinstance(gm, GraphMemory):
        # Shared packed memory image: see overlay.simulate_batch.
        wants = {schedulers.get(c.scheduler).wants_criticality_order
                 for c in cfgs}
        if len(wants) != 1:
            raise ValueError(
                "simulate_batch_sharded over a raw DataflowGraph needs "
                "schedulers with a uniform wants_criticality_order; group "
                "configs by memory layout or pass a pre-built GraphMemory")
    gm = overlay._as_memory(gm, cfgs[0], nx, ny)
    names: list[str] = []
    for c in cfgs:
        schedulers.get(c.scheduler)  # validate early
        if c.scheduler not in names:
            names.append(c.scheduler)

    base = dataclasses.replace(
        cfgs[0], scheduler=names[0], select_latency=None,
        max_cycles=max(c.max_cycles for c in cfgs))
    sched = schedulers.BatchedScheduler(tuple(names))
    with jax.profiler.TraceAnnotation(SPAN_TO_DEVICE):
        g = overlay.device_graph(gm)
        L = g["opcode"].shape[2]
        num_words = L // bitvec.FLAGS_PER_WORD
        policy_ids = jnp.asarray([names.index(c.scheduler) for c in cfgs],
                                 jnp.int32)
        sel_lats = jnp.asarray(
            [schedulers.get(c.scheduler).sel_lat(c, num_words) for c in cfgs],
            jnp.int32)
        max_cycs = jnp.asarray([c.max_cycles for c in cfgs], jnp.int32)
    K = overlay.resolve_check_every(base, gm.nx, gm.ny, L,
                                    num_devices=mesh.size)

    def spec_for(leaf):
        return P(axis_x, axis_y, *([None] * (leaf.ndim - 2)))

    nsx = mesh.shape[axis_x]
    nsy = mesh.shape[axis_y]

    @functools.partial(jax.shard_map, mesh=mesh,
                       in_specs=(jax.tree.map(spec_for, dict(g)), P(), P(), P()),
                       out_specs=P(),
                       check_vma=False)
    def run(gl, policy_ids, sel_lats, max_cycs):
        nx_loc = gl["opcode"].shape[0]
        ny_loc = gl["opcode"].shape[1]

        def init_one(pid, lat):
            s = overlay.init_state(gl, base, scheduler=sched)
            s["sched"]["policy_id"] = pid
            s["sel_lat"] = lat
            s["sel_wait"] = jnp.full_like(s["sel_wait"], lat - 1)
            return s

        state = jax.vmap(init_one)(policy_ids, sel_lats)
        all_reduce = _mk_all_reduce(axis_x, axis_y)

        def mk_cycle(reduce):
            return overlay.make_cycle_fn(
                gl, base,
                scheduler=sched,
                shift_e=_shard_shift(axis_x, 0, nsx),
                shift_s=_shard_shift(axis_y, 1, nsy),
                all_reduce=reduce,
                x0=jax.lax.axis_index(axis_x) * nx_loc,
                y0=jax.lax.axis_index(axis_y) * ny_loc,
                global_ny=gm.ny,
            )

        def cond(s):
            return ((~s["done"]) & (s["cycle"] < max_cycs)).any()

        if K > 1:
            vchunk = _mk_chunk(K, mk_cycle, all_reduce, batched=True)

            def chunk_cond(s):
                running = (~s["done"]) & (s["cycle"] < max_cycs)
                # An unfinished element at/near its budget is not a fixed
                # point — it must force the exit to the per-cycle tail.
                overruns = (~s["done"]) & (s["cycle"] + K > max_cycs)
                return running.any() & ~overruns.any()

            state = jax.lax.while_loop(chunk_cond, vchunk, state)

        vcycle = jax.vmap(mk_cycle(all_reduce))

        def freeze_body(s):
            new = vcycle(s)
            halted = s["done"] | (s["cycle"] >= max_cycs)

            def freeze(old, upd):
                d = halted.reshape(halted.shape + (1,) * (old.ndim - 1))
                return jnp.where(d, old, upd)

            return jax.tree.map(freeze, s, new)

        final = jax.lax.while_loop(cond, freeze_body, state)
        value = jax.lax.all_gather(final["value"], axis_y, axis=2, tiled=True)
        out = {
            "value": jax.lax.all_gather(value, axis_x, axis=1, tiled=True),
            "cycle": final["cycle"],
            "done": final["done"],
        }
        for k in overlay.stat_keys(final):
            out[k] = final[k]
        if "telem" in final:
            out["telem"] = _gather_telem(final["telem"], axis_x, axis_y)
        return out

    with jax.profiler.TraceAnnotation(SPAN_DISPATCH):
        final = run(dict(g), policy_ids, sel_lats, max_cycs)
    with jax.profiler.TraceAnnotation(SPAN_UNPACK):
        return [overlay._unpack_result(final, gm, b, cfg=base)
                for b in range(len(cfgs))]


def simulate_batch_sharded(gm: GraphMemory, mesh: Mesh,
                           cfgs, axis_x: str = "data", axis_y: str = "model",
                           nx: int | None = None, ny: int | None = None):
    """DEPRECATED: use :func:`repro.run` with ``mesh=mesh, batch=cfgs``."""
    warnings.warn(
        "distributed.simulate_batch_sharded is deprecated; use "
        "repro.run(gm, mesh=mesh, batch=cfgs, nx=, ny=)",
        DeprecationWarning, stacklevel=2)
    return _simulate_batch_sharded(gm, mesh, cfgs, axis_x, axis_y, nx, ny)
