"""Fused K-cycle overlay chunk as ONE Pallas kernel (the "megakernel").

The paper's overlay sustains 300 soft processors at 250MHz because scheduler
select (tag match + leading-one detect), Hoplite routing, and eject all
resolve inside a single hardware cycle. The software analogue in
:mod:`repro.core.overlay` pays ~5 separate jnp dispatch regions per simulated
cycle glued by ``lax.scan`` — every region re-materializes the full state
from HBM. This module fuses the *entire chunk* instead: one
``pl.pallas_call`` whose kernel body runs ``check_every`` back-to-back
cycles — scheduler select for every registered policy (via the
``Scheduler.step`` protocol, same code path the PR-2 ``schedule_step`` /
``rotating_schedule_step`` kernels generalized), the unidirectional-Hoplite
torus route, the fused per-port eject updates, and the remaining-nodes
termination counter — with operand/dependency state carried across cycles in
kernel refs (VMEM on TPU) rather than round-tripped per dispatch.

State layout in refs
--------------------
The simulation state pytree (see ``overlay.init_state``) and the
``DeviceGraph`` dict are flattened to leaf arrays in canonical pytree order;
each leaf becomes one kernel ref (graph leaves are read-only inputs, state
leaves are inputs with matching outputs). Rank-0 leaves (``cycle``,
``remaining``, ``done``, the stat counters) ride as shape-``(1,)`` refs and
are reshaped back inside the kernel. The kernel loads every leaf once,
iterates the cycle body ``K`` times in a ``fori_loop`` with the whole state
as the carry, records the per-cycle ``done`` flag into a ``[K]`` (or
``[K, B]`` batched) trace ref, and stores the final state once.

K-cycle carry + exactness
-------------------------
The in-kernel cycle body IS ``overlay.make_cycle_fn`` — the same pure-jnp
transition the reference engine scans, traced into the kernel instead of
into an XLA while-loop body. That makes bit-exactness an identity, not a
re-derivation: the chunk repair (completion-cycle recovery from the done
trace, once-per-chunk stat reduction) is the same arithmetic as
``overlay.make_chunk_fn``, applied to the kernel's outputs. The pure-jnp
chunked path stays the reference oracle; ``tests/test_megakernel.py`` pins
every policy x chunk depth x engine combination bit-for-bit, and the BENCH
``megakernel`` section gates the fig1-family cycle counts.

Mesh and backend semantics
--------------------------
The kernel cannot contain cross-shard collectives, so the sharded engines
(:mod:`repro.core.distributed`) run ``engine="megakernel"`` only when both
mesh axes are size 1 (torus shifts are then pure local rolls); on a real
multi-shard mesh they raise ``ValueError``. On non-TPU backends the kernel
executes in Pallas interpret mode (the validated CI configuration). The TPU
compiler refuses it: the dynamic per-PE ``[nx, ny, L]`` gathers of the
cycle body give ``NotImplementedError: Only 2D gather is supported``
(pinned in ``tests/test_tpu_compile.py``), which is why the jnp path
remains the default engine.
"""
from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..core import overlay


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def make_mega_chunk_fn(
    g: dict,
    cfg: "overlay.OverlayConfig",
    check_every: int,
    *,
    scheduler=None,
    batched: bool = False,
    all_reduce: Callable[[Any], Any] = lambda x: x,
    interpret: bool | None = None,
):
    """Build ``chunk(state) -> state`` running ``check_every`` cycles in one
    ``pallas_call`` — the ``engine="megakernel"`` counterpart of
    ``overlay.make_chunk_fn(cycle_fn, check_every)``.

    ``batched=True`` builds the vmapped-cycle variant for the batched sweep
    engine (state leaves carry a leading config axis; the done trace becomes
    ``[K, B]`` and the chunk repair runs per element). ``all_reduce`` is the
    once-per-chunk cross-shard reduction (identity on a single device) and
    stays *outside* the kernel, exactly like the jnp chunk.
    """
    if interpret is None:
        interpret = _interpret()
    sched = overlay._resolve(cfg, scheduler)
    K = int(check_every)
    g_leaves, g_tree = jax.tree_util.tree_flatten(dict(g))

    def chunk(s):
        s_leaves, s_tree = jax.tree_util.tree_flatten(s)
        n_g, n_s = len(g_leaves), len(s_leaves)
        # Rank-0 leaves ride as (1,) refs; remember the true shapes.
        s_shapes = [l.shape for l in s_leaves]
        trace_shape = (K,) + tuple(s["done"].shape)

        def kernel(*refs):
            g_vals = [refs[i][...] for i in range(n_g)]
            s_vals = [refs[n_g + i][...].reshape(s_shapes[i])
                      for i in range(n_s)]
            out_refs = refs[n_g + n_s:]
            gv = jax.tree_util.tree_unflatten(g_tree, g_vals)
            sv = jax.tree_util.tree_unflatten(s_tree, s_vals)
            # The reference cycle body, traced INTO the kernel: select +
            # route + eject + termination stay fused across all K cycles.
            cycle = overlay.make_cycle_fn(gv, cfg, scheduler=sched)
            if batched:
                cycle = jax.vmap(cycle)

            def body(k, carry):
                st, trace = carry
                st = cycle(st)
                trace = jax.lax.dynamic_update_index_in_dim(
                    trace, st["done"], k, 0)
                return st, trace

            trace0 = jnp.zeros(trace_shape, jnp.bool_)
            st, trace = jax.lax.fori_loop(0, K, body, (sv, trace0))
            for r, leaf in zip(out_refs[:n_s], jax.tree_util.tree_leaves(st)):
                r[...] = leaf.reshape(r.shape)
            out_refs[n_s][...] = trace

        at_least_1d = lambda l: l.reshape((1,)) if l.ndim == 0 else l
        out_shape = [jax.ShapeDtypeStruct(at_least_1d(l).shape, l.dtype)
                     for l in s_leaves]
        out_shape.append(jax.ShapeDtypeStruct(trace_shape, jnp.bool_))
        res = pl.pallas_call(kernel, out_shape=out_shape,
                             interpret=interpret)(
            *g_leaves, *(at_least_1d(l) for l in s_leaves))
        s2 = jax.tree_util.tree_unflatten(
            s_tree, [r.reshape(shp) for r, shp in zip(res[:-1], s_shapes)])
        done_trace = res[-1]

        # Chunk repair — the same arithmetic as overlay.make_chunk_fn,
        # applied along the in-chunk axis 0 (elementwise over any batch
        # axis, so batched repair == vmap of the solo repair).
        keys = overlay.stat_keys(s)
        start_stats = jnp.stack([s[k] for k in keys])
        start_cycle = s["cycle"]
        start_done = s["done"]
        done_trace = all_reduce(done_trace)            # one collective
        any_done = done_trace.any(axis=0)
        first = jnp.argmax(done_trace, axis=0).astype(jnp.int32)
        cycle_ct = jnp.where(
            start_done, start_cycle,
            jnp.where(any_done, start_cycle + first + 1, s2["cycle"]))
        end_stats = jnp.stack([s2[k] for k in keys])
        stats = start_stats + all_reduce(end_stats - start_stats)

        out = dict(s2, done=any_done, cycle=cycle_ct)
        for i, k in enumerate(keys):
            out[k] = stats[i]
        if "telem" in out:
            # Telemetry leaves ride the state pytree into kernel refs like
            # any other leaf, so the fused engine gets full traces for free;
            # only the fixed-point overshoot repair happens out here.
            out["telem"] = overlay.repair_telemetry(
                out["telem"], s2["cycle"] - cycle_ct)
        return out

    return chunk
