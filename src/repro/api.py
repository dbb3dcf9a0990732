"""repro.run — the one front-door entry point for overlay simulation.

Historically the engines were four separate functions that differed only in
*how* the same cycle body executes, never in what it computes:

  =====================================  ==========================
  legacy entry point                     ``repro.run`` spelling
  =====================================  ==========================
  ``overlay.simulate``                   ``run(gm, cfg)``
  ``overlay.simulate_batch``             ``run(gm, batch=cfgs)``
  ``distributed.simulate_sharded``       ``run(gm, cfg, mesh=mesh)``
  ``distributed.simulate_batch_sharded`` ``run(gm, mesh=mesh, batch=cfgs)``
  =====================================  ==========================

``run`` keeps that bit-determinism contract: every path returns results
bit-identical to the legacy entry point it replaces (asserted in
``tests/test_service.py``; all 48 tracked BENCH cycle counts reproduce
through the dispatcher). The legacy four remain as thin
``DeprecationWarning`` wrappers around the same private implementations.
"""
from __future__ import annotations

from typing import Any, Sequence


def run(graph_or_gm, cfg=None, *, mesh=None, batch: Sequence | None = None,
        nx: int | None = None, ny: int | None = None) -> Any:
    """Simulate an overlay; the engine path is picked from the arguments.

    Args:
      graph_or_gm: a packed :class:`repro.core.partition.GraphMemory`, or a
        raw :class:`repro.core.graph.DataflowGraph` plus ``nx``/``ny`` (the
        graph is placed per ``cfg.placement`` — see :mod:`repro.place`).
      cfg: a single :class:`repro.core.overlay.OverlayConfig` (``None`` =
        defaults). Mutually exclusive with ``batch``.
      mesh: a :class:`jax.sharding.Mesh` with ``("data", "model")`` axes —
        shards the PE grid across devices (``nx`` divisible by the data
        axis, ``ny`` by the model axis).
      batch: a sequence of ``OverlayConfig`` — runs the whole sweep as ONE
        XLA program (vmapped cycle body) and returns a list of results,
        element-wise bit-identical to solo runs.
      nx, ny: PE grid, required only with a raw ``DataflowGraph``.

    Returns:
      :class:`repro.core.overlay.SimResult` (or a list of them with
      ``batch=``).

    Under an open ``jax.profiler`` session the call is the host span
    ``repro.run``, with the spans of its steps inside (see
    :data:`repro.HOST_SPANS` and docs/telemetry.md).
    """
    import jax

    from . import SPAN_RUN

    with jax.profiler.TraceAnnotation(SPAN_RUN):
        return _dispatch(graph_or_gm, cfg, mesh, batch, nx, ny)


def _dispatch(graph_or_gm, cfg, mesh, batch, nx, ny):
    """The engine path :func:`run`'s arguments pick, called with them."""
    if batch is not None:
        if cfg is not None:
            raise ValueError(
                "repro.run: pass either cfg= (one config) or batch= "
                "(a config sweep), not both")
        batch = list(batch)
        if mesh is not None:
            from .core.distributed import _simulate_batch_sharded
            return _simulate_batch_sharded(graph_or_gm, mesh, batch,
                                           nx=nx, ny=ny)
        from .core.overlay import _simulate_batch
        return _simulate_batch(graph_or_gm, batch, nx=nx, ny=ny)
    if mesh is not None:
        from .core.distributed import _simulate_sharded
        return _simulate_sharded(graph_or_gm, mesh, cfg, nx=nx, ny=ny)
    from .core.overlay import _simulate
    return _simulate(graph_or_gm, cfg, nx=nx, ny=ny)
