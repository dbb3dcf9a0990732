"""repro — out-of-order dataflow scheduling for FPGA overlays, in JAX.

Package front door. The two names most callers need:

  * :func:`repro.run` — the unified simulate dispatcher (single / batched /
    sharded / batched-sharded engine paths picked from its arguments);
  * :mod:`repro.service` — the batched placement-and-simulation service
    (content-hash result cache, batched query execution, Pareto explorer).

Both are loaded lazily so ``import repro`` stays free of JAX import cost
until an engine is actually used.
"""
from __future__ import annotations

__all__ = ["run", "service"]

# Names of the profiler spans and scopes the simulator opens. Host spans are
# ``jax.profiler.TraceAnnotation``s: they cost nothing unless a profiler
# session (``jax.profiler.trace``) is open, and then share its clock with the
# device's events. Device scopes are ``jax.named_scope``s on the cycle body:
# they only name the compiled ops (the ``op_name`` metadata), so the program
# is the same with or without them.
SPAN_RUN = "repro.run"                  # api.run, the whole call
SPAN_PACK = "repro.pack"                # a raw graph packed into GraphMemory
SPAN_PLACE = "repro.place"              # node -> PE placement
SPAN_CRITICALITY = "repro.criticality"  # criticality levels of the nodes
SPAN_SLOTS = "repro.slots"              # per-PE slot assignment
SPAN_PACK_NODES = "repro.pack_nodes"    # per-node records of every PE
SPAN_PACK_EDGES = "repro.pack_edges"    # per-PE fanout edge lists
SPAN_TO_DEVICE = "repro.to_device"      # host arrays handed to the device
SPAN_DISPATCH = "repro.dispatch"        # the compiled engine launched
SPAN_UNPACK = "repro.unpack"            # results read back (waits for them)
HOST_SPANS = (SPAN_RUN, SPAN_PACK, SPAN_PLACE, SPAN_CRITICALITY, SPAN_SLOTS,
              SPAN_PACK_NODES, SPAN_PACK_EDGES, SPAN_TO_DEVICE, SPAN_DISPATCH,
              SPAN_UNPACK)
#: The children of ``SPAN_PACK``, in the order they run.
PACK_SPANS = (SPAN_PLACE, SPAN_CRITICALITY, SPAN_SLOTS, SPAN_PACK_NODES,
              SPAN_PACK_EDGES)

SCOPE_INJECT = "ovl.inject"             # cycle stage 1: injection offer
SCOPE_ROUTE = "ovl.route"               # stages 2-3: Hoplite route, cursor
SCOPE_EJECT_FIRE = "ovl.eject_fire"     # stage 4: eject updates, fire, ALU
SCOPE_SELECT = "ovl.select"             # stage 5: scheduler pick
SCOPE_TERMINATE = "ovl.terminate"       # stage 6: done predicate, stats
SCOPE_CHUNK_REPAIR = "ovl.chunk_repair"  # a chunk's cycle and stat repair
DEVICE_SCOPES = (SCOPE_INJECT, SCOPE_ROUTE, SCOPE_EJECT_FIRE, SCOPE_SELECT,
                 SCOPE_TERMINATE, SCOPE_CHUNK_REPAIR)


def __getattr__(name):
    if name == "run":
        from .api import run
        return run
    if name == "service":
        import importlib
        return importlib.import_module(".service", __name__)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
