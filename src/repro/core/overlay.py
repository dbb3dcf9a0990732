"""Cycle-accurate token-dataflow overlay simulator (paper §II), in JAX.

The whole simulation is one compiled XLA program: a ``lax.while_loop`` whose
body advances every PE and every Hoplite router by one cycle. All per-cycle
updates are local to a PE row (the paper's "local graph memory"), which is
what lets :mod:`repro.core.distributed` run the same body under ``shard_map``
with ppermute torus hops.

Timing model (faithful to §II):
  * one packet ejected per PE per cycle, one packet injected per PE per cycle
    (subject to NoC arbitration);
  * ALU latency 1 cycle (single-stage pipelined DSP), folded into fire;
  * scheduler select latency: policy-dependent exposed cycles (see
    ``OverlayConfig.select_latency`` and each policy's ``sel_lat``);
  * Hoplite: 1 cycle per hop, deflection on contention.

Scheduling policy is pluggable: the cycle kernel only talks to the
:class:`repro.core.schedulers.Scheduler` protocol (its fused per-cycle entry
point is ``step`` — select + latency-gated commit, optionally backed by the
Pallas kernels in :mod:`repro.kernels.lod` via
``OverlayConfig(engine="select")``), and the policy's state lives in the
``"sched"`` sub-dict of the simulation state pytree. See
:mod:`repro.core.schedulers` for the registered policies (``ooo``,
``inorder``, ``scan``, ``lru_flat``) and how to add one.

Hot-path engineering (engine-level, never observable in results):
  * *Fused eject application*: each eject port writes its target slot of
    every PE by one one-hot masked dense pass per state array, never a
    scatter (see ``_deliver``), and fire detection stays in gathered
    per-port form; termination tracks a remaining-nodes counter instead of
    reducing the computed plane.
  * *Chunked stepping* (``OverlayConfig.check_every``; autotuned 8-32 from
    graph size, ``1`` = the per-cycle reference engine): ``check_every``
    cycles run back-to-back in a ``lax.scan`` per ``while_loop`` iteration,
    so the termination predicate — and under ``shard_map`` the cross-shard
    psum/pmin — runs once per chunk. A completed overlay is a fixed point of
    the cycle body, so the exact completion cycle is recovered from the
    chunk's per-cycle done trace (see :func:`make_chunk_fn`); results are
    bit-identical for every ``check_every``.

Profiling: the cycle body's stages run under ``jax.named_scope``s
(``ovl.inject``, ``ovl.route``, ``ovl.eject_fire``, ``ovl.select``,
``ovl.terminate``; ``ovl.chunk_repair`` around a chunk's repair), which
only name the compiled ops, and the host steps of an engine call under
``jax.profiler.TraceAnnotation`` spans (``repro.pack``, ``repro.to_device``,
``repro.dispatch``, ``repro.unpack``). The names live in :mod:`repro`.

Three entry points share the same cycle body:
  * :func:`simulate`          — single device, one config;
  * :func:`simulate_batch`    — one device, a *stacked* config axis: the body
    is vmapped so an N-scheduler x M-latency sweep is one XLA program
    instead of N*M serial retraces (Fig. 1-style sweeps);
  * :func:`repro.core.distributed.simulate_sharded` — shard_map over a mesh
    (and :func:`repro.core.distributed.simulate_batch_sharded`, the sharded
    multi-config sweep: vmap inside shard_map).
"""
from __future__ import annotations

import dataclasses
import functools
import warnings
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .. import (
    SCOPE_CHUNK_REPAIR, SCOPE_EJECT_FIRE, SCOPE_INJECT, SCOPE_ROUTE,
    SCOPE_SELECT, SCOPE_TERMINATE, SPAN_DISPATCH, SPAN_PACK, SPAN_TO_DEVICE,
    SPAN_UNPACK,
)
from . import bitvec, noc, schedulers
from .graph import (
    DIV_EPS, OP_ADD, OP_DIV, OP_MUL, OP_NAMES, OP_SUB, DataflowGraph,
)
from .partition import GraphMemory
from .schedulers import row_gather as _row_gather

Shift = Callable[[dict], dict]

#: Chunk execution engines (cycle-exact by construction, see OverlayConfig).
ENGINES = ("jnp", "select")


def alu(opcode, a, b):
    """Vectorized ALU — identical semantics to graph.apply_op (f32)."""
    safe_b = b + jnp.where(b >= 0, jnp.float32(DIV_EPS), jnp.float32(-DIV_EPS))
    return jnp.select(
        [opcode == OP_ADD, opcode == OP_SUB, opcode == OP_MUL, opcode == OP_DIV],
        [a + b, a - b, a * b, a / safe_b],
        jnp.float32(0),
    )


@dataclasses.dataclass(frozen=True)
class OverlayConfig:
    """``select_latency`` models the scheduler pick cost in *exposed* cycles.

    The paper's hierarchical LOD is a deterministic 2-cycle circuit — the
    point of determinism is that the pick pipelines behind the (>=1 cycle)
    fanout drain of the previous node, so its exposed cost equals the FIFO
    pop's: 1 cycle. ``None`` defers to the policy's own default (1 for
    ``ooo``/``inorder``/``lru_flat``; the RDY word count for ``scan``, which
    models the un-pipelined memory sweep the paper rejects). Pass
    ``select_latency=2`` to model an un-pipelined LOD (ablation), or larger
    values to widen the exposed scan cost.

    ``check_every`` is an engine knob, not a model knob: the termination
    predicate (and, sharded, its cross-shard reduction) is evaluated once per
    ``check_every``-cycle chunk instead of once per cycle. Results are
    bit-identical for every value — a completed overlay is a fixed point of
    the cycle function, so the exact completion cycle is recovered from the
    per-cycle done trace recorded inside the chunk. ``None`` autotunes from
    the graph size (8–32); ``1`` forces the legacy cycle-by-cycle reference
    engine.

    ``engine`` picks how a chunk of cycles executes — never *what* it
    computes (the two engines are bit-identical, asserted in tests):

      * ``"jnp"`` (default) — the pure-jnp reference path: one ``lax.scan``
        of the cycle body per chunk;
      * ``"select"`` — the jnp cycle body with the scheduler pick routed
        through the fused Pallas kernels in :mod:`repro.kernels.lod` (one
        VMEM round-trip per pick), for policies that support it.

    On non-TPU backends the Pallas kernels run in interpret mode.

    ``eject_policy`` picks the NoC's single-port eject arbitration:
    ``"n_first"`` (Hoplite's N-beats-W default) or ``"priority"`` (the
    criticality-aware W/N pick — see :func:`repro.core.noc.router_cycle`).
    This IS a model knob: cycle counts change under ``"priority"``.

    ``placement`` names how nodes map onto the PE grid when an engine is
    handed a raw :class:`~repro.core.graph.DataflowGraph` (a
    :class:`repro.place.PlacementSpec`, a strategy name, or ``None`` =
    identity — the partitioner's default round-robin, bit-identical to the
    pre-placement-subsystem engine). Whatever spelling is passed,
    ``__post_init__`` normalizes it through :func:`repro.place.spec.resolve`
    so the stored field is ALWAYS a canonical ``PlacementSpec`` — equal
    layouts hash equal as jit static arguments and service cache keys.
    Ignored when the caller passes an already-packed :class:`GraphMemory`.

    ``telemetry`` opts into the in-engine trace layer (a
    :class:`repro.telemetry.TelemetrySpec` or ``None`` = off, the default):
    cycle-resolved (bucketed) integer traces of per-PE occupancy, per-link
    Hoplite utilization and deflections, eject-port contention, scheduler
    ready-set depth / pick position, and wavefront progress, accumulated
    *inside* the jitted cycle loop under ``state["telem"]``. Telemetry is an
    observer, never a model knob: simulated cycles and stats are bit-
    identical with it on or off, and with ``telemetry=None`` the traced
    program is exactly today's (no extra state, no extra ops). See
    :mod:`repro.telemetry` and docs/telemetry.md.
    """

    scheduler: str = "ooo"           # any name in schedulers.REGISTRY
    select_latency: int | None = None  # exposed cycles; None = policy default
    eject_capacity: int = 1          # 2 == paper §II-C BRAM multipumping
    max_cycles: int = 1_000_000
    check_every: int | None = None   # cycles per termination check; None=auto
    eject_policy: str = "n_first"    # NoC eject arbitration (see noc.py)
    placement: Any = None            # PlacementSpec | strategy name | None
    engine: str = "jnp"              # "jnp" | "select"
    telemetry: Any = None            # TelemetrySpec | None = tracing off

    def __post_init__(self):
        if self.engine not in ENGINES:
            raise ValueError(
                f"engine must be one of {sorted(ENGINES)}, got {self.engine!r}")
        if self.select_latency is not None and self.select_latency < 1:
            raise ValueError(
                f"select_latency must be >= 1 exposed cycle (or None for the "
                f"policy default), got {self.select_latency}")
        if self.check_every is not None and self.check_every < 1:
            raise ValueError(
                f"check_every must be >= 1 cycle per termination check (or "
                f"None to autotune), got {self.check_every}")
        if self.eject_policy not in ("n_first", "priority"):
            raise ValueError(
                f"eject_policy must be 'n_first' or 'priority', got "
                f"{self.eject_policy!r}")
        from ..place.spec import resolve  # lazy: placement specs live in place
        # Store the canonical spec (raises on malformed values): every
        # downstream consumer — jit static-arg caches, batch uniformity
        # checks, service content hashes — sees one spelling per layout.
        object.__setattr__(self, "placement", resolve(self.placement))
        if self.telemetry is not None:
            from ..telemetry.spec import TelemetrySpec  # lazy, like place.spec
            if not isinstance(self.telemetry, TelemetrySpec):
                raise TypeError(
                    f"telemetry must be a repro.telemetry.TelemetrySpec or "
                    f"None, got {type(self.telemetry).__name__}")

    @property
    def sel_lat(self) -> int:
        return 1 if self.select_latency is None else self.select_latency


def resolve_check_every(cfg: OverlayConfig, nx: int, ny: int, L: int, *,
                        backend: str | None = None,
                        num_devices: int = 1) -> int:
    """Static chunk length for the stepping engine. Any value is cycle-exact;
    the autotune only trades per-chunk overhead against wasted tail cycles
    (up to K-1 extra cycle evaluations after completion).

    Keyed on graph size AND execution target AND engine path:
      * single-device CPU — grows with the slot count (bigger graphs run
        long enough to amortize deep chunks): 8 / 16 / 32;
      * multi-device mesh (``num_devices > 1``) — the chunk also amortizes
        the per-check cross-shard psum/pmin, which dominates regardless of
        graph size (~1.5x on an 8-device CPU mesh): always 32;
      * single-device TPU — the compiled chunk body is cheap relative to the
        host-visible while_loop predicate sync: at least 16;
      * ``engine="select"`` — one Pallas select dispatch per *cycle*; a
        deeper chunk keeps more of them inside one while-loop iteration:
        at least 16.

    ``backend`` defaults to ``jax.default_backend()`` at trace time.
    """
    if cfg.check_every is not None:
        return cfg.check_every
    if num_devices > 1:
        return 32
    slots = nx * ny * L
    base = 8 if slots <= 4_096 else (16 if slots <= 65_536 else 32)
    if cfg.engine == "select":
        base = max(base, 16)
    backend = backend or jax.default_backend()
    if backend == "tpu":
        return max(base, 16)
    return base


#: Width of a node word's opcode field: the bits of the largest opcode.
OPCODE_BITS = max(OP_NAMES).bit_length()


class DeviceGraph(dict):
    """GraphMemory as jnp arrays reshaped to [nx, ny, ...].

    ``opcode``, ``fanin``, ``init_value``, ``fo_count`` and ``valid`` are the
    node records that ``init_state`` and the schedulers' ``init`` read. The
    cycle body reads the static tables as two packed int32 words instead,
    one gather per index for all the fields it needs there (the paper's PE
    likewise reads one node record per access):

      * ``e_word`` [nx, ny, emax], read at the fanout cursor:
        ``((dst_pe * L + dst_slot) * 2 + last) * 2 + opidx``, with
        ``dst_pe`` the global PE index and ``last`` set on the final edge of
        its source node's fanout list. Padded columns are 0. Fits while
        ``4 * P * L <= 2**31`` (P PEs of L slots).
      * ``n_word`` [nx, ny, L], read at the ejected and the picked slot:
        ``(fo_base * 2**OPCODE_BITS + opcode) * 2 + has_fanout``, with
        ``has_fanout = fo_count > 0``. Fits while
        ``(emax + 1) * 2**(OPCODE_BITS + 1) <= 2**31``, since a node's
        ``fo_base`` is at most ``emax``.

    :func:`edge_fields` and :func:`node_fields` decode them.
    """


def check_word_limits(num_pes: int, lmax: int, emax: int) -> None:
    """Raise ``ValueError`` unless both packed words of a ``num_pes`` x
    ``lmax``-slot graph memory with ``emax`` edges per PE fit in int32."""
    if 4 * num_pes * lmax > 2**31:
        raise ValueError(
            f"edge word overflows int32: {num_pes} PEs x {lmax} slots = "
            f"{num_pes * lmax} slots, the limit is 2**29")
    if (emax + 1) << (OPCODE_BITS + 1) > 2**31:
        raise ValueError(
            f"node word overflows int32: emax = {emax} edges per PE, the "
            f"limit is 2**{30 - OPCODE_BITS} - 1 with {OPCODE_BITS} opcode "
            f"bits")


def edge_word(gm: GraphMemory) -> np.ndarray:
    """[P, emax] int32 edge words of ``gm`` (see :class:`DeviceGraph`).

    In place on one int32 copy, and ``last`` by one store at the flat
    column of each fanout list's final edge: this runs on the host for
    every query."""
    last = np.zeros(gm.e_dst_pe.shape, bool)
    end = gm.fo_base + gm.fo_count - 1 \
        + (np.arange(gm.num_pes) * gm.emax)[:, None]
    last.ravel()[end[gm.fo_count > 0]] = True
    word = gm.e_dst_pe.astype(np.int32)
    word *= gm.lmax
    word += gm.e_dst_slot
    word <<= 1
    word += last
    word <<= 1
    word += gm.e_dst_opidx
    return word


def node_word(gm: GraphMemory) -> np.ndarray:
    """[P, lmax] int32 node words of ``gm`` (see :class:`DeviceGraph`)."""
    word = gm.fo_base.astype(np.int32)
    word <<= OPCODE_BITS
    word += gm.opcode
    word <<= 1
    word += gm.fo_count > 0
    return word


def edge_fields(word, L: int) -> dict:
    """Decode edge words (numpy or jnp) of a graph memory with ``L`` slots
    per PE: ``dst_pe``, ``dst_slot``, ``last`` (bool) and ``opidx``."""
    return dict(dst_pe=(word >> 2) // L, dst_slot=(word >> 2) % L,
                last=(word & 2) != 0, opidx=word & 1)


def node_fields(word) -> dict:
    """Decode node words (numpy or jnp): ``fo_base``, ``opcode`` and
    ``has_fanout`` (bool)."""
    return dict(fo_base=word >> (OPCODE_BITS + 1),
                opcode=(word >> 1) & ((1 << OPCODE_BITS) - 1),
                has_fanout=(word & 1) != 0)


def device_graph(gm: GraphMemory) -> DeviceGraph:
    nx, ny = gm.nx, gm.ny
    check_word_limits(gm.num_pes, gm.lmax, gm.emax)
    r3 = lambda a: jnp.asarray(a).reshape(nx, ny, -1)
    return DeviceGraph(
        opcode=r3(gm.opcode).astype(jnp.int32),
        fanin=r3(gm.fanin).astype(jnp.int32),
        init_value=r3(gm.init_value),
        fo_count=r3(gm.fo_count).astype(jnp.int32),
        valid=r3(gm.valid),
        n_word=r3(node_word(gm)),
        e_word=r3(edge_word(gm)),
    )


def _resolve(cfg: OverlayConfig, scheduler: schedulers.Scheduler | None):
    return scheduler if scheduler is not None else schedulers.get(cfg.scheduler)


# ---------------------------------------------------------------------------
# Stat-counter registry. Each entry is a monotone int32 *scalar* counter in
# the simulation state: zero-initialized, incremented by the cycle body with
# a cross-shard ``all_reduce`` on the per-cycle delta, and repaired once per
# chunk as ``start + all_reduce(end - start)`` (see make_chunk_fn). Because
# a completed overlay is a fixed point of the cycle body, every registered
# counter's increment must be zero once ``done`` holds — that is what lets
# the guard-free chunk engines over-simulate past completion without drift.
# Register new counters here (telemetry, future schedulers); the chunk
# repair, init_state and the sharded engines all
# iterate the registry, so no repair code needs editing.
# ---------------------------------------------------------------------------

STAT_COUNTERS: dict[str, str] = {}


def register_counter(name: str, doc: str = "") -> None:
    """Add a monotone scalar int32 stat counter to every engine's state."""
    if name in STAT_COUNTERS:
        raise ValueError(f"duplicate stat counter {name!r}")
    STAT_COUNTERS[name] = doc


for _name, _doc in (
    ("delivered", "packets ejected into a PE's local memory"),
    ("noc_deflections", "route-contention deflections: in-flight S-turn "
     "losers plus blocked PE injections (away from the destination)"),
    ("eject_deflections", "eject-port losers at the destination router, "
     "sent around the ring again"),
    ("deflections", "noc_deflections + eject_deflections (back-compat sum)"),
    ("busy_cycles", "node fires summed over PEs and cycles"),
    ("inject_blocked", "PE-cycles in which an active node offered a packet "
     "and its router refused it: the PE-injection part of noc_deflections, "
     "what a long fanout drain waits on"),
):
    register_counter(_name, _doc)


def stat_keys(state: dict) -> tuple[str, ...]:
    """Registered counters present in ``state``, in registration order —
    the keys the chunk repair stacks into its one-collective stat block."""
    return tuple(k for k in STAT_COUNTERS if k in state)


def init_state(g: DeviceGraph, cfg: OverlayConfig,
               scheduler: schedulers.Scheduler | None = None):
    """Policy-agnostic simulation state. Scheduler state is namespaced under
    ``state["sched"]``; the exposed select latency rides along as the
    ``state["sel_lat"]`` scalar so the batched engine can vmap over it."""
    sched = _resolve(cfg, scheduler)
    nx, ny, L = g["opcode"].shape
    is_input = (g["fanin"] == 0) & g["valid"]
    computed = is_input
    value = jnp.where(is_input, g["init_value"], 0.0)
    lat = sched.sel_lat(cfg, L // bitvec.FLAGS_PER_WORD)

    state = dict(
        pending=g["fanin"].astype(jnp.int32),
        operands=jnp.zeros((nx, ny, L, 2), jnp.float32),
        computed=computed,
        value=value,
        remaining=(g["valid"] & ~computed).sum().astype(jnp.int32),
        sched=sched.init(g, cfg),
        active=jnp.full((nx, ny), -1, jnp.int32),
        cursor=jnp.zeros((nx, ny), jnp.int32),
        sel_lat=jnp.int32(lat),
        sel_wait=jnp.full((nx, ny), lat - 1, jnp.int32),
        link_e=noc.empty_packets(nx, ny),
        link_s=noc.empty_packets(nx, ny),
        cycle=jnp.int32(0),
        done=jnp.bool_(False),
        **{k: jnp.int32(0) for k in STAT_COUNTERS},
    )
    if cfg.telemetry is not None:
        from ..telemetry import trace as telemetry_trace  # lazy, like place

        state["telem"] = telemetry_trace.init(cfg.telemetry, nx, ny)
    return state


def _deliver(operands, pending, valid, slot, opidx, val):
    """Apply the eject ports' packets: each adds its value into its
    (slot, opidx) operand cell and counts its slot's pending operands down.

    Port arrays are [E, nx, ny], ``slot`` and ``opidx`` already clipped.
    Each port writes by one masked dense pass over the slot axis, one-hot at
    its slot, in port order: on a TPU the loop state sits in VMEM and a pass
    over a whole [nx, ny, L] array costs less than a scatter of one element
    per PE, whose cost is per index. The cells written are exactly a point
    update's: a port with no packet adds zero to its slot, and every other
    cell is left as it was.
    """
    slots = jnp.arange(pending.shape[-1], dtype=jnp.int32)
    sides = jnp.arange(2, dtype=jnp.int32)
    for e in range(valid.shape[0]):
        hit = slots == slot[e][..., None]
        cell = hit[..., None] & (sides == opidx[e][..., None, None])
        add = jnp.where(valid[e], val[e], 0.0)[..., None, None]
        operands = jnp.where(cell, operands + add, operands)
        drop = valid[e].astype(jnp.int32)[..., None]
        pending = jnp.where(hit, pending - drop, pending)
    return operands, pending


def _record_fires(value, computed, slot, fired, fval):
    """Add each port's fired value into its slot and mark the slot computed,
    by the same one-hot dense passes as :func:`_deliver`."""
    slots = jnp.arange(value.shape[-1], dtype=jnp.int32)
    for e in range(slot.shape[0]):
        hit = slots == slot[e][..., None]
        add = jnp.where(fired[e], fval[e], 0.0)[..., None]
        value = jnp.where(hit, value + add, value)
        computed = computed | (hit & fired[e][..., None])
    return value, computed


def make_cycle_fn(
    g: DeviceGraph,
    cfg: OverlayConfig,
    *,
    scheduler: schedulers.Scheduler | None = None,
    shift_e: Shift = noc.roll_shift_e,
    shift_s: Shift = noc.roll_shift_s,
    all_reduce: Callable[[Any], Any] = lambda x: x,
    x0=0,
    y0=0,
    global_ny: int | None = None,
):
    """Build the one-cycle transition function. ``all_reduce`` reduces scalar
    termination predicates across shards (identity on a single device);
    ``x0``/``y0``/``global_ny`` supply global router coordinates when the PE
    grid is sharded (see core.distributed)."""
    sched = _resolve(cfg, scheduler)
    _, ny, L = g["opcode"].shape
    ny_i32 = jnp.int32(global_ny if global_ny is not None else ny)
    telem_spec = cfg.telemetry
    if telem_spec is not None:
        from ..telemetry import trace as telemetry_trace  # lazy, like place

    def cycle(s):
        with jax.named_scope(SCOPE_INJECT):
            # ---- 1. offer injection packet from the active node's fanout
            # cursor. One gather of the edge word (see DeviceGraph) gives
            # the packet's destination PE, slot and operand side, and
            # whether it is the node's last edge. A node with no fanout is
            # never enqueued, so an active node always has an edge to send.
            inj_valid = s["active"] >= 0
            edge = edge_fields(_row_gather(g["e_word"], s["cursor"]), L)
            inject = dict(
                valid=inj_valid,
                dst_x=edge["dst_pe"] // ny_i32,
                dst_y=edge["dst_pe"] % ny_i32,
                dst_slot=edge["dst_slot"],
                opidx=edge["opidx"],
                value=_row_gather(s["value"], s["active"]),
            )

        with jax.named_scope(SCOPE_ROUTE):
            # ---- 2. NoC cycle
            link_e, link_s, ejects, accepted, deflected = noc.router_cycle(
                s["link_e"], s["link_s"], inject, shift_e=shift_e,
                shift_s=shift_s, x0=x0, y0=y0,
                eject_capacity=cfg.eject_capacity,
                eject_policy=cfg.eject_policy,
            )

            # ---- 3. advance fanout cursor; retire drained nodes: a node is
            # drained the cycle its router accepts the edge word's ``last``
            # edge
            cursor = s["cursor"] + accepted.astype(jnp.int32)
            drained = inj_valid & accepted & edge["last"]
            active = jnp.where(drained, -1, s["active"])
            sel_wait = jnp.where(drained, s["sel_lat"] - 1, s["sel_wait"])

        with jax.named_scope(SCOPE_EJECT_FIRE):
            # ---- 4. apply ejected packets, fused across the eject ports,
            # then fire. Every write lands in the one slot a port targets
            # on each PE (see _deliver), and fire detection stays in
            # gathered [E, nx, ny] form. Exactness relies on the
            # graph-memory invariants that each (pe, slot, opidx) operand
            # cell receives exactly one packet over the whole run (fanin
            # semantics) and each slot fires at most once, so adding into
            # the zero-initialized cells equals the sequential writes of a
            # per-port loop.
            sched_st = s["sched"]

            ej_valid = jnp.stack([e["valid"] for e in ejects])  # [E,nx,ny]
            ej_slot = jnp.clip(jnp.stack([e["dst_slot"] for e in ejects]),
                               0, L - 1)
            ej_op = jnp.clip(jnp.stack([e["opidx"] for e in ejects]), 0, 1)
            ej_val = jnp.stack([e["value"] for e in ejects])
            E = len(ejects)
            operands, pending = _deliver(s["operands"], s["pending"],
                                         ej_valid, ej_slot, ej_op, ej_val)

            # A slot fires the cycle a delivery drops its pending count to
            # zero. Gathered at each port's own target slot; when two ports
            # hit the same slot in one cycle both see the post-decrement
            # count, so the first port claims the fire (same single fire,
            # same operands and value, as the sequential loop).
            new_pend = [_row_gather(pending, ej_slot[e]) for e in range(E)]
            fired = []
            for e in range(E):
                f = (ej_valid[e] & (new_pend[e] == 0)
                     & ~_row_gather(s["computed"], ej_slot[e]))
                for prev in range(e):
                    f = f & ~(fired[prev] & (ej_slot[prev] == ej_slot[e]))
                fired.append(f)
            fired = jnp.stack(fired)

            opnds = jnp.stack([_row_gather(operands, ej_slot[e])
                               for e in range(E)])  # [E,nx,ny,2]
            # One node-word gather per port for its opcode and fanout test.
            node = [node_fields(_row_gather(g["n_word"], ej_slot[e]))
                    for e in range(E)]
            opc = jnp.stack([n["opcode"] for n in node])
            fval = alu(opc, opnds[..., 0], opnds[..., 1])
            value, computed = _record_fires(s["value"], s["computed"],
                                            ej_slot, fired, fval)

            # Enqueue fired nodes in eject-port order (per-PE FIFO arrival
            # semantics are exactly the sequential loop's).
            for e in range(E):
                ready_e = fired[e] & node[e]["has_fanout"]
                sched_st = sched.on_ready(sched_st, ej_slot[e], ready_e)

            n_delivered = ej_valid.sum().astype(jnp.int32)
            n_fired = fired.sum().astype(jnp.int32)

        with jax.named_scope(SCOPE_SELECT):
            # ---- 5. scheduler: select (and consume) the next node on idle
            # PEs
            idle = active < 0
            gate = idle & (sel_wait == 0)
            if telem_spec is not None and telem_spec.sched:
                # Ready-set depth as the scheduler sees it at pick time:
                # after this cycle's fires enqueued, before the pick consumes.
                rdy_depth = sched.ready_depth(sched_st)
            cand, have, sched_st = sched.step(
                sched_st, idle, gate, use_pallas=cfg.engine == "select")
            can_wait = idle & have & (sel_wait > 0)
            sel_wait = jnp.where(can_wait, sel_wait - 1, sel_wait)
            sel = gate & have

            active = jnp.where(sel, cand, active)
            new_base = node_fields(_row_gather(g["n_word"], cand))["fo_base"]
            cursor = jnp.where(sel, new_base, cursor)

        with jax.named_scope(SCOPE_TERMINATE):
            # ---- 6. termination + stats. ``remaining`` counts local
            # uncomputed valid nodes so the all-computed predicate is O(1)
            # per cycle instead of an O(slots) reduction over the computed
            # plane.
            remaining = s["remaining"] - n_fired
            all_computed = all_reduce(remaining == 0)
            no_ready = all_reduce(sched.empty(sched_st))
            no_active = all_reduce((active < 0).all())
            links_idle = all_reduce(noc.links_empty(link_e, link_s))
            done = all_computed & no_ready & no_active & links_idle

            # Deflections, split by cause (see noc.router_cycle): a blocked
            # PE injection keeps the packet circulating in the PE just as a
            # lost S-turn keeps it circulating on the ring, so both count as
            # NoC (route-contention) deflections; eject-port losers count
            # separately. ``deflections`` stays their sum — bit-exactly the
            # pre-split stat.
            inj_blocked = inj_valid & ~accepted
            n_blocked = inj_blocked.sum().astype(jnp.int32)
            d_noc = all_reduce(
                n_blocked + deflected["noc"].sum()).astype(jnp.int32)
            d_ej = all_reduce(deflected["eject"].sum()).astype(jnp.int32)

            out = dict(
                pending=pending, operands=operands, computed=computed,
                value=value,
                remaining=remaining,
                sched=sched_st,
                active=active, cursor=cursor,
                sel_lat=s["sel_lat"], sel_wait=sel_wait,
                link_e=link_e, link_s=link_s,
                cycle=s["cycle"] + 1,
                delivered=s["delivered"]
                + all_reduce(n_delivered).astype(jnp.int32),
                noc_deflections=s["noc_deflections"] + d_noc,
                eject_deflections=s["eject_deflections"] + d_ej,
                deflections=s["deflections"] + d_noc + d_ej,
                busy_cycles=s["busy_cycles"]
                + all_reduce(n_fired).astype(jnp.int32),
                inject_blocked=s["inject_blocked"] + all_reduce(n_blocked),
                done=done,
            )
        if telem_spec is not None:
            # Observer only: every input below is shard-local and already
            # computed by the model above; nothing feeds back into it.
            out["telem"] = telemetry_trace.accumulate(
                telem_spec, s["telem"],
                cycle=s["cycle"],
                fired=fired.sum(axis=0).astype(jnp.int32),
                occupied=(s["active"] >= 0),
                link_e_busy=link_e["valid"],
                link_s_busy=link_s["valid"],
                defl_noc=deflected["noc"] + inj_blocked.astype(jnp.int32),
                defl_eject=deflected["eject"],
                eject_grant=ej_valid.sum(axis=0).astype(jnp.int32),
                ready_depth=rdy_depth if telem_spec.sched else None,
                sel=sel, cand=cand,
                no_ready=idle & ~have,
                inj_blocked=inj_blocked,
                sel_waiting=can_wait,
            )
        return out

    return cycle


def repair_telemetry(telem: dict, overshoot):
    """Undo the only telemetry increment that is NOT zero at the completed-
    overlay fixed point: once every PE is idle with an empty ready set,
    ``stall_no_ready`` gains 1 per PE per over-simulated cycle inside a
    guard-free chunk. ``overshoot`` is the chunk's over-simulated cycle count
    (``end_cycle - repaired_cycle``: 0 while running, K - first - 1 when the
    run completes in-chunk, K for an already-done element re-entering). Every
    other trace leaf's increment vanishes at the fixed point (no fires, empty
    links, no packets, empty ready sets, no picks), so chunk overshoot never
    touches it — asserted against check_every=1 in tests/test_telemetry.py.
    """
    if "stall_no_ready" not in telem:
        return telem
    out = dict(telem)
    over = jnp.asarray(overshoot, jnp.int32)
    out["stall_no_ready"] = telem["stall_no_ready"] - over.reshape(
        over.shape + (1, 1))
    return out


def make_chunk_fn(cycle_fn, check_every: int,
                  all_reduce: Callable[[Any], Any] = lambda x: x):
    """Wrap ``check_every`` cycles of ``cycle_fn`` into one chunk step.

    ``cycle_fn`` must be built with the *identity* all_reduce: inside the
    chunk every termination predicate and stat increment stays shard-local,
    and the cross-shard reduction (``all_reduce``) runs once per chunk — on
    the stacked per-cycle done trace and on the chunk's stat deltas — instead
    of ~7 collectives per cycle.

    The chunk body is deliberately guard-free (no per-cycle freeze, no
    branch): a completed overlay is a *fixed point* of ``cycle_fn`` (no
    ready nodes, no active fanout drains, empty links), so cycles simulated
    past completion change nothing but the cycle counter, and the counter is
    repaired afterwards from the first globally-done entry of the per-cycle
    trace. The ``max_cycles`` budget is enforced by the *caller*: only enter
    a chunk when every still-running element has at least ``check_every``
    cycles of budget left, and finish the tail with the per-cycle engine
    (see ``_run_jit``). That keeps the hot path exactly ``check_every``
    back-to-back cycle evaluations.
    """

    def chunk(s):
        keys = stat_keys(s)
        with jax.named_scope(SCOPE_CHUNK_REPAIR):
            start_stats = jnp.stack([s[k] for k in keys])
        start_cycle = s["cycle"]
        start_done = s["done"]  # already-finished batch elements re-enter

        def body(c, _):
            c = cycle_fn(c)
            return c, c["done"]

        s2, done_trace = jax.lax.scan(body, s, None, length=check_every)

        with jax.named_scope(SCOPE_CHUNK_REPAIR):
            done_trace = all_reduce(done_trace)            # one collective
            any_done = done_trace.any()
            first = jnp.argmax(done_trace).astype(jnp.int32)
            cycle = jnp.where(
                start_done, start_cycle,
                jnp.where(any_done, start_cycle + first + 1, s2["cycle"]))

            end_stats = jnp.stack([s2[k] for k in keys])
            stats = start_stats + all_reduce(end_stats - start_stats)

            out = dict(s2, done=any_done, cycle=cycle)
            for i, k in enumerate(keys):
                out[k] = stats[i]
            if "telem" in out:
                out["telem"] = repair_telemetry(out["telem"],
                                                s2["cycle"] - cycle)
        return out

    return chunk


@dataclasses.dataclass
class SimResult:
    cycles: int
    done: bool
    values: np.ndarray        # [N] node values in global id order
    delivered: int
    deflections: int          # noc_deflections + eject_deflections
    busy_cycles: int
    noc_deflections: int = 0
    eject_deflections: int = 0
    #: PE-cycles an offered injection was refused (part of noc_deflections).
    inject_blocked: int = 0
    #: repro.telemetry.TelemetryResult when the config carried a
    #: TelemetrySpec, else None.
    telemetry: Any = None


@functools.partial(jax.jit, static_argnames=("cfg", "nx", "ny"))
def _run_jit(g: dict, cfg: OverlayConfig, nx: int, ny: int):
    state = init_state(g, cfg)
    cycle_fn = make_cycle_fn(g, cfg)
    K = resolve_check_every(cfg, nx, ny, g["opcode"].shape[2])

    def cond(s):
        return (~s["done"]) & (s["cycle"] < cfg.max_cycles)

    if K > 1:
        # Chunked phase: K back-to-back cycles per termination check, entered
        # only while a full chunk fits the budget (so no freeze guard is
        # needed inside); the per-cycle loop below finishes the < K tail.
        chunk = make_chunk_fn(cycle_fn, K)
        state = jax.lax.while_loop(
            lambda s: (~s["done"]) & (s["cycle"] + K <= cfg.max_cycles),
            chunk, state)
    final = jax.lax.while_loop(cond, cycle_fn, state)
    return final


def _unpack_result(final, gm: GraphMemory, b: int | None = None,
                   cfg: OverlayConfig | None = None) -> SimResult:
    pick = (lambda a: a[b]) if b is not None else (lambda a: a)
    value = np.asarray(pick(final["value"])).reshape(gm.num_pes, gm.lmax)
    telemetry = None
    if "telem" in final and cfg is not None and cfg.telemetry is not None:
        from ..telemetry.result import TelemetryResult  # lazy, like place

        telemetry = TelemetryResult(
            spec=cfg.telemetry,
            traces={k: np.asarray(pick(v))
                    for k, v in final["telem"].items()},
            cycles=int(pick(final["cycle"])),
            nx=gm.nx, ny=gm.ny,
        )
    return SimResult(
        cycles=int(pick(final["cycle"])),
        done=bool(pick(final["done"])),
        values=value[gm.node_pe, gm.node_slot],
        delivered=int(pick(final["delivered"])),
        deflections=int(pick(final["deflections"])),
        busy_cycles=int(pick(final["busy_cycles"])),
        noc_deflections=int(pick(final["noc_deflections"])),
        eject_deflections=int(pick(final["eject_deflections"])),
        inject_blocked=int(pick(final["inject_blocked"])),
        telemetry=telemetry,
    )


def _as_memory(gm, cfg: OverlayConfig, nx: int | None, ny: int | None):
    """Accept a packed GraphMemory or a raw DataflowGraph (+ grid shape).

    A raw graph is placed according to ``cfg.placement`` (identity default)
    with the memory layout the scheduler prefers — the placement subsystem's
    integration point into every engine."""
    if isinstance(gm, GraphMemory):
        return gm
    if isinstance(gm, DataflowGraph):
        if nx is None or ny is None:
            raise ValueError(
                "simulating a raw DataflowGraph needs the PE grid: "
                "pass nx= and ny=")
        from ..place.api import graph_memory_for_config

        with jax.profiler.TraceAnnotation(SPAN_PACK):
            return graph_memory_for_config(gm, nx, ny, cfg)
    raise TypeError(f"expected GraphMemory or DataflowGraph, got {type(gm)}")


def _simulate(gm: GraphMemory | DataflowGraph,
              cfg: OverlayConfig | None = None,
              *, nx: int | None = None, ny: int | None = None) -> SimResult:
    """Run the overlay to completion on a single device.

    Accepts a packed :class:`GraphMemory`, or a raw
    :class:`~repro.core.graph.DataflowGraph` plus ``nx``/``ny`` — the graph
    is then placed per ``cfg.placement`` (see :mod:`repro.place`).

    Internal engine behind :func:`repro.run`; the public entry point is the
    dispatcher, not this function.
    """
    cfg = cfg or OverlayConfig()
    gm = _as_memory(gm, cfg, nx, ny)
    with jax.profiler.TraceAnnotation(SPAN_TO_DEVICE):
        g = device_graph(gm)
    with jax.profiler.TraceAnnotation(SPAN_DISPATCH):
        final = _run_jit(dict(g), cfg, gm.nx, gm.ny)
    with jax.profiler.TraceAnnotation(SPAN_UNPACK):
        return _unpack_result(final, gm, cfg=cfg)


def simulate(gm: GraphMemory | DataflowGraph, cfg: OverlayConfig | None = None,
             *, nx: int | None = None, ny: int | None = None) -> SimResult:
    """DEPRECATED: use :func:`repro.run` (same arguments, same result)."""
    warnings.warn(
        "overlay.simulate is deprecated; use repro.run(gm, cfg, nx=, ny=)",
        DeprecationWarning, stacklevel=2)
    return _simulate(gm, cfg, nx=nx, ny=ny)


# ---------------------------------------------------------------------------
# Batched sweep engine: one XLA program for an entire config sweep.
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("cfg", "names", "nx", "ny"))
def _run_batch_jit(g: dict, cfg: OverlayConfig, names: tuple[str, ...],
                   policy_ids, sel_lats, max_cycs, nx: int, ny: int):
    sched = schedulers.BatchedScheduler(names)

    def init_one(pid, lat):
        s = init_state(g, cfg, scheduler=sched)
        s["sched"]["policy_id"] = pid
        s["sel_lat"] = lat
        s["sel_wait"] = jnp.full_like(s["sel_wait"], lat - 1)
        return s

    state = jax.vmap(init_one)(policy_ids, sel_lats)
    cycle_fn = make_cycle_fn(g, cfg, scheduler=sched)
    nx_, ny_, L = g["opcode"].shape
    K = resolve_check_every(cfg, nx_, ny_, L)
    vcycle = jax.vmap(cycle_fn)

    def cond(s):
        return ((~s["done"]) & (s["cycle"] < max_cycs)).any()

    def freeze_body(s):
        new = vcycle(s)
        halted = s["done"] | (s["cycle"] >= max_cycs)

        def freeze(old, upd):
            d = halted.reshape(halted.shape + (1,) * (old.ndim - 1))
            return jnp.where(d, old, upd)

        # Batch elements that finished (or exhausted their own cycle budget)
        # stop evolving, so each element's final cycle count and done flag
        # are exactly what a solo run with the same config would report.
        return jax.tree.map(freeze, s, new)

    if K > 1:
        # Chunked phase, vmapped whole: guard-free K-cycle chunks run while
        # every still-running element has a full chunk of budget left
        # (completed elements are fixed points and get their cycle counter
        # repaired from their own done trace — see make_chunk_fn); the
        # per-cycle freeze body then finishes the heterogeneous tail.
        vchunk = jax.vmap(make_chunk_fn(cycle_fn, K))

        def chunk_cond(s):
            running = (~s["done"]) & (s["cycle"] < max_cycs)
            # Any unfinished element without a full chunk of budget left —
            # including one already AT its budget, which is not a fixed
            # point — must force the exit to the freezing per-cycle tail.
            overruns = (~s["done"]) & (s["cycle"] + K > max_cycs)
            return running.any() & ~overruns.any()

        state = jax.lax.while_loop(chunk_cond, vchunk, state)

    return jax.lax.while_loop(cond, freeze_body, state)


def _simulate_batch(gm: GraphMemory | DataflowGraph,
                    cfgs: Sequence[OverlayConfig], *,
                    nx: int | None = None,
                    ny: int | None = None) -> list[SimResult]:
    """Run one overlay graph under many configs as a single XLA program.

    The cycle body is vmapped over a stacked config axis (policy id, exposed
    select latency, cycle budget), so a Fig. 1-style N-scheduler x M-latency
    sweep compiles once instead of retracing per config. Batch elements that
    finish — or exhaust their own ``max_cycles`` — freeze in place, so every
    returned result is identical to a serial :func:`simulate` call with the
    same config. Requirements: all configs share ``eject_capacity``,
    ``eject_policy``, ``engine``, ``placement`` and ``telemetry`` (they
    change the traced structure / the packed memory image).

    A raw :class:`~repro.core.graph.DataflowGraph` (plus ``nx``/``ny``) is
    placed per the shared ``placement`` before the sweep.
    """
    cfgs = list(cfgs)
    if not cfgs:
        return []
    eject = {c.eject_capacity for c in cfgs}
    if len(eject) != 1:
        raise ValueError(f"simulate_batch needs a uniform eject_capacity, got {eject}")
    policy = {c.eject_policy for c in cfgs}
    if len(policy) != 1:
        raise ValueError(f"simulate_batch needs a uniform eject_policy, got {policy}")
    engines = {c.engine for c in cfgs}
    if len(engines) != 1:
        raise ValueError(
            f"simulate_batch needs a uniform engine "
            f"('jnp' | 'select'), got {engines}")
    placements = {c.placement for c in cfgs}
    if len(placements) != 1:
        raise ValueError(
            f"simulate_batch needs a uniform placement, got {placements}")
    telems = {c.telemetry for c in cfgs}
    if len(telems) != 1:
        raise ValueError(
            f"simulate_batch needs a uniform telemetry spec (it shapes the "
            f"traced state), got {telems}")
    if not isinstance(gm, GraphMemory):
        # The packed memory image is shared across the batch, so every
        # scheduler must want the same slot layout — otherwise elements would
        # silently diverge from their serial runs. Group configs by layout
        # (as benchmarks/fig1 does) or pass a pre-built GraphMemory.
        wants = {schedulers.get(c.scheduler).wants_criticality_order
                 for c in cfgs}
        if len(wants) != 1:
            raise ValueError(
                "simulate_batch over a raw DataflowGraph needs schedulers "
                "with a uniform wants_criticality_order; group configs by "
                "memory layout or pass a pre-built GraphMemory")
    gm = _as_memory(gm, cfgs[0], nx, ny)
    names: list[str] = []
    for c in cfgs:
        schedulers.get(c.scheduler)  # validate early
        if c.scheduler not in names:
            names.append(c.scheduler)

    base = dataclasses.replace(
        cfgs[0], scheduler=names[0], select_latency=None,
        max_cycles=max(c.max_cycles for c in cfgs))
    with jax.profiler.TraceAnnotation(SPAN_TO_DEVICE):
        g = device_graph(gm)
        num_words = g["opcode"].shape[2] // bitvec.FLAGS_PER_WORD
        policy_ids = jnp.asarray([names.index(c.scheduler) for c in cfgs],
                                 jnp.int32)
        sel_lats = jnp.asarray(
            [schedulers.get(c.scheduler).sel_lat(c, num_words) for c in cfgs],
            jnp.int32)
        max_cycs = jnp.asarray([c.max_cycles for c in cfgs], jnp.int32)

    with jax.profiler.TraceAnnotation(SPAN_DISPATCH):
        final = _run_batch_jit(dict(g), base, tuple(names), policy_ids,
                               sel_lats, max_cycs, gm.nx, gm.ny)
    with jax.profiler.TraceAnnotation(SPAN_UNPACK):
        return [_unpack_result(final, gm, b, cfg=base)
                for b in range(len(cfgs))]


def simulate_batch(gm: GraphMemory | DataflowGraph,
                   cfgs: Sequence[OverlayConfig], *,
                   nx: int | None = None,
                   ny: int | None = None) -> list[SimResult]:
    """DEPRECATED: use :func:`repro.run` with ``batch=cfgs``."""
    warnings.warn(
        "overlay.simulate_batch is deprecated; use "
        "repro.run(gm, batch=cfgs, nx=, ny=)",
        DeprecationWarning, stacklevel=2)
    return _simulate_batch(gm, cfgs, nx=nx, ny=ny)
