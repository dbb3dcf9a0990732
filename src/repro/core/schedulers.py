"""Pluggable ready-node scheduler policies for the overlay simulator.

The paper's core contribution is a *scheduler policy* choice — FIFO FCFS vs.
tagged leading-one-detect out-of-order — but richer policies (and the naive
ones the paper rejects) are needed for ablations. This module extracts the
policy behind a small protocol so the cycle kernel in
:mod:`repro.core.overlay` stays policy-agnostic:

  * ``init(g, cfg)``                      -> per-PE scheduler state pytree
  * ``on_ready(st, slot, ready)``         -> mark ``slot`` ready where ``ready``
  * ``select(st, idle)``                  -> (candidate slot, have) per PE
  * ``commit(st, sel, cand)``             -> consume the candidate where ``sel``
  * ``empty(st)``                         -> scalar bool: no node is queued
  * ``ready_depth(st)``                   -> [nx, ny] queued-ready count (the
    :mod:`repro.telemetry` probe; never called unless tracing is on)
  * ``sel_lat(cfg, num_words)``           -> exposed select latency (cycles)

The cycle kernel drives one fused entry point per cycle,
``step(st, idle, gate, use_pallas=...) -> (cand, have, st)``; the base class
composes ``select`` + ``commit`` so policies only implement the hooks above,
while ``ooo``/``scan``/``lru_flat`` override it to route the pick + RDY
clear through the fused Pallas kernels (:mod:`repro.kernels.lod`) when
``OverlayConfig(engine="select")``.

All hooks are pure jnp functions of [nx, ny, ...] arrays, so every policy
works unchanged under ``jax.jit``, ``shard_map`` (state is local to a PE row)
and ``jax.vmap`` (the batched sweep engine, see
:func:`repro.core.overlay.simulate_batch`). A hook that writes one entry per
PE (an RDY flag, a FIFO cell) does it by a masked dense pass over the PE's
row, one-hot at the entry, never by a scatter (see :func:`bitvec.slot_bits`).

Registered policies:

  * ``ooo``      — packed RDY bit-flags + hierarchical OuterLOD/InnerLOD pick;
                   with criticality-ordered local memory the pick is the most
                   critical ready node (the paper's contribution).
  * ``inorder``  — FIFO in arrival order (FCFS), the prior-TDP baseline.
  * ``scan``     — the naive non-deterministic memory scan the paper rejects:
                   a rotating pointer walks the RDY vector, so the exposed
                   pick latency defaults to the word count of the scanned
                   memory (configurable via ``cfg.select_latency``).
  * ``lru_flat`` — single-level (flat) LOD with rotating least-recently-
                   granted priority and no criticality exploitation: the
                   1-cycle "fair arbiter" ablation point between ``scan`` and
                   ``ooo``.

Adding a policy: subclass :class:`Scheduler`, implement the hooks, decorate
with :func:`register`. ``cfg.scheduler = "<name>"`` then selects it in
``simulate``, ``simulate_sharded`` and ``simulate_batch`` — no cycle-kernel
edits required.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import bitvec


def row_gather(arr, idx):
    """arr: [nx, ny, L(, ...)], idx: [nx, ny] -> arr[x, y, idx[x, y]]."""
    idxc = jnp.clip(idx, 0, arr.shape[2] - 1)
    take = jnp.take_along_axis(
        arr, idxc.reshape(*idx.shape, 1, *(1,) * (arr.ndim - 3)), axis=2)
    return take.reshape(idx.shape + arr.shape[3:])


def _initial_ready(g):
    """Inputs with fanouts are ready at cycle 0 (they must drain tokens)."""
    is_input = (g["fanin"] == 0) & g["valid"]
    return is_input & (g["fo_count"] > 0)


def _rdy_image(need_drain):
    """[nx, ny, L] bool -> packed [nx, ny, W] uint32 RDY bit image."""
    nx, ny, L = need_drain.shape
    W = L // bitvec.FLAGS_PER_WORD
    slots = jnp.arange(L, dtype=jnp.int32)
    bit = jnp.uint32(1) << (31 - (slots % 32)).astype(jnp.uint32)
    masks = jnp.where(need_drain, bit[None, None, :], jnp.uint32(0))
    return jax.lax.reduce(
        masks.reshape(nx, ny, W, 32), jnp.uint32(0), jax.lax.bitwise_or, (3,))


def _clear_selected(rdy, sel, cand):
    """Clear bit ``cand`` on PEs where ``sel``; L = W * 32."""
    L = rdy.shape[-1] * bitvec.FLAGS_PER_WORD
    return bitvec.clear_bit(rdy, jnp.clip(cand, 0, L - 1), sel)


class Scheduler:
    """Base policy. Subclasses override the hooks; see the module docstring."""

    name: str = "?"
    #: whether the policy exploits criticality-ordered local memory (used by
    #: benchmarks to choose the matching GraphMemory layout).
    wants_criticality_order: bool = True

    def sel_lat(self, cfg, num_words: int) -> int:
        """Exposed select latency in cycles (static, resolved at trace time)."""
        return cfg.sel_lat

    def init(self, g, cfg) -> dict:
        raise NotImplementedError

    def on_ready(self, st: dict, slot, ready) -> dict:
        raise NotImplementedError

    def select(self, st: dict, idle):
        raise NotImplementedError

    def commit(self, st: dict, sel, cand) -> dict:
        raise NotImplementedError

    def empty(self, st: dict):
        raise NotImplementedError

    def ready_depth(self, st: dict):
        """[nx, ny] int32 count of queued-ready nodes per PE — the telemetry
        probe behind :mod:`repro.telemetry`'s ready-set-depth trace. Purely
        observational: never called by the cycle kernel unless a
        ``TelemetrySpec`` asks for scheduler traces."""
        raise NotImplementedError

    def step(self, st: dict, idle, gate, *, use_pallas: bool = False):
        """Fused select + commit: the cycle kernel's per-cycle entry point.

        ``gate`` marks PEs whose pick is consumed this cycle (idle and past
        the exposed select latency); the candidate is committed where
        ``gate & have``. The default composes the two hooks, so policies only
        implementing the five base hooks work unchanged; policies with a
        fused Pallas kernel override this (``use_pallas=True``) to do the
        pick and the RDY clear in one VMEM round-trip.
        """
        cand, have = self.select(st, idle)
        return cand, have, self.commit(st, gate & have, cand)


REGISTRY: dict[str, Scheduler] = {}


def register(cls):
    """Class decorator: instantiate and add to the policy REGISTRY."""
    inst = cls()
    if inst.name in REGISTRY:
        raise ValueError(f"duplicate scheduler name {inst.name!r}")
    REGISTRY[inst.name] = inst
    return cls


def get(name: str) -> Scheduler:
    try:
        return REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown scheduler {name!r}; registered: {sorted(REGISTRY)}"
        ) from None


@register
class OooScheduler(Scheduler):
    """Packed RDY bit-flags + hierarchical leading-one detect (paper §II-B)."""

    name = "ooo"
    wants_criticality_order = True

    def init(self, g, cfg):
        return dict(rdy=_rdy_image(_initial_ready(g)))

    def on_ready(self, st, slot, ready):
        return dict(st, rdy=bitvec.set_bit(st["rdy"], slot, ready))

    def select(self, st, idle):
        cand = bitvec.leading_one(st["rdy"])   # most critical ready slot
        return cand, cand >= 0

    def commit(self, st, sel, cand):
        return dict(st, rdy=_clear_selected(st["rdy"], sel, cand))

    def empty(self, st):
        return (st["rdy"] == 0).all()

    def ready_depth(self, st):
        return bitvec.count_set(st["rdy"])

    def step(self, st, idle, gate, *, use_pallas=False):
        if not use_pallas:
            return super().step(st, idle, gate, use_pallas=False)
        from repro.kernels import ops  # lazy: keep core importable sans Pallas

        nx, ny, W = st["rdy"].shape
        slot, newbits = ops.schedule_step(
            st["rdy"].reshape(nx * ny, W), gate=gate.reshape(nx * ny))
        cand = slot.reshape(nx, ny)
        return cand, cand >= 0, dict(st, rdy=newbits.reshape(nx, ny, W))


@register
class InorderScheduler(Scheduler):
    """FIFO in arrival order (FCFS) — the prior-TDP baseline. Depth is the
    deadlock-free worst case: every local slot simultaneously ready."""

    name = "inorder"
    wants_criticality_order = False

    def init(self, g, cfg):
        nx, ny, L = g["opcode"].shape
        need_drain = _initial_ready(g)
        slots = jnp.arange(L, dtype=jnp.int32)
        # FIFO pre-loaded with ready inputs in ascending slot (arrival) order.
        order_key = jnp.where(need_drain, slots, L)
        fifo_init = jnp.sort(order_key, axis=-1)
        fifo = jnp.where(fifo_init < L, fifo_init, -1).astype(jnp.int32)
        return dict(
            fifo=fifo,
            head=jnp.zeros((nx, ny), jnp.int32),
            size=need_drain.sum(axis=-1).astype(jnp.int32),
        )

    def on_ready(self, st, slot, ready):
        fifo, head, size = st["fifo"], st["head"], st["size"]
        depth = fifo.shape[-1]
        tail = (head + size) % depth
        push = (jnp.arange(depth, dtype=jnp.int32) == tail[..., None]) \
            & ready[..., None]
        fifo = jnp.where(push, slot[..., None], fifo)
        return dict(fifo=fifo, head=head, size=size + ready.astype(jnp.int32))

    def select(self, st, idle):
        return row_gather(st["fifo"], st["head"]), st["size"] > 0

    def commit(self, st, sel, cand):
        depth = st["fifo"].shape[-1]
        head = jnp.where(sel, (st["head"] + 1) % depth, st["head"])
        size = jnp.where(sel, st["size"] - 1, st["size"])
        return dict(st, head=head, size=size)

    def empty(self, st):
        return (st["size"] == 0).all()

    def ready_depth(self, st):
        return st["size"]


class _RotatingRdyScheduler(Scheduler):
    """Shared machinery: RDY bit vector scanned from a rotating pointer.

    The pick is the first ready slot at/after the pointer (wrapping), i.e.
    rotating / least-recently-granted priority — deliberately blind to the
    criticality slot ordering the ``ooo`` policy exploits.
    """

    wants_criticality_order = False

    def init(self, g, cfg):
        nx, ny, _ = g["opcode"].shape
        return dict(rdy=_rdy_image(_initial_ready(g)),
                    ptr=jnp.zeros((nx, ny), jnp.int32))

    def on_ready(self, st, slot, ready):
        return dict(st, rdy=bitvec.set_bit(st["rdy"], slot, ready))

    def select(self, st, idle):
        rdy = st["rdy"]
        hi = rdy & bitvec.mask_slots_ge(st["ptr"], rdy.shape[-1])
        cand_hi = bitvec.leading_one(hi)
        cand = jnp.where(cand_hi >= 0, cand_hi, bitvec.leading_one(rdy))
        return cand, cand >= 0

    def commit(self, st, sel, cand):
        rdy = _clear_selected(st["rdy"], sel, cand)
        L = rdy.shape[-1] * bitvec.FLAGS_PER_WORD
        ptr = jnp.where(sel, (jnp.clip(cand, 0, L - 1) + 1) % L, st["ptr"])
        return dict(rdy=rdy, ptr=ptr)

    def empty(self, st):
        return (st["rdy"] == 0).all()

    def ready_depth(self, st):
        return bitvec.count_set(st["rdy"])

    def step(self, st, idle, gate, *, use_pallas=False):
        if not use_pallas:
            return super().step(st, idle, gate, use_pallas=False)
        from repro.kernels import ops  # lazy: keep core importable sans Pallas

        nx, ny, W = st["rdy"].shape
        L = W * bitvec.FLAGS_PER_WORD
        slot, newbits = ops.rotating_schedule_step(
            st["rdy"].reshape(nx * ny, W), st["ptr"].reshape(nx * ny),
            gate.reshape(nx * ny))
        cand = slot.reshape(nx, ny)
        have = cand >= 0
        sel = gate & have
        ptr = jnp.where(sel, (cand + 1) % L, st["ptr"])
        return cand, have, dict(rdy=newbits.reshape(nx, ny, W), ptr=ptr)


@register
class ScanScheduler(_RotatingRdyScheduler):
    """The naive memory scan the paper rejects: the pick walks graph memory
    word by word, so its exposed latency defaults to the RDY word count
    (non-deterministic in hardware; modeled as the worst-case full sweep).
    Override with ``cfg.select_latency`` for a shallower exposed cost."""

    name = "scan"

    def sel_lat(self, cfg, num_words):
        if cfg.select_latency is not None:
            return cfg.select_latency
        return max(1, num_words)


@register
class LruFlatScheduler(_RotatingRdyScheduler):
    """Single-level (flat) LOD, rotating priority, 1-cycle exposed pick —
    the fair-arbiter ablation: as fast as ``ooo`` per pick but unable to
    exploit criticality ordering."""

    name = "lru_flat"


class BatchedScheduler(Scheduler):
    """Composite policy for the vmapped sweep engine.

    Maintains every member policy's state side by side plus a per-batch-
    element ``policy_id``; ``select``/``empty`` dispatch on it with
    ``jnp.select`` so one traced cycle body serves a whole
    (scheduler x latency) sweep. Inactive substates still advance (their
    updates are data-independent of the dispatch) but only the active
    policy's state ever reaches ``select``/``empty``, so each batch element
    is cycle-exact with the corresponding solo run.
    """

    name = "batched"
    wants_criticality_order = True

    def __init__(self, names: tuple[str, ...] = ()):
        self.names = tuple(names)
        self.policies = [get(n) for n in self.names]

    def sel_lat(self, cfg, num_words):
        # Placeholder: simulate_batch overwrites sel_wait/sel_lat per element.
        return 1

    def init(self, g, cfg):
        st = {n: p.init(g, cfg) for n, p in zip(self.names, self.policies)}
        st["policy_id"] = jnp.int32(0)
        return st

    def _preds(self, st):
        return [st["policy_id"] == i for i in range(len(self.policies))]

    @property
    def _solo(self):
        """Single-policy sweep: the dispatch predicate is statically true for
        member 0 and statically false for everyone else, so the per-policy
        masking and ``jnp.select`` dispatch are pruned at trace time."""
        return len(self.policies) == 1

    def on_ready(self, st, slot, ready):
        out = dict(st)
        for n, p in zip(self.names, self.policies):
            out[n] = p.on_ready(st[n], slot, ready)
        return out

    def select(self, st, idle):
        if self._solo:
            return self.policies[0].select(st[self.names[0]], idle)
        cands, haves = zip(*(p.select(st[n], idle)
                             for n, p in zip(self.names, self.policies)))
        preds = self._preds(st)
        cand = jnp.select(preds, list(cands), cands[0])
        have = jnp.select(preds, list(haves), haves[0])
        return cand, have

    def commit(self, st, sel, cand):
        out = dict(st)
        if self._solo:
            n = self.names[0]
            out[n] = self.policies[0].commit(st[n], sel, cand)
            return out
        for i, (n, p) in enumerate(zip(self.names, self.policies)):
            out[n] = p.commit(st[n], sel & (st["policy_id"] == i), cand)
        return out

    def empty(self, st):
        if self._solo:
            return self.policies[0].empty(st[self.names[0]])
        es = [p.empty(st[n]) for n, p in zip(self.names, self.policies)]
        return jnp.select(self._preds(st), es, es[0])

    def ready_depth(self, st):
        if self._solo:
            return self.policies[0].ready_depth(st[self.names[0]])
        ds = [p.ready_depth(st[n]) for n, p in zip(self.names, self.policies)]
        return jnp.select(self._preds(st), ds, ds[0])

    def step(self, st, idle, gate, *, use_pallas=False):
        out = dict(st)
        if self._solo:
            n = self.names[0]
            cand, have, out[n] = self.policies[0].step(
                st[n], idle, gate, use_pallas=use_pallas)
            return cand, have, out
        # Each member commits its own candidate under its dispatch predicate;
        # where the predicate holds, the member's candidate IS the dispatched
        # candidate, so this equals select-then-masked-commit exactly.
        preds = self._preds(st)
        cands, haves = [], []
        for i, (n, p) in enumerate(zip(self.names, self.policies)):
            c, h, out[n] = p.step(st[n], idle, gate & preds[i],
                                  use_pallas=use_pallas)
            cands.append(c)
            haves.append(h)
        return (jnp.select(preds, cands, cands[0]),
                jnp.select(preds, haves, haves[0]), out)
