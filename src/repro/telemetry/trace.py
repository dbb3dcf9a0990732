"""In-engine trace accumulation — the jnp half of :mod:`repro.telemetry`.

:func:`init` builds the zeroed trace leaves that ride the simulation state
pytree under ``state["telem"]``; :func:`accumulate` is called once per cycle
by ``overlay.make_cycle_fn`` with signals the model already computed. Both
are pure jnp functions of [nx, ny]-local arrays, so they work unchanged
under ``jax.vmap`` (the batched sweep engine), ``shard_map`` (leaves keep
the grid dims as their LAST TWO axes — one tiled all_gather per mesh axis
reassembles the global trace, see ``distributed._gather_telem``).

Bit-determinism contract: every increment is integer, PE-local, and —
except ``stall_no_ready``, repaired by ``overlay.repair_telemetry`` — zero
at the completed-overlay fixed point, so the guard-free chunk engines can
over-simulate past completion without drifting any trace. This module must
not import :mod:`repro.core.overlay` (overlay lazily imports it).
"""
from __future__ import annotations

import jax.numpy as jnp

from .spec import TelemetrySpec


def init(spec: TelemetrySpec, nx: int, ny: int) -> dict:
    """Zeroed trace leaves for one simulation on an nx x ny PE grid."""
    zb = lambda: jnp.zeros((spec.buckets, nx, ny), jnp.int32)
    z2 = lambda: jnp.zeros((nx, ny), jnp.int32)
    t: dict = {}
    if spec.pe:
        t["pe_busy"] = zb()       # node fires          (sums to busy_cycles)
        t["pe_occ"] = zb()        # fanout-drain-occupied cycles
    if spec.links:
        t["link_e"] = zb()        # E output register valid
        t["link_s"] = zb()        # S output register valid
        t["defl_noc"] = zb()      # route-contention    (sums to noc_deflections)
        t["defl_eject"] = zb()    # eject-port losers   (sums to eject_deflections)
    if spec.eject:
        t["eject_grant"] = zb()   # eject-port grants   (sums to delivered)
    if spec.sched:
        t["ready_depth"] = zb()   # queued-ready nodes, summed per bucket
        t["pick_pos"] = z2()      # summed selected slot index
        t["picks"] = z2()         # number of committed picks
    if spec.stalls:
        t["stall_no_ready"] = z2()   # idle, nothing ready (overshoot-repaired)
        t["stall_inject"] = z2()     # injection offered but NoC-blocked
        t["stall_sel_wait"] = z2()   # pick held behind exposed select latency
    return t


def accumulate(spec: TelemetrySpec, t: dict, *, cycle, fired, occupied,
               link_e_busy, link_s_busy, defl_noc, defl_eject, eject_grant,
               ready_depth, sel, cand, no_ready, inj_blocked,
               sel_waiting) -> dict:
    """One cycle of trace increments. All inputs are [nx, ny] signals the
    cycle body already computed (``cycle`` is the pre-increment cycle
    counter, used as the bucket timestamp); clamping the bucket index keeps
    post-horizon cycles counted, so trace sums stay exactly equal to the
    scalar stat counters."""
    out = dict(t)
    b = jnp.minimum(cycle // spec.bucket_cycles, spec.buckets - 1)

    def bump(name, inc):
        out[name] = out[name].at[b].add(inc.astype(jnp.int32))

    if spec.pe:
        bump("pe_busy", fired)
        bump("pe_occ", occupied)
    if spec.links:
        bump("link_e", link_e_busy)
        bump("link_s", link_s_busy)
        bump("defl_noc", defl_noc)
        bump("defl_eject", defl_eject)
    if spec.eject:
        bump("eject_grant", eject_grant)
    if spec.sched:
        bump("ready_depth", ready_depth)
        out["pick_pos"] = out["pick_pos"] + jnp.where(sel, cand, 0)
        out["picks"] = out["picks"] + sel.astype(jnp.int32)
    if spec.stalls:
        out["stall_no_ready"] = out["stall_no_ready"] + no_ready.astype(jnp.int32)
        out["stall_inject"] = out["stall_inject"] + inj_blocked.astype(jnp.int32)
        out["stall_sel_wait"] = (out["stall_sel_wait"]
                                 + sel_waiting.astype(jnp.int32))
    return out
