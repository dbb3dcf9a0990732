"""BENCH ``telemetry`` section: the instrument's own numbers, CI-gated.

For the fig1-family ooo-vs-inorder pair (the cached ``arrow_b4_s10_w8``
graph on the 16x16 grid), run each policy with tracing on and report:

  * ``cycles_<policy>`` — must equal the untraced run (asserted here,
    no-increase gated by check_bench like every cycles_* key);
  * ``ctr_*`` — integer counter values from the traces (stall attribution,
    deflection split, busiest-link cycles, pick counts): bit-exact gated by
    ``check_bench._telemetry_counters`` — the instrument itself must not
    drift silently;
  * ``derived`` / ``*_util_*`` — tracing overhead ratio and utilization
    percentiles, informational (wall-clock / derived floats).

The ooo-vs-inorder stall attribution printed here is the worked example in
docs/telemetry.md.
"""
from __future__ import annotations

import time

from repro.core import schedulers
from repro.core import workloads as wl
from repro.api import run as overlay_run
from repro.core.overlay import OverlayConfig
from repro.core.partition import build_graph_memory
from repro.telemetry import TelemetrySpec


def run(nx: int = 16, ny: int = 16):
    name = "arrow_b4_s10_w8_seed3"
    g = wl.cached_graph(name, lambda: wl.arrow_lu_graph(4, 10, 8, seed=3))
    spec = TelemetrySpec()
    rows = []
    for sched in ("ooo", "inorder"):
        gm = build_graph_memory(
            g, nx, ny,
            criticality_order=schedulers.get(sched).wants_criticality_order)
        cfg_off = OverlayConfig(scheduler=sched, max_cycles=8_000_000)
        cfg_on = OverlayConfig(scheduler=sched, max_cycles=8_000_000,
                               telemetry=spec)
        t0 = time.time()
        off = overlay_run(gm, cfg_off)
        r = overlay_run(gm, cfg_on)
        wall = time.time() - t0
        assert r.done and r.cycles == off.cycles, (sched, r.cycles, off.cycles)

        hot_off = hot_on = float("inf")
        for _ in range(2):  # min over reps: shared machines have noisy clocks
            t0 = time.time()
            overlay_run(gm, cfg_off)
            hot_off = min(hot_off, time.time() - t0)
            t0 = time.time()
            overlay_run(gm, cfg_on)
            hot_on = min(hot_on, time.time() - t0)

        rep = r.telemetry.report()
        rows.append({
            "name": f"telemetry_arrow_n{g.num_nodes}_{sched}",
            "us_per_call": round(1e6 * hot_on, 1),
            # tracing overhead: traced / untraced hot wall (1.0 == free)
            "derived": round(hot_on / hot_off, 4),
            "nodes": g.num_nodes,
            "wall_s": round(wall, 3),
            "hot_wall_s": round(hot_on, 3),
            "hot_wall_s_untraced": round(hot_off, 3),
            f"cycles_{sched}": r.cycles,
            # bit-exact-gated instrument counters (check_bench ctr_* gate)
            "ctr_busy_total": r.busy_cycles,
            "ctr_delivered": r.delivered,
            "ctr_noc_deflections": r.noc_deflections,
            "ctr_eject_deflections": r.eject_deflections,
            "ctr_link_busy_max": rep["links"]["busy_max"],
            "ctr_stall_no_ready": rep["stalls"]["no_ready"],
            "ctr_stall_inject_blocked": rep["stalls"]["inject_blocked"],
            "ctr_stall_select_wait": rep["stalls"]["select_wait"],
            "ctr_picks": rep["sched"]["picks"],
            # informational derived floats
            "link_util_p50": rep["links"]["util_p50"],
            "link_util_p95": rep["links"]["util_p95"],
            "link_util_max": rep["links"]["util_max"],
            "pick_pos_mean": rep["sched"]["pick_pos_mean"],
            "ready_depth_mean": rep["sched"]["ready_depth_mean"],
        })
    return rows
