"""Paper Fig. 1: out-of-order vs in-order scheduling speedup vs graph size.

Workloads: LU-factorization dataflow DAGs of bordered block-diagonal
("arrow") matrices — the canonical circuit/power-grid structure behind
sparse-matrix-factorization kernels — on the 16x16 (256 PE) overlay, exactly
the paper's evaluation setup. The paper's own matrices are not published;
sizes sweep a few K to ~500K nodes as in Fig. 1.

Each graph size runs the requested scheduler policies through
``repro.run(gm, batch=...)``: the cycle body is vmapped over the policy
axis, so a
sweep compiles once per (graph, memory layout) instead of retracing per
scheduler. Policies are grouped by ``wants_criticality_order`` and each
group gets the matching GraphMemory layout — the seed methodology (``ooo``
on criticality-ordered memory, the FCFS baseline on naive node-id order);
slot numbering shifts packet-arrival order, so mixing layouts would move
the tracked speedup by a few percent.

Output CSV: name,us_per_call,derived  where derived = inorder/ooo speedup.
"""
from __future__ import annotations

import time

from repro.api import run as overlay_run
from repro.core import schedulers
from repro.core import workloads as wl
from repro.core.overlay import OverlayConfig
from repro.core.partition import build_graph_memory

# (blocks, block_size, border): graph sizes ~15K .. ~470K nodes
SWEEP = [(4, 10, 8), (8, 10, 8), (16, 10, 8), (32, 10, 8), (64, 10, 8)]
SWEEP_FULL = SWEEP + [(96, 10, 8), (128, 10, 8)]

DEFAULT_POLICIES = ("ooo", "inorder")


def _run_policies(g, nx, ny, policies, max_cycles=8_000_000, timed=False,
                  check_every=None):
    """One batched program per GraphMemory layout group. Returns
    ({policy: cycles}, wall seconds[, hot wall seconds]).

    ``timed=True`` reruns every (already compiled) program once more and
    additionally returns the hot wall — the simulated-cycles-per-second
    throughput metric tracked in BENCH_overlay.json, free of compile time.
    """
    groups: dict = {}
    for p in policies:
        wants = schedulers.get(p).wants_criticality_order
        groups.setdefault(wants, []).append(p)
    cyc = {}
    runs = []
    t0 = time.time()
    for wants, group in groups.items():
        gm = build_graph_memory(g, nx, ny, criticality_order=wants)
        cfgs = [OverlayConfig(scheduler=p, max_cycles=max_cycles,
                              check_every=check_every)
                for p in group]
        for p, r in zip(group, overlay_run(gm, batch=cfgs)):
            assert r.done, p
            cyc[p] = r.cycles
        runs.append((gm, cfgs))
    wall = time.time() - t0
    if not timed:
        return cyc, wall
    hot = float("inf")
    for _ in range(2):  # min over reps: shared machines have noisy clocks
        t0 = time.time()
        for gm, cfgs in runs:
            overlay_run(gm, batch=cfgs)
        hot = min(hot, time.time() - t0)
    return cyc, wall, hot


def run(full: bool = False, nx: int = 16, ny: int = 16,
        policies: tuple[str, ...] = DEFAULT_POLICIES):
    rows = []
    for blocks, s, w in (SWEEP_FULL if full else SWEEP):
        # Cached on disk (experiments/graph_cache/): the --full sweep's big
        # DAGs take minutes of Python elimination to build, and CI persists
        # the cache across runs keyed on the workload code.
        g = wl.cached_graph(
            f"arrow_b{blocks}_s{s}_w{w}_seed3",
            lambda blocks=blocks, s=s, w=w: wl.arrow_lu_graph(
                blocks, s, w, seed=3))
        cyc, wall, hot_wall = _run_policies(g, nx, ny, policies, timed=True)
        total_cycles = sum(cyc.values())
        row = {
            "name": f"fig1_arrow_n{g.num_nodes}",
            "us_per_call": round(1e6 * wall, 1),
            "derived": round(cyc["inorder"] / cyc["ooo"], 4)
            if {"ooo", "inorder"} <= cyc.keys() else 0.0,
            "nodes": g.num_nodes,
            "edges": g.num_edges,
            "wall_s": round(wall, 3),
            "hot_wall_s": round(hot_wall, 3),
            "cycles_per_sec": round(total_cycles / hot_wall, 1),
        }
        row.update({f"cycles_{p}": c for p, c in cyc.items()})
        rows.append(row)
    return rows


def chunking_throughput(nx: int = 16, ny: int = 16,
                        blocks: int = 8, block_size: int = 10, border: int = 8):
    """Chunked-engine before/after: the same fig1 graph stepped with
    ``check_every=1`` (the per-cycle reference engine) versus the autotuned
    chunk depth, hot-timed. The simulated-cycles-per-second ratio is the
    tracked win of chunked termination checking on this backend (the larger
    wins are on sharded meshes, where the chunk also amortizes the
    cross-shard collectives — see docs/schedulers.md)."""
    from repro.core.overlay import resolve_check_every

    g = wl.arrow_lu_graph(blocks, block_size, border, seed=3)
    rows = []
    for label, check_every in (("check_every_1", 1), ("check_every_auto", None)):
        cyc, wall, hot = _run_policies(g, nx, ny, ("ooo", "inorder"),
                                       timed=True, check_every=check_every)
        total = sum(cyc.values())
        rows.append({
            "name": f"chunking_{label}_n{g.num_nodes}",
            "us_per_call": round(1e6 * hot, 1),
            "derived": round(total / hot, 1),   # simulated cycles per second
            "wall_s": round(wall, 3),
            "hot_wall_s": round(hot, 3),
            "cycles_per_sec": round(total / hot, 1),
            "cycles": dict(sorted(cyc.items())),
        })
    base, auto = rows[0], rows[1]
    k = resolve_check_every(OverlayConfig(), nx, ny,
                            build_graph_memory(g, nx, ny).lmax)
    return {
        "rows": rows,
        "auto_check_every": k,
        "speedup_hot": round(auto["cycles_per_sec"] / base["cycles_per_sec"], 4),
    }


def sweep_policies(nx: int = 16, ny: int = 16,
                   blocks: int = 8, block_size: int = 10, border: int = 8):
    """All registered policies on one mid-size arrow-LU graph (one batched
    program per layout group). Returns per-scheduler cycles + speedup vs the
    FCFS baseline."""
    policies = tuple(sorted(schedulers.REGISTRY))
    g = wl.arrow_lu_graph(blocks, block_size, border, seed=3)
    cyc, wall = _run_policies(g, nx, ny, policies)
    base = cyc["inorder"]
    return {
        "nodes": g.num_nodes,
        "edges": g.num_edges,
        "grid": [nx, ny],
        "wall_s": round(wall, 3),
        "schedulers": [
            {"scheduler": p, "cycles": c, "done": True,
             "speedup_vs_inorder": round(base / c, 4)}
            for p, c in sorted(cyc.items())
        ],
    }


def main(full: bool = False):
    print("name,us_per_call,derived")
    for r in run(full):
        print(f"{r['name']},{r['us_per_call']},{r['derived']}")


if __name__ == "__main__":
    import sys
    main(full="--full" in sys.argv)
