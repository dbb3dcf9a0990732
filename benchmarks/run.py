# One function per paper table/figure. Prints ``name,us_per_call,derived`` CSV.
"""Benchmark driver.

  table1    — RDY-flag overhead / FIFO-elimination capacity model (Table I, §III)
  kernels   — per-policy scheduler pick-rate microbench (LOD + select/commit
              + the fused Pallas schedule_step / rotating variants)
  fig1      — OoO vs in-order speedup vs graph size (paper Fig. 1), with
              hot-timed simulated-cycles-per-second throughput per row
  sweep     — every registered policy on one graph via one batched program
  chunking  — chunked-engine throughput: check_every=1 vs autotuned depth
  placement — repro.place subsystem: identity vs random vs annealed
              placements (CI-gated cycles) + priority eject arbitration
  guided    — surrogate-guided annealing vs the plain annealer: cycles and
              exact full-cost-evaluation counters (CI-gated)
  telemetry — fig1 ooo-vs-inorder with repro.telemetry tracing on: cycles
              unchanged vs untraced (CI-gated), instrument counters
              bit-exact (CI-gated), tracing overhead informational
  service   — replayed 32-query placement-service stream: repeats answer
              from the content-hash cache with zero simulations, cached ==
              fresh cycles bit-exact both directions (CI-gated), hit-rate
              floor, plus the explorer's Pareto frontier cycle counts
  fig1_full — (--full only) budgeted multilevel placement + simulation of
              the ~470K-node paper-scale LU DAG (CI-gated cycles)
  roofline  — per (arch x shape) roofline terms from the dry-run artifacts

``python -m benchmarks.run [--full]`` runs everything (fig1 sweeps to ~470K
nodes and the fig1_full tracked row lands with --full; default tops out
near ~235K to keep wall-time sane).

Besides the CSV on stdout, the driver snapshots everything machine-readable
to ``BENCH_overlay.json`` (per-scheduler cycles, wall time, speedups) so the
perf trajectory is tracked across PRs.
"""
from __future__ import annotations

import json
import os
import sys

BENCH_PATH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                          "BENCH_overlay.json")


def main() -> None:
    full = "--full" in sys.argv
    print("name,us_per_call,derived")

    import jax

    from repro import compile_cache
    from repro.core import schedulers

    compile_cache.configure()

    bench: dict = {
        "meta": {
            "backend": jax.default_backend(),
            "device_count": jax.device_count(),
            "policies": sorted(schedulers.REGISTRY),
            "full": full,
        }
    }

    from benchmarks import table1_resources
    bench["table1"] = []
    for name, value, paper in table1_resources.run()[0]:
        note = f" (paper: {paper})" if paper is not None else ""
        print(f"{name},0.0,{value}{note}", flush=True)
        bench["table1"].append({"name": name, "value": value, "paper": paper})

    from benchmarks import kernel_bench
    bench["kernels"] = kernel_bench.run()
    for r in bench["kernels"]:
        print(f"{r['name']},{r['us_per_call']},{r['derived']}", flush=True)

    from benchmarks import fig1_ooo_speedup
    bench["fig1"] = fig1_ooo_speedup.run(full=full)
    for r in bench["fig1"]:
        print(f"{r['name']},{r['us_per_call']},{r['derived']}", flush=True)

    bench["policy_sweep"] = fig1_ooo_speedup.sweep_policies()
    for row in bench["policy_sweep"]["schedulers"]:
        print(f"sweep_{row['scheduler']},0.0,{row['speedup_vs_inorder']}",
              flush=True)

    # Chunked-engine before/after on one fig1 graph: hot-timed simulated
    # cycles per second at check_every=1 vs the autotuned chunk depth.
    bench["chunking"] = fig1_ooo_speedup.chunking_throughput()
    for r in bench["chunking"]["rows"]:
        print(f"{r['name']},{r['us_per_call']},{r['derived']}", flush=True)
    print(f"chunking_speedup_hot,0.0,{bench['chunking']['speedup_hot']}",
          flush=True)

    # Placement subsystem: identity vs random vs NoC-annealed placements
    # (cycle counts CI-gated), and the criticality-aware eject arbitration
    # on congested grids.
    from benchmarks import placement_bench
    bench["placement"] = {"rows": placement_bench.run_placement()}
    for r in bench["placement"]["rows"]:
        print(f"{r['name']},{r['us_per_call']},{r['derived']}", flush=True)
    bench["eject"] = {"rows": placement_bench.run_eject()}
    for r in bench["eject"]["rows"]:
        print(f"{r['name']},{r['us_per_call']},{r['derived']}", flush=True)

    # Surrogate subsystem: held-out rank correlation + prediction-pruned
    # search quality per fig1 workload, and the multilevel
    # coarsen->anneal->refine placement at >= 100K nodes vs round-robin.
    # check_bench gates the Spearman floor, the pruning gap, and the
    # multilevel cycle counts.
    bench["surrogate"] = {"rows": placement_bench.run_surrogate()
                          + placement_bench.run_multilevel()}
    for r in bench["surrogate"]["rows"]:
        print(f"{r['name']},{r['us_per_call']},{r['derived']}", flush=True)

    # Surrogate-guided annealing vs the plain annealer: equal-or-better
    # cycles under <= 0.5x full-cost evaluations (both counters exact and
    # deterministic; check_bench gates the cycles, the ratio cap, and the
    # guided <= unguided relation).
    bench["guided"] = {"rows": placement_bench.run_guided()}
    for r in bench["guided"]["rows"]:
        print(f"{r['name']},{r['us_per_call']},{r['derived']}", flush=True)

    # Telemetry instrument: fig1 ooo-vs-inorder with tracing on. Cycle
    # counts must equal the untraced run (asserted in the bench, gated like
    # every cycles_* key); the ctr_* counter values (stall attribution,
    # deflection split, busiest link) are bit-exact gated by check_bench;
    # the derived column (traced/untraced hot-wall ratio) is informational.
    from benchmarks import telemetry_bench
    bench["telemetry"] = {"rows": telemetry_bench.run()}
    for r in bench["telemetry"]["rows"]:
        print(f"{r['name']},{r['us_per_call']},{r['derived']}", flush=True)

    # Placement service: the 32-query / 8-distinct replayed stream
    # (repeats must answer from the content-hash cache with zero
    # simulations; cached-vs-fresh cycles gated bit-exact both directions;
    # hit rate floor-gated) plus the explorer's Pareto frontier rows
    # (cycles no-increase gated). Wall/amortization stays informational.
    from benchmarks import service_bench
    bench["service"] = {"rows": service_bench.run()}
    for r in bench["service"]["rows"]:
        print(f"{r['name']},{r['us_per_call']},{r['derived']}", flush=True)

    if full:
        # fig1-full tracked row: budgeted multilevel placement + simulation
        # of the ~470K-node paper-scale LU DAG (cycle counts CI-gated
        # bit-exactly; the DAG itself is served from the on-disk graph
        # cache, which CI persists across runs).
        bench["fig1_full"] = {"rows": placement_bench.run_fig1_full()}
        for r in bench["fig1_full"]["rows"]:
            print(f"{r['name']},{r['us_per_call']},{r['derived']}",
                  flush=True)

    from benchmarks import roofline
    rows = roofline.run("single")
    bench["roofline"] = rows or []
    if rows:
        for r in rows:
            print(f"{r['name']},{r['us_per_call']},{r['derived']}", flush=True)
    else:
        print("roofline_pending,0.0,run repro.launch.dryrun first", flush=True)

    with open(BENCH_PATH, "w") as f:
        json.dump(bench, f, indent=1)
    print(f"# wrote {BENCH_PATH}", file=sys.stderr)


if __name__ == "__main__":
    main()
