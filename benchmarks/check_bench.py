"""CI gate: diff a fresh BENCH_overlay.json against the committed snapshot.

Cycle counts are simulation *semantics* — for a cycle-accurate simulator they
must not regress silently. This check fails (exit 1) when any tracked cycle
count grew versus the baseline or a tracked row disappeared; cycle counts
that *shrank* are reported as improvements (update the committed snapshot to
lock them in). Wall-clock numbers are machine-dependent, so wall/throughput
deltas are printed for the log but never block (shared CI runners).

The ``surrogate`` section additionally carries two *quality floors* (also
blocking): held-out Spearman rank correlation >= SPEARMAN_FLOOR per rank row,
and the prediction-pruned best placement within PRUNE_GAP_MAX of the
exhaustive best. These are floors rather than exact diffs because the ridge
solve is float64 — integer features make it stable to reproduce, but the last
bits (and thus near-tie ranks) may differ across BLAS builds, unlike the
integer cycle counts which must match bit-exactly.

The ``guided`` section carries two more blocking relations, both on exact
deterministic integers (the guided annealer's accept rule is fully
quantized): full-cost evaluations <= GUIDED_EVAL_RATIO_MAX of the unguided
budget, and guided simulated cycles <= unguided simulated cycles.

The ``telemetry`` section's ``ctr_*`` counters (stall attribution,
deflection split, busiest-link cycles) are gated *bit-exact in both
directions*: the instrument's output on a deterministic workload must not
move at all unless the committed snapshot is updated deliberately.

The ``service`` section's ``svc_*`` counters (cached/fresh cycle pairs,
stream hit/miss/simulation counts, frontier sizes) get the same
both-direction bit-exact treatment, plus two fresh-run relations:
cached cycles == recomputed cycles per row, and the replayed stream's
hit rate >= SERVICE_HIT_RATE_FLOOR.

Usage:  python benchmarks/check_bench.py BASELINE.json FRESH.json
"""
from __future__ import annotations

import json
import sys

#: minimum held-out Spearman(predicted, simulated cycles) per surrogate row.
SPEARMAN_FLOOR = 0.8
#: max pruned_best / exhaustive_best: the top-k predicted candidates must
#: contain a placement within 5% of the exhaustive-simulation best.
PRUNE_GAP_MAX = 1.05
#: max full-cost evaluations of the guided annealer over the unguided
#: budget: the surrogate gate must screen out at least half the proposals
#: an unguided run would have cost-evaluated (exact integer counters).
GUIDED_EVAL_RATIO_MAX = 0.5
#: minimum cache hit rate on the replayed service stream: the 32-query /
#: 8-distinct stream is 75% repeats, and every repeat must answer from the
#: content-hash cache — a hit rate under 0.5 means repeat queries are
#: re-simulating.
SERVICE_HIT_RATE_FLOOR = 0.5


def _cycle_counts(bench: dict) -> dict[str, int]:
    """Flatten every tracked cycle count to {metric_name: cycles}."""
    out: dict[str, int] = {}
    flat_rows = list(bench.get("fig1", []))
    # Placement / eject / surrogate / guided / fig1_full sections carry
    # per-row cycles_* keys like fig1 does (identity/random/annealed
    # placements; n_first/priority arbitration; multilevel and guided
    # searches; the fig1-full tracked row; the telemetry-on runs, whose
    # cycles must equal the untraced baseline) — all deterministic
    # simulation semantics, all blocking. (cycles_per_sec is throughput and
    # stays informational: only the cycles_ prefix is gated.)
    for section in ("placement", "eject", "surrogate", "guided", "fig1_full",
                    "telemetry", "service"):
        flat_rows += bench.get(section, {}).get("rows", [])
    for row in flat_rows:
        for key, val in row.items():
            # cycles_per_sec is wall-clock throughput, not simulation
            # semantics — it belongs to the informational wall report.
            if key.startswith("cycles_") and key != "cycles_per_sec":
                out[f"{row['name']}.{key}"] = int(val)
    sweep = bench.get("policy_sweep", {})
    for row in sweep.get("schedulers", []):
        out[f"policy_sweep.cycles_{row['scheduler']}"] = int(row["cycles"])
    for row in bench.get("chunking", {}).get("rows", []):
        for sched, cycles in row.get("cycles", {}).items():
            out[f"{row['name']}.cycles_{sched}"] = int(cycles)
    return out


def _surrogate_quality(baseline: dict, fresh: dict) -> list[str]:
    """Blocking quality-floor violations in the fresh surrogate section.

    Rank rows carry no ``cycles_*`` keys, so the missing-row protection in
    the cycle diff never covers them — a baseline quality row that vanishes
    from the fresh run must fail here, or the Spearman/prune gates would
    silently disappear.
    """
    bad = []
    fresh_rows = {row["name"]: row
                  for row in fresh.get("surrogate", {}).get("rows", [])}
    for row in baseline.get("surrogate", {}).get("rows", []):
        if ("spearman" in row or "prune_gap" in row) \
                and row["name"] not in fresh_rows:
            bad.append(f"{row['name']}: quality row missing from fresh run")
    for row in fresh_rows.values():
        if "spearman" in row and row["spearman"] < SPEARMAN_FLOOR:
            bad.append(f"{row['name']}: spearman {row['spearman']} "
                       f"< floor {SPEARMAN_FLOOR}")
        if "prune_gap" in row and row["prune_gap"] > PRUNE_GAP_MAX:
            bad.append(f"{row['name']}: prune_gap {row['prune_gap']} "
                       f"> max {PRUNE_GAP_MAX} "
                       f"(pruned_best {row.get('pruned_best')} vs "
                       f"exhaustive_best {row.get('exhaustive_best')})")
    return bad


def _guided_quality(fresh: dict) -> list[str]:
    """Blocking guided-annealing floor violations in the fresh run.

    Two relations per ``guided`` row, both exact deterministic integers:
    the surrogate gate must keep full-cost evaluations at or under
    ``GUIDED_EVAL_RATIO_MAX`` of the unguided budget, and the guided search
    must reach equal-or-better simulated cycles than the unguided annealer
    of the same run. (Vanished guided rows are caught by the cycle diff —
    they carry ``cycles_*`` keys.)
    """
    bad = []
    for row in fresh.get("guided", {}).get("rows", []):
        if {"cost_evals", "cost_evals_unguided"} <= row.keys():
            # Exact integer comparison — the reported eval_ratio is rounded
            # for display and could hide a hairline violation.
            if row["cost_evals"] > GUIDED_EVAL_RATIO_MAX \
                    * row["cost_evals_unguided"]:
                bad.append(f"{row['name']}: cost_evals {row['cost_evals']} "
                           f"> {GUIDED_EVAL_RATIO_MAX} * unguided budget "
                           f"{row['cost_evals_unguided']}")
        elif "eval_ratio" in row and row["eval_ratio"] > GUIDED_EVAL_RATIO_MAX:
            bad.append(f"{row['name']}: eval_ratio {row['eval_ratio']} "
                       f"> max {GUIDED_EVAL_RATIO_MAX}")
        if {"cycles_guided", "cycles_unguided"} <= row.keys() \
                and row["cycles_guided"] > row["cycles_unguided"]:
            bad.append(f"{row['name']}: guided {row['cycles_guided']} "
                       f"cycles > unguided {row['cycles_unguided']}")
    return bad


def _telemetry_counters(baseline: dict, fresh: dict) -> list[str]:
    """Blocking instrument drift in the ``telemetry`` section.

    ``ctr_*`` keys are the telemetry traces reduced to scalars (stall
    attribution, deflection split, busiest-link cycles, pick counts) for a
    deterministic workload — the instrument's own output. Unlike cycle
    counts, *any* change (up or down) is a failure: a counter that moved
    without the simulation moving means the instrument drifted, which is a
    semantics bug even if it looks like an "improvement". Changing counter
    definitions deliberately requires updating the committed snapshot.
    """
    bad = []
    fresh_rows = {row["name"]: row
                  for row in fresh.get("telemetry", {}).get("rows", [])}
    for row in baseline.get("telemetry", {}).get("rows", []):
        new = fresh_rows.get(row["name"])
        for key, base in sorted(row.items()):
            if not key.startswith("ctr_"):
                continue
            if new is None:
                bad.append(f"{row['name']}: telemetry row missing from "
                           f"fresh run")
                break
            if key not in new:
                bad.append(f"{row['name']}.{key}: missing (was {base})")
            elif int(new[key]) != int(base):
                bad.append(f"{row['name']}.{key}: {base} -> {new[key]} "
                           f"(counters must match bit-exactly)")
    return bad


def _service_gates(baseline: dict, fresh: dict) -> list[str]:
    """Blocking placement-service contract violations.

    ``svc_*`` keys are exact deterministic integers (cached / fresh cycle
    pairs, hit/miss/simulation counters, frontier point counts) — like the
    telemetry ``ctr_*`` counters they are gated bit-exact in BOTH
    directions against the committed snapshot; a moved counter means the
    caching layer changed behavior even if cycle counts look fine. Two
    fresh-run relations also block: ``svc_cycles_cached`` must equal
    ``svc_cycles_fresh`` row by row (a cache hit must be indistinguishable
    from recomputation), and the stream ``hit_rate`` must clear
    ``SERVICE_HIT_RATE_FLOOR`` (every repeat query must actually hit).
    """
    bad = []
    fresh_rows = {row["name"]: row
                  for row in fresh.get("service", {}).get("rows", [])}
    for row in baseline.get("service", {}).get("rows", []):
        new = fresh_rows.get(row["name"])
        for key, base in sorted(row.items()):
            if not key.startswith("svc_"):
                continue
            if new is None:
                bad.append(f"{row['name']}: service row missing from "
                           f"fresh run")
                break
            if key not in new:
                bad.append(f"{row['name']}.{key}: missing (was {base})")
            elif int(new[key]) != int(base):
                bad.append(f"{row['name']}.{key}: {base} -> {new[key]} "
                           f"(service counters must match bit-exactly)")
    for row in fresh_rows.values():
        if {"svc_cycles_cached", "svc_cycles_fresh"} <= row.keys() \
                and row["svc_cycles_cached"] != row["svc_cycles_fresh"]:
            bad.append(f"{row['name']}: cached {row['svc_cycles_cached']} "
                       f"!= fresh {row['svc_cycles_fresh']} cycles")
        if "hit_rate" in row and row["hit_rate"] < SERVICE_HIT_RATE_FLOOR:
            bad.append(f"{row['name']}: hit_rate {row['hit_rate']} "
                       f"< floor {SERVICE_HIT_RATE_FLOOR}")
    return bad


def _wall_times(bench: dict) -> dict[str, float]:
    out: dict[str, float] = {}
    rows = list(bench.get("fig1", []))
    for section in ("placement", "eject", "surrogate", "guided", "fig1_full",
                    "telemetry", "service"):
        rows += bench.get(section, {}).get("rows", [])
    for row in rows:
        out[f"{row['name']}.wall_s"] = float(row["wall_s"])
        if "cycles_per_sec" in row:
            out[f"{row['name']}.cycles_per_sec"] = float(row["cycles_per_sec"])
    return out


def main(baseline_path: str, fresh_path: str) -> int:
    with open(baseline_path) as f:
        baseline = json.load(f)
    with open(fresh_path) as f:
        fresh = json.load(f)

    base_cyc = _cycle_counts(baseline)
    new_cyc = _cycle_counts(fresh)

    regressions, improvements = [], []
    for name, base in sorted(base_cyc.items()):
        if name not in new_cyc:
            regressions.append(f"{name}: missing from fresh run (was {base})")
            continue
        new = new_cyc[name]
        if new > base:
            regressions.append(f"{name}: {base} -> {new} (+{new - base})")
        elif new < base:
            improvements.append(f"{name}: {base} -> {new} ({new - base})")

    for name in sorted(set(new_cyc) - set(base_cyc)):
        print(f"NEW     {name} = {new_cyc[name]} (no baseline)")
    for line in improvements:
        print(f"BETTER  {line}")

    # Wall-clock: informational only.
    base_wall = _wall_times(baseline)
    for name, new in sorted(_wall_times(fresh).items()):
        base = base_wall.get(name)
        delta = "" if base is None else f" (baseline {base})"
        print(f"WALL    {name} = {new}{delta}")

    quality = _surrogate_quality(baseline, fresh)
    guided = _guided_quality(fresh)
    telem = _telemetry_counters(baseline, fresh)
    service = _service_gates(baseline, fresh)
    failures = regressions + quality + guided + telem + service
    if failures:
        if regressions:
            print(f"\nFAIL: {len(regressions)} cycle-count regression(s):")
            for line in regressions:
                print(f"  {line}")
        if quality:
            print(f"\nFAIL: {len(quality)} surrogate quality-floor "
                  f"violation(s):")
            for line in quality:
                print(f"  {line}")
        if guided:
            print(f"\nFAIL: {len(guided)} guided-annealing floor "
                  f"violation(s):")
            for line in guided:
                print(f"  {line}")
        if telem:
            print(f"\nFAIL: {len(telem)} telemetry counter drift(s):")
            for line in telem:
                print(f"  {line}")
        if service:
            print(f"\nFAIL: {len(service)} service contract violation(s):")
            for line in service:
                print(f"  {line}")
        return 1
    print(f"\nOK: {len(base_cyc)} tracked cycle counts, no regressions.")
    return 0


if __name__ == "__main__":
    if len(sys.argv) != 3:
        print(__doc__)
        sys.exit(2)
    sys.exit(main(sys.argv[1], sys.argv[2]))
