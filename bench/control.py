"""The lower-precision control of the value check of ``repro_run`` cells.

The plain reference, computed in bfloat16 (one step below the float32 that
the configuration states), is put in the program's place and driven through
the harness like the program: the same queries from the same seeds, the
same check. It carries the tracked statistics, so the value gap alone
decides, and that gap has to exceed the limit.

``python3 bench/control.py --workload <cell> --seeds 1,2,3`` prints one
reading per seed and, last, a JSON summary. The benchmark's own runs never
run it.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import types

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if __name__ == "__main__":
    sys.path[:0] = [_ROOT, os.path.join(_ROOT, "src")]

from bench import harness, reference  # noqa: E402


def control_entry(cell: harness.Cell):
    """A stand-in for ``repro.run`` that answers with the bfloat16 reference
    and the tracked statistics of the call it was given."""
    from repro.core.graph import DataflowGraph

    spec = cell.spec
    calls = [cell.driver.overlay_configs(c) for c in spec.calls]
    lv = None

    def run(graph, cfg=None, *, batch=None, nx=None, ny=None):
        nonlocal lv
        configs = list(batch) if batch is not None else [cfg]
        tracked = spec.expected["calls"][calls.index(configs)]
        values = None  # the warm-up's empty memory
        if isinstance(graph, DataflowGraph):
            if lv is None:  # every query has the same structure
                lv = reference.levelize(graph.opcode, graph.fanout_ptr,
                                        graph.fanout_dst, graph.fanout_slot)
            values = reference.evaluate(lv, graph.initial_values,
                                        reference.CONTROL_DTYPE)
        out = [types.SimpleNamespace(done=True, values=values, **row)
               for row in tracked]
        return out if batch is not None else out[0]

    return run


def readings(cell: harness.Cell, seeds) -> list:
    """The control's harness lines, one per seed, each a one-query window."""
    import jax

    return [harness.run_cell(cell, seed, 0.0, False, time.perf_counter(),
                             jax.devices(), entry=control_entry(cell),
                             log=lambda s: None)
            for seed in seeds]


def main(argv) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    args = ap.parse_args(argv)
    cell = harness.load_cell(_ROOT, args.workload)
    seeds = [int(s) for s in args.seeds.split(",")]
    out = []
    for seed, line in zip(seeds, readings(cell, seeds)):
        gap = line["checks"]["value_gap"]
        print(f"seed {seed}: {line['attempted']} queries, value_gap "
              f"{gap['value']} (limit {gap['limit']}), correct {line['correct']}")
        out.append({"seed": seed, "value_gap": gap["value"],
                    "correct": line["correct"]})
    print(json.dumps({"workload": args.workload, "control": "bfloat16 reference",
                      "readings": out}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
