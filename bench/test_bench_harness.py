"""The harness end to end on a tiny cell defined only by data files, with
the look for a chip skipped: sound runs are correct, and the control and
every fault a cell of this benchmark can have are not."""
import dataclasses
import json
import os
import time

import jax
import numpy as np
import pytest

import repro
from bench import control, harness

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(root, workload, entry=None, seconds=0.0, seed=2**33 + 5, trace=False,
         log=lambda s: None):
    cell = harness.load_cell(root, workload)
    return harness.run_cell(cell, seed, seconds, trace, time.perf_counter(),
                            jax.devices(), entry=entry, log=log)


def _members(out, batch):
    return out if batch is not None else [out]


def _unchanged(graph, cfg=None, *, batch=None, nx, ny):
    """Every step returns its state unchanged: no cycle runs, no node fires."""
    out = repro.run(graph, cfg, batch=batch, nx=nx, ny=ny)
    for r in _members(out, batch):
        r.cycles = r.delivered = r.deflections = r.busy_cycles = 0
        r.noc_deflections = r.eject_deflections = 0
        r.done = False
        if isinstance(graph, repro.core.graph.DataflowGraph):
            r.values = np.where(graph.opcode == 0, graph.initial_values, 0)
    return out


def _half_batch(graph, cfg=None, *, batch=None, nx, ny):
    """Half of the batch left out: its results repeat those of the rest."""
    if batch is None:
        return repro.run(graph, cfg, nx=nx, ny=ny)
    half = list(batch)[: (len(batch) + 1) // 2]
    out = repro.run(graph, batch=half, nx=nx, ny=ny)
    return [dataclasses.replace(out[i % len(out)]) for i in range(len(batch))]


def _altered_value(graph, cfg=None, *, batch=None, nx, ny):
    """One node's answer altered where it is produced."""
    out = repro.run(graph, cfg, batch=batch, nx=nx, ny=ny)
    r = _members(out, batch)[-1]
    r.values = r.values.copy()
    r.values[-1] *= np.float32(1.1)
    return out


def _altered_cycles(graph, cfg=None, *, batch=None, nx, ny):
    """One simulation's cycle count altered where it is produced."""
    out = repro.run(graph, cfg, batch=batch, nx=nx, ny=ny)
    _members(out, batch)[0].cycles += 1
    return out


@pytest.mark.parametrize("workload", ["tiny.query", "tiny.sweep"])
def test_sound_run_is_correct(tiny_root, workload):
    line = _run(tiny_root, workload, seconds=0.3)
    assert line["correct"], line["checks"]
    assert line["failed"] == 0 and line["attempted"] >= 1
    assert list(line) == ["correct", "attempted", "failed", "metrics", "device",
                          "checks"]
    assert set(line["metrics"]) == {"sim_cycles_per_s", "setup_s"}
    assert line["metrics"]["sim_cycles_per_s"]["value"] > 0
    assert line["checks"]["value_gap"]["value"] <= line["checks"]["value_gap"]["limit"]


@pytest.mark.parametrize("workload", ["tiny.query", "tiny.sweep"])
def test_control_is_not_correct(tiny_root, workload):
    cell = harness.load_cell(tiny_root, workload)
    for line in control.readings(cell, [1, 2, 3]):
        assert not line["correct"]
        gap = line["checks"]["value_gap"]
        assert gap["value"] > gap["limit"]
        assert line["checks"]["stats_mismatch"]["value"] == 0


@pytest.mark.parametrize("workload,fault,check", [
    ("tiny.query", _unchanged, "not_done"),
    ("tiny.sweep", _unchanged, "not_done"),
    ("tiny.sweep", _half_batch, "stats_mismatch"),
    ("tiny.query", _altered_value, "value_gap"),
    ("tiny.sweep", _altered_value, "value_gap"),
    ("tiny.query", _altered_cycles, "stats_mismatch"),
    ("tiny.sweep", _altered_cycles, "stats_mismatch"),
], ids=["unchanged-query", "unchanged-sweep", "half-batch", "value-query",
        "value-sweep", "cycles-query", "cycles-sweep"])
def test_fault_is_not_correct(tiny_root, workload, fault, check):
    line = _run(tiny_root, workload, entry=fault)
    assert not line["correct"]
    assert line["failed"] == line["attempted"]
    c = line["checks"][check]
    assert c["value"] > c["limit"]


def test_no_window_compiles_and_counts_logged(tiny_root):
    logs = []
    _run(tiny_root, "tiny.sweep", seconds=0.2, seed=7, log=logs.append)
    assert "compilations inside the window: 0" in logs
    assert any(s.startswith("window: ") and "cycles " in s for s in logs)


def test_run_without_tpu_fails_and_prints_no_result(capsys):
    rc = harness.main(["--workload", "lu470k.query", "--seed", "1",
                       "--seconds", "1", "--trace", "0"], time.perf_counter())
    out = capsys.readouterr()
    assert rc != 0
    assert out.out == ""
    assert "no TPU" in out.err


def test_every_cell_of_the_benchmark_loads_from_its_files():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    for w in spec["workloads"]:
        cell = harness.load_cell(ROOT, w["name"])
        assert cell.readers and cell.end_to_end and cell.per_layer
        assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
        assert cell.spec is not None


def test_traced_run_on_cpu_reads_no_device_metric(tiny_root):
    line = _run(tiny_root, "tiny.query", seconds=0.2, seed=3, trace=True)
    # The CPU has no device plane: the per-layer readers find nothing, and a
    # share is never reported as 0 in its place. The traced window is its
    # one traced query.
    assert line["correct"] and line["attempted"] == 1
    assert line["metrics"] == {}
    assert "busy_s" not in line["device"] and "breakdown" not in line


def _query(i, cycles):
    return harness.Query(i, i, 0.0, 0.0, 1.0, [], {"cycles": cycles})


def test_device_time_per_cycle_counts_the_traced_queries_alone():
    read = harness._load_module(
        os.path.join(ROOT, "bench", "metrics", "device_us_per_cycle.sim.py"),
        "device_us_per_cycle").read
    trace = {"spans": [{"s": 1.5, "device_s": 1.0}]}
    run = harness.Run(setup_s=1.0, window_s=3.0,
                      queries=[_query(0, 1000), _query(1, 1000), _query(2, 500)],
                      trace=trace)
    assert read(run) == pytest.approx(1000.0)
    assert read(dataclasses.replace(run, trace=None)) is None


def test_a_cell_of_new_files_calls_another_entry(tiny_root):
    """``tiny.plain``: its driver, traffic (an open loop with repeats) and
    metric are files that no other cell uses, and its queries call the
    program's ``reference_evaluate``, not ``repro.run``."""
    line = _run(tiny_root, "tiny.plain", seconds=0.5)
    assert line["correct"], line["checks"]
    assert set(line["metrics"]) == {"queries_per_s", "setup_s"}
    # About 40 queries a second for half a second, not back to back.
    assert 5 <= line["attempted"] <= 60
    assert line["metrics"]["queries_per_s"]["value"] > 0

    def altered(graph):
        from repro.core.graph import reference_evaluate

        out = reference_evaluate(graph)
        out[-1] += 1
        return out

    line = _run(tiny_root, "tiny.plain", entry=altered, seconds=0.2)
    assert not line["correct"] and line["failed"] == line["attempted"]
