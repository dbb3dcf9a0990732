"""The reduction of a trace to the program's spans and scopes, on hand-made
traces and on a small trace recorded on a TPU v5e (arrow_lu(2, 4, 2) on a
2x2 overlay: one solo ``repro.run``, then one batch of four, each in a
``bench.query`` span; event names shortened to their labels), and the
tracing tool end to end on the CPU."""
import glob
import gzip
import json
import os

import pytest

import repro
from bench import harness, stage_trace
from bench.metrics import stage_reduction as sr
from bench.metrics import trace_reduction as tr

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures",
                       "trace_v5e_scoped.json.gz")
#: The hand-made traces' time unit: 10 us in ns (host events shorter than
#: 100 us are not read), and in seconds.
UNIT, UNIT_S = 10_000, 1e-5


def _hand_made():
    """One device; a ``while`` over scoped leaves on the ops line, a module
    event on another line that outlasts them, then lost trace buffers; two
    query spans, a pack span half inside the first and one outside both."""
    body = "jit(_run_jit)/while/body/while/body/"
    ops = [[10, 50, "%while.1 while", ""],
           [12, 10, "%fusion.1 fusion", body + "ovl.route/ge"],
           [22, 5, "%fusion.2 fusion", body + "vmap(ovl.select)/and"],
           [30, 20, "%fusion.3 fusion", body + "ovl.eject_fire/scatter"],
           [52, 4, "%copy.1 copy", ""],
           [80, 10, "%fusion.4 fusion", "jit(_run_jit)/ovl.chunk_repair/eq"]]
    modules = [[8, 62, "jit__run_jit(1)", ""], [80, 10, "jit__run_jit(1)", ""]]
    lost = [[92, 6, sr.DROPPED_EVENT, ""]]
    host = [[0, 75, "bench.query"], [78, 20, "bench.query"],
            [-5, 10, repro.SPAN_PACK], [60, 10, repro.SPAN_UNPACK],
            [100, 5, repro.SPAN_PACK]]
    scale = (lambda evs: [[s * UNIT, d * UNIT, *rest] for s, d, *rest in evs])
    return {"device": {"/device:TPU:0": {"XLA Modules": scale(modules),
                                          "XLA Ops": scale(ops),
                                          "XLA TraceMe": scale(lost)}},
            "host": scale(host)}


def test_self_time_leaves_the_enclosing_loop_out():
    trace = _hand_made()
    st = sr.stage_times(trace, "bench.query")
    got = st["stages"]
    assert got[repro.SCOPE_ROUTE] == pytest.approx(10 * UNIT_S)
    assert got[repro.SCOPE_SELECT] == pytest.approx(5 * UNIT_S)
    assert got[repro.SCOPE_EJECT_FIRE] == pytest.approx(20 * UNIT_S)
    assert got[repro.SCOPE_CHUNK_REPAIR] == pytest.approx(10 * UNIT_S)
    assert got[repro.SCOPE_INJECT] == got[repro.SCOPE_TERMINATE] == 0
    # The loop owns 10-12, 27-30, 50-52 and 56-60 units, which no body op
    # covers, beside the copy's 52-56; the module event alone covers 8-10
    # and 60-70.
    assert got[sr.UNSCOPED] == pytest.approx(15 * UNIT_S)
    assert got[sr.NO_OP] == pytest.approx(12 * UNIT_S)
    assert got[sr.DROPPED] == pytest.approx(6 * UNIT_S)
    assert st["recorded_s"] == pytest.approx(72 * UNIT_S)
    assert dict(st["unscoped_ops"]) == pytest.approx(
        {"%while.1 while": 11 * UNIT_S, "%copy.1 copy": 4 * UNIT_S})
    # Stages and unscoped split the busy time inside the spans exactly.
    red = tr.reduce_trace(sr.plain(trace), "bench.query")
    assert st["busy_s"] == pytest.approx(
        sum(s["device_s"] for s in red["spans"]))
    assert sum(got.values()) == pytest.approx(st["busy_s"])


def test_host_spans_sum_inside_the_query_spans():
    spans = sr.host_spans(_hand_made(), "bench.query")
    assert set(spans) == {repro.SPAN_PACK, repro.SPAN_UNPACK}
    assert spans[repro.SPAN_PACK] == pytest.approx(
        {"s": 5 * UNIT_S, "device_s": 0.0, "count": 1})
    # 60-70 units: the module event covers it, no op event does.
    assert spans[repro.SPAN_UNPACK] == pytest.approx(
        {"s": 10 * UNIT_S, "device_s": 10 * UNIT_S, "count": 1})


def test_no_spans_or_no_device_reads_nothing():
    trace = _hand_made()
    assert sr.stage_times(trace, "other.span") is None
    assert sr.host_spans(trace, "other.span") == {}
    assert sr.stage_times({"device": {}, "host": trace["host"]},
                          "bench.query") is None


@pytest.mark.parametrize("op_name,scope", [
    ("jit(_run_jit)/while/body/ovl.route/ge", repro.SCOPE_ROUTE),
    ("jit(_run_batch_jit)/while/body/vmap(ovl.select)/and", repro.SCOPE_SELECT),
    ("jit(f)/ovl.chunk_repair/jit(_where)/select_n", repro.SCOPE_CHUNK_REPAIR),
    ("jit(_run_jit)/while/body/dynamic_update_slice", sr.UNSCOPED),
    ("", sr.UNSCOPED),
])
def test_scope_of(op_name, scope):
    assert sr.scope_of(op_name) == scope


def test_span_metrics_read_stages_and_spans():
    # Half the busy time was recorded: the split of that half stands for all.
    stages = {"busy_s": 0.2, "recorded_s": 0.1,
              "stages": {s: (i + 1) * 0.5e-3
                         for i, s in enumerate(repro.DEVICE_SCOPES)}}
    spans = {repro.SPAN_PACK: {"s": 4.0, "device_s": 0.5, "count": 2},
             repro.SPAN_TO_DEVICE: {"s": 0.2, "device_s": 0.0, "count": 2},
             repro.SPAN_DISPATCH: {"s": 0.1, "device_s": 0.05, "count": 2},
             repro.SPAN_UNPACK: {"s": 9.0, "device_s": 8.9, "count": 2}}
    got = stage_trace.span_metrics(stages, spans, cycles=1000, queries=2)
    assert got["stage_us_per_cycle.inject.sim"] == pytest.approx(1.0)
    assert got["stage_us_per_cycle.chunk_repair.sim"] == pytest.approx(6.0)
    assert got["pack_s_per_query.sim"] == pytest.approx(1.75)
    assert got["dispatch_s_per_query.sim"] == pytest.approx(0.175)
    assert len(got) == 8
    # Nothing to read: a trace with no device and a program with no spans.
    assert stage_trace.span_metrics(None, {}, cycles=1000, queries=2) == {}


@pytest.fixture(scope="module")
def recorded():
    with gzip.open(FIXTURE, "rt") as f:
        return json.load(f)


def test_recorded_v5e_trace_carries_op_names(recorded):
    ops = recorded["device"]["/device:TPU:0"]["XLA Ops"]
    scopes = {sr.scope_of(e[3]) for e in ops}
    assert scopes == set(repro.DEVICE_SCOPES) | {sr.UNSCOPED}


def test_reduce_recorded_v5e_stages(recorded):
    st = sr.stage_times(recorded, "bench.query")
    red = tr.reduce_trace(sr.plain(recorded), "bench.query")
    assert st["busy_s"] == pytest.approx(
        sum(s["device_s"] for s in red["spans"]), rel=1e-9)
    assert sum(st["stages"].values()) == pytest.approx(st["busy_s"], rel=1e-9)
    # Numbers as this reduction first read them from the recorded trace.
    assert st["busy_s"] == pytest.approx(0.016332023, rel=1e-9)
    scoped = sum(st["stages"][s] for s in repro.DEVICE_SCOPES)
    assert scoped / st["busy_s"] == pytest.approx(0.9138, abs=1e-4)
    assert st["stages"][repro.SCOPE_EJECT_FIRE] == max(st["stages"].values())
    assert st["unscoped_ops"][0][0].split(" ")[1] == "while"


def test_reduce_recorded_v5e_host_spans(recorded):
    spans = sr.host_spans(recorded, "bench.query")
    assert set(spans) == set(repro.HOST_SPANS)
    assert {v["count"] for v in spans.values()} == {2}
    run = spans[repro.SPAN_RUN]
    assert all(v["s"] <= run["s"] for v in spans.values())
    assert sum(spans[n]["s"] for n in repro.PACK_SPANS) <= (
        spans[repro.SPAN_PACK]["s"])
    # The device runs the program inside dispatch and unpack, not in the pack.
    assert spans[repro.SPAN_PACK]["device_s"] < 1e-5
    assert spans[repro.SPAN_UNPACK]["device_s"] > 0.5 * run["device_s"]


def test_read_xplane_ops_reads_what_read_xplane_reads(tmp_path):
    import jax

    from repro.core import workloads as wl

    graph = wl.arrow_lu_graph(2, 4, 2, seed=3)
    repro.run(graph, nx=2, ny=2)
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("bench.query"):
            repro.run(graph, nx=2, ny=2)
    (path,) = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)
    ops = sr.read_xplane_ops(path)
    assert sr.plain(ops) == tr.read_xplane(path)
    # Program spans are kept however short they are.
    assert {e[2] for e in ops["host"]} >= set(repro.HOST_SPANS)


def test_stage_trace_runs_a_cell_on_the_cpu(tiny_root):
    cell = harness.load_cell(tiny_root, "tiny.sweep")
    out = stage_trace.trace_cell(cell, 2**33 + 5, untraced=1,
                                 log=lambda s: None)
    assert out["check"]["failed"] == 0
    assert len(out["untraced_query_s"]) == len(out["traced_query_s"]) == 1
    assert set(out["host_spans"]) == set(repro.HOST_SPANS)
    # The CPU has no device plane: the device metrics have nothing to read.
    assert out["reduced"] is None and out["stages"] is None
    assert set(out["per_layer"]) == {"pack_s_per_query.sim",
                                     "dispatch_s_per_query.sim"}
