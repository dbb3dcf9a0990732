"""repro.telemetry contract tests.

Three claims pin the tentpole down:

  1. *Telemetry is invisible when off*: PR-6-era results (cycles, stats,
     node values) are bit-identical with the split deflection counters in
     place, for every policy x engine x chunk depth.
  2. *Telemetry is an observer when on*: simulated cycles/stats don't move,
     traces are bit-identical across engines, chunk depths and entry points
     (batched row b == solo run of config b; sharded == single-device), and
     trace sums equal the scalar stat counters exactly.
  3. *Exports are well-formed*: the Perfetto/Chrome-trace JSON round-trips
     through ``json`` and carries exactly the advertised counter-track
     count; the report's integers are consistent with the stats.
"""
import json

import numpy as np
import pytest

from repro.core import schedulers
from repro.core import workloads as wl
from repro.core.overlay import OverlayConfig, simulate, simulate_batch
from repro.core.partition import build_graph_memory
from repro.telemetry import TelemetrySpec
from repro.telemetry.perfetto import track_count

ALL_POLICIES = sorted(schedulers.REGISTRY)
SPEC = TelemetrySpec(buckets=16, bucket_cycles=8)


def _gm(sched="ooo", nx=2, ny=2):
    g = wl.arrow_lu_graph(3, 6, 4, seed=5)
    policy = schedulers.get(sched)
    return build_graph_memory(g, nx, ny,
                              criticality_order=policy.wants_criticality_order)


def _stats(r):
    return (r.done, r.cycles, r.deflections, r.busy_cycles, r.delivered)


@pytest.fixture(scope="module")
def reference_runs():
    """Per policy: (telemetry-off, telemetry-on) check_every=1 references."""
    out = {}
    for sched in ALL_POLICIES:
        gm = _gm(sched)
        off = simulate(gm, OverlayConfig(scheduler=sched, check_every=1))
        on = simulate(gm, OverlayConfig(scheduler=sched, check_every=1,
                                        telemetry=SPEC))
        assert off.done and on.done
        out[sched] = (off, on)
    return out


# ---------------------------------------------------------------------------
# 1. telemetry=None leaves the model bit-exact (incl. the deflection split)
# ---------------------------------------------------------------------------

# Engine x chunk-depth sampling: the jnp reference path runs the full policy
# matrix; the select engine runs representative policies here because its
# full policy x chunk-depth off-matrix is already pinned bit-for-bit by
# tests/test_chunked.py against the same check_every=1 reference.
OFF_MATRIX = [
    ("jnp", 1, ALL_POLICIES), ("jnp", 8, ALL_POLICIES),
    ("jnp", 32, ALL_POLICIES),
    ("select", 8, ("ooo", "scan")),
]


@pytest.mark.parametrize("engine,check_every,policies", OFF_MATRIX)
def test_off_bit_exact(engine, check_every, policies, reference_runs):
    for sched in policies:
        gm = _gm(sched)
        r = simulate(gm, OverlayConfig(scheduler=sched, engine=engine,
                                       check_every=check_every))
        ref = reference_runs[sched][0]
        assert _stats(r) == _stats(ref), (sched, check_every, engine)
        np.testing.assert_array_equal(r.values, ref.values)
        assert r.telemetry is None


def test_deflection_split_sums(reference_runs):
    for sched in ALL_POLICIES:
        r = reference_runs[sched][0]
        assert r.noc_deflections + r.eject_deflections == r.deflections
        assert r.noc_deflections >= 0 and r.eject_deflections >= 0


# ---------------------------------------------------------------------------
# 2. telemetry on: cycles unchanged, traces engine/chunk/entry-point exact
# ---------------------------------------------------------------------------

def _assert_same_traces(a, b, ctx):
    assert set(a.traces) == set(b.traces), ctx
    for k in a.traces:
        np.testing.assert_array_equal(a.traces[k], b.traces[k], err_msg=str((ctx, k)))


ON_MATRIX = [
    ("jnp", 1, ALL_POLICIES), ("jnp", 8, ALL_POLICIES),
    ("jnp", 32, ALL_POLICIES),
    ("select", 8, ("ooo", "lru_flat")),
    ("select", 32, ("scan",)),
    ("select", 1, ALL_POLICIES),
    ("select", 32, ("ooo", "lru_flat")),
]


@pytest.mark.parametrize("engine,check_every,policies", ON_MATRIX)
def test_on_bit_exact(engine, check_every, policies, reference_runs):
    for sched in policies:
        gm = _gm(sched)
        r = simulate(gm, OverlayConfig(scheduler=sched, engine=engine,
                                       check_every=check_every, telemetry=SPEC))
        off, on = reference_runs[sched]
        # tracing never moves the model...
        assert _stats(r) == _stats(off), (sched, check_every, engine)
        np.testing.assert_array_equal(r.values, off.values)
        # ...and the traces themselves are engine/chunk-depth invariant
        # (stall_no_ready is the overshoot-repair witness).
        _assert_same_traces(r.telemetry, on.telemetry, (sched, check_every, engine))


def test_trace_sums_equal_counters(reference_runs):
    for sched in ALL_POLICIES:
        r = reference_runs[sched][1]
        t = r.telemetry.traces
        assert int(t["pe_busy"].sum()) == r.busy_cycles
        assert int(t["defl_noc"].sum()) == r.noc_deflections
        assert int(t["defl_eject"].sum()) == r.eject_deflections
        assert int(t["eject_grant"].sum()) == r.delivered
        # every PE-cycle is attributed at most once per stall cause, and
        # no-ready stalls can never exceed total idle PE-cycles
        total_pe_cycles = r.cycles * r.telemetry.nx * r.telemetry.ny
        occupied = int(t["pe_occ"].sum())
        assert int(t["stall_no_ready"].sum()) <= total_pe_cycles - occupied
        assert (t["stall_no_ready"] >= 0).all()  # overshoot repair exact
        # wavefront is monotone and ends at the total fire count
        wf = r.telemetry.wavefront()
        assert (np.diff(wf) >= 0).all() and wf[-1] == r.busy_cycles


def test_batched_rows_match_solo():
    g = wl.arrow_lu_graph(3, 6, 4, seed=5)
    gm = build_graph_memory(g, 4, 4, criticality_order=True)
    policies = ("ooo", "lru_flat", "scan")
    rs = simulate_batch(gm, [OverlayConfig(scheduler=p, telemetry=SPEC)
                             for p in policies])
    for b, p in enumerate(policies):
        solo = simulate(gm, OverlayConfig(scheduler=p, telemetry=SPEC))
        assert _stats(rs[b]) == _stats(solo), p
        _assert_same_traces(rs[b].telemetry, solo.telemetry, p)


def test_batched_requires_uniform_telemetry():
    gm = _gm()
    with pytest.raises(ValueError, match="uniform telemetry"):
        simulate_batch(gm, [OverlayConfig(telemetry=SPEC),
                            OverlayConfig(telemetry=None)])


def test_sharded_matches_solo():
    import jax
    from jax.sharding import Mesh
    from repro.core import distributed

    gm = _gm(nx=4, ny=4)
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
    solo = simulate(gm, OverlayConfig(telemetry=SPEC))
    r = distributed.simulate_sharded(gm, mesh, OverlayConfig(telemetry=SPEC))
    assert _stats(r) == _stats(solo)
    _assert_same_traces(r.telemetry, solo.telemetry, "sharded")
    rs = distributed.simulate_batch_sharded(
        gm, mesh, [OverlayConfig(scheduler=s, telemetry=SPEC)
                   for s in ("ooo", "inorder")])
    # rows share gm's packed memory image, so each solo reference must too
    for b, s in enumerate(("ooo", "inorder")):
        ref = simulate(gm, OverlayConfig(scheduler=s, telemetry=SPEC))
        assert _stats(rs[b]) == _stats(ref), s
        _assert_same_traces(rs[b].telemetry, ref.telemetry, ("batch-sharded", s))


def test_spec_validation():
    with pytest.raises(ValueError, match="buckets"):
        TelemetrySpec(buckets=0)
    with pytest.raises(ValueError, match="records nothing"):
        TelemetrySpec(pe=False, links=False, eject=False, sched=False,
                      stalls=False)
    with pytest.raises(TypeError, match="TelemetrySpec"):
        OverlayConfig(telemetry="yes please")
    # partial specs only allocate what they trace
    slim = TelemetrySpec(pe=True, links=False, eject=False, sched=False,
                        stalls=False)
    r = simulate(_gm(), OverlayConfig(telemetry=slim))
    assert set(r.telemetry.traces) == {"pe_busy", "pe_occ"}
    assert int(r.telemetry.traces["pe_busy"].sum()) == r.busy_cycles


def test_bucket_clamp_keeps_sums():
    # horizon far shorter than the run: everything past it lands in the
    # last bucket instead of being dropped
    tiny = TelemetrySpec(buckets=2, bucket_cycles=4)
    r = simulate(_gm(), OverlayConfig(telemetry=tiny))
    t = r.telemetry.traces
    assert r.cycles > tiny.horizon
    assert int(t["pe_busy"].sum()) == r.busy_cycles
    assert int(t["pe_busy"][-1].sum()) > 0


# ---------------------------------------------------------------------------
# 3. exports
# ---------------------------------------------------------------------------

def test_perfetto_export_valid_json(tmp_path, reference_runs):
    r = reference_runs["ooo"][1]
    path = tmp_path / "trace.json"
    r.telemetry.export_perfetto(str(path))
    loaded = json.loads(path.read_text())
    assert loaded["otherData"]["generator"] == "repro.telemetry"
    counters = [e for e in loaded["traceEvents"] if e["ph"] == "C"]
    assert counters and all("ts" in e and "args" in e for e in counters)
    tracks = {(e["pid"], e["name"]) for e in counters}
    assert len(tracks) == track_count(SPEC, 2, 2)
    # 2x2 grid, all groups on: 4 PE + 1 wavefront + 12 link + 4 eject + 1
    assert len(tracks) == 22


def test_report_consistent(reference_runs):
    r = reference_runs["ooo"][1]
    rep = r.telemetry.report(top_k=3)
    assert rep["cycles"] == r.cycles
    assert rep["pe"]["busy_total"] == r.busy_cycles
    assert rep["links"]["defl_noc"] == r.noc_deflections
    assert rep["links"]["defl_eject"] == r.eject_deflections
    assert rep["stalls"]["eject_deflected"] == r.eject_deflections
    assert len(rep["links"]["top"]) == 3
    assert rep["links"]["top"][0]["busy"] == rep["links"]["busy_max"]
    assert 0.0 <= rep["links"]["util_p50"] <= rep["links"]["util_p95"] <= 1.0
    json.dumps(rep)  # report is JSON-serializable as-is (BENCH section)
    heat = r.telemetry.ascii_heatmap("pe_busy")
    assert heat.count("\n") == r.telemetry.nx
