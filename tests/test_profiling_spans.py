"""The simulator's own profiler spans and scopes.

Host spans (``jax.profiler.TraceAnnotation``) nest as documented under an
open profiler session; the device scopes (``jax.named_scope``) reach the
``op_name`` metadata of the engines' programs; and neither moves a result.
The names are the constants in :mod:`repro`.
"""
import glob
import re

import jax
import numpy as np
import pytest

import repro
from repro.core import overlay, schedulers
from repro.core import workloads as wl
from repro.core.criticality import height
from repro.core.overlay import OverlayConfig
from repro.core.partition import build_graph_memory

GRID = 2
BATCH = [OverlayConfig(select_latency=k) for k in (1, 2, 3)]


@pytest.fixture(scope="module")
def graph():
    return wl.arrow_lu_graph(2, 4, 2, seed=3)


def _call(graph, batched):
    if batched:
        return repro.run(graph, batch=BATCH, nx=GRID, ny=GRID)
    return [repro.run(graph, OverlayConfig(), nx=GRID, ny=GRID)]


def _host_spans(trace_dir) -> dict:
    """``{name: [(start_ns, end_ns), ...]}`` of the ``repro.*`` host events
    in the one profile written under ``trace_dir``."""
    (path,) = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    spans: dict = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("repro."):
                    spans.setdefault(e.name, []).append(
                        (e.start_ns, e.start_ns + e.duration_ns))
    return spans


def _inside(child, parents) -> bool:
    a, b = child
    return any(lo <= a and b <= hi for lo, hi in parents)


@pytest.mark.parametrize("batched", [False, True], ids=["solo", "batch"])
def test_host_spans_nest_under_run(graph, tmp_path, batched):
    _call(graph, batched)  # compile outside the session
    with jax.profiler.trace(str(tmp_path)):
        _call(graph, batched)
    spans = _host_spans(tmp_path)
    assert set(spans) == set(repro.HOST_SPANS)
    assert len(spans[repro.SPAN_RUN]) == 1
    for name, intervals in spans.items():
        for iv in intervals:
            assert _inside(iv, spans[repro.SPAN_RUN]), name
    for name in repro.PACK_SPANS:
        for iv in spans[name]:
            assert _inside(iv, spans[repro.SPAN_PACK]), name
    # The pack's steps run one after another, in the documented order.
    starts = [spans[n][0][0] for n in repro.PACK_SPANS]
    assert starts == sorted(starts)


@pytest.mark.parametrize("batched", [False, True], ids=["solo", "batch"])
def test_results_identical_with_profiler_open(graph, tmp_path, batched):
    plain = _call(graph, batched)
    with jax.profiler.trace(str(tmp_path)):
        traced = _call(graph, batched)
    assert len(plain) == len(traced)
    for a, b in zip(plain, traced):
        assert (a.cycles, a.done, a.delivered, a.deflections, a.busy_cycles,
                a.noc_deflections, a.eject_deflections) == (
                    b.cycles, b.done, b.delivered, b.deflections,
                    b.busy_cycles, b.noc_deflections, b.eject_deflections)
        np.testing.assert_array_equal(a.values, b.values)


def test_criticality_span_carries_the_peel_levels(graph, tmp_path):
    with jax.profiler.trace(str(tmp_path)):
        build_graph_memory(graph, GRID, GRID)
    (path,) = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)
    stats = [dict(e.stats)
             for plane in jax.profiler.ProfileData.from_file(path).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events
             if e.name == repro.SPAN_CRITICALITY]
    assert stats == [{"levels": int(height(graph).max()) + 1}]


def test_pack_spans_carry_the_padded_shapes(tmp_path):
    g = wl.kron_pagerank_graph(6, seed=1)
    with jax.profiler.trace(str(tmp_path)):
        gm = build_graph_memory(g, GRID, GRID)
    (path,) = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)
    stats = {e.name: dict(e.stats)
             for plane in jax.profiler.ProfileData.from_file(path).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events
             if e.name in (repro.SPAN_PACK_NODES, repro.SPAN_PACK_EDGES)}
    per_pe = np.bincount(gm.node_pe, weights=g.fanout_count(),
                         minlength=GRID * GRID)
    assert stats[repro.SPAN_PACK_NODES] == {"lmax": gm.lmax}
    edges = stats[repro.SPAN_PACK_EDGES]
    assert edges["emax"] == gm.emax == edges["edges_max"] == per_pe.max()
    assert edges["edges_mean"] == pytest.approx(per_pe.mean())


def _lowered(graph, program):
    gm = build_graph_memory(graph, GRID, GRID)
    g = dict(overlay.device_graph(gm))
    cfg = OverlayConfig()
    if program == "run_jit":
        return overlay._run_jit.lower(g, cfg, GRID, GRID)
    words = g["opcode"].shape[2] // 32
    ooo = schedulers.get("ooo")
    return overlay._run_batch_jit.lower(
        g, cfg, ("ooo",), np.zeros(len(BATCH), np.int32),
        np.asarray([ooo.sel_lat(c, words) for c in BATCH], np.int32),
        np.asarray([c.max_cycles for c in BATCH], np.int32), GRID, GRID)


def _scopes(op_name: str) -> set:
    """The device scopes named in ``op_name``: a path component is the scope
    itself, or the scope under a transformation (``vmap(ovl.route)``)."""
    parts = {re.sub(r"^\w+\((.*)\)$", r"\1", p) for p in op_name.split("/")}
    return parts & set(repro.DEVICE_SCOPES)


@pytest.mark.parametrize("program", ["run_jit", "run_batch_jit"])
def test_lowered_programs_carry_every_scope(graph, program):
    text = _lowered(graph, program).as_text(dialect="hlo", debug_info=True)
    found = set()
    for name in re.findall(r'op_name="([^"]*)"', text):
        found |= _scopes(name)
    assert found == set(repro.DEVICE_SCOPES)
