"""The main path's Pallas kernels and chunk programs compile for TPU v5e.

The chip is described, not attached: ``jax.experimental.topologies``
describes a v5e:2x2 host, and each program is lowered for one of its
devices from shapes alone and compiled by the TPU compiler installed with
JAX. What that compiler refuses here would be refused on the chip. Nothing
runs, so these tests say nothing about results or times.

Code that asks ``jax.default_backend()`` still sees the CPU here and would
lower the kernels as interpreted jnp, so every test forces the compiled
path itself (``interpret=False``, or ``ops._interpret`` patched) and checks
that a kernel (``tpu_custom_call``) is really in the compiled program.
"""
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

import repro
from repro.core import overlay
from repro.core import workloads as wl
from repro.core.overlay import OverlayConfig
from repro.core.partition import build_graph_memory
from repro.kernels import lod, ops

#: One block of 256 PE rows at the paper-scale width: L = 1856 slots per PE
#: (fig1 arrow-LU, 470,772 nodes on 16x16) is 58 32-bit RDY words.
ROWS, WORDS = 256, 58
GRID = 16


@pytest.fixture(scope="module")
def one_chip():
    """One device of a described v5e:2x2 host, with JAX's persistent
    compilation cache off: a compile for a described chip is written to the
    cache but cannot be read back without one."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")  # else libtpu logs under /tmp
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        cc.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", enabled)
            cc.reset_cache()


@pytest.fixture(scope="module")
def fig1_graph():
    """The 15,072-node fig1 graph packed on 16x16, criticality-ordered."""
    gm = build_graph_memory(wl.arrow_lu_graph(4, 10, 8, seed=3), GRID, GRID,
                            criticality_order=True)
    return dict(overlay.device_graph(gm))


def _shapes(tree, sharding):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


@pytest.mark.parametrize("kernel", ["lod", "schedule_step",
                                    "rotating_schedule_step"])
def test_lod_kernel_compiles(one_chip, kernel):
    bits = jax.ShapeDtypeStruct((ROWS, WORDS), jnp.uint32, sharding=one_chip)
    row = jax.ShapeDtypeStruct((ROWS,), jnp.int32, sharding=one_chip)
    args = {"lod": (bits,), "schedule_step": (bits, row),
            "rotating_schedule_step": (bits, row, row)}[kernel]
    compiled = getattr(lod, kernel).lower(*args, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _compile_chunk(graph, sharding, engine):
    cfg = OverlayConfig(engine=engine)
    L = graph["opcode"].shape[2]
    K = overlay.resolve_check_every(cfg, GRID, GRID, L, backend="tpu")
    state = jax.eval_shape(lambda g: overlay.init_state(g, cfg), graph)
    chunk = jax.jit(lambda g, s: overlay.make_chunk_fn(
        overlay.make_cycle_fn(g, cfg), K)(s))
    return chunk.lower(_shapes(graph, sharding),
                       _shapes(state, sharding)).compile()


@pytest.mark.parametrize("engine,has_kernel", [("jnp", False),
                                               ("select", True)])
def test_chunk_program_compiles(one_chip, fig1_graph, monkeypatch, engine,
                                has_kernel):
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    compiled = _compile_chunk(fig1_graph, one_chip, engine)
    assert ("tpu_custom_call" in compiled.as_text()) == has_kernel


def _computations(hlo: str) -> dict:
    """``{name: [instruction line, ...]}`` of a compiled HLO module's text."""
    comps, name = {}, None
    for line in hlo.splitlines():
        head = re.match(r"(?:ENTRY )?%([\w.-]+) .*\{$", line)
        if head:
            name = head.group(1)
            comps[name] = []
        elif name and line.startswith("  "):
            comps[name].append(line)
    return comps


def _scopes(line: str) -> set:
    """The device scopes in an instruction's ``op_name``, bare or under a
    transformation (``vmap(ovl.route)``)."""
    m = re.search(r'op_name="([^"]*)"', line)
    parts = {re.sub(r"^\w+\((.*)\)$", r"\1", p)
             for p in (m.group(1).split("/") if m else ())}
    return parts & set(repro.DEVICE_SCOPES)


def test_chunk_program_keeps_stage_scopes(one_chip, fig1_graph):
    # Named scopes are metadata: the TPU compiler keeps them on the fusions
    # of the chunk's while body, which is what a chip trace names ops by.
    text = _compile_chunk(fig1_graph, one_chip, "jnp").as_text()
    comps = _computations(text)
    bodies = re.findall(r" while\(.*body=%([\w.-]+)", text)
    assert bodies
    found = set()
    for body in bodies:
        for line in comps[body]:
            if " fusion(" in line:
                found |= _scopes(line)
    assert found == set(repro.DEVICE_SCOPES) - {repro.SCOPE_CHUNK_REPAIR}
    assert any(repro.SCOPE_CHUNK_REPAIR in _scopes(line)
               for lines in comps.values() for line in lines)


def _reachable(comps: dict, roots) -> set:
    """The computations ``roots`` call, directly or through others: fused
    computations, reducers, nested loop bodies."""
    seen, todo = set(), list(roots)
    while todo:
        name = todo.pop()
        if name in seen or name not in comps:
            continue
        seen.add(name)
        for line in comps[name]:
            todo += re.findall(
                r"(?:calls|to_apply|body|condition)=%([\w.-]+)", line)
    return seen


def _loop_scatters(hlo: str) -> list:
    """The ``scatter`` instructions any while loop of a compiled program
    runs, directly or in a computation it calls."""
    comps = _computations(hlo)
    bodies = re.findall(r" while\(.*body=%([\w.-]+)", hlo)
    assert bodies
    return [line.strip()[:120]
            for name in _reachable(comps, bodies)
            for line in comps[name] if re.search(r" scatter\(", line)]


@pytest.mark.parametrize("engine", ["jnp", "select"])
def test_chunk_loop_has_no_scatter(one_chip, fig1_graph, monkeypatch,
                                   engine):
    # The cycle body writes one slot, RDY word or FIFO cell per PE by a
    # one-hot dense pass: a point scatter costs per index on a TPU, and
    # six of them took most of a cycle's device time.
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    assert not _loop_scatters(
        _compile_chunk(fig1_graph, one_chip, engine).as_text())


def _gathers(hlo: str) -> int:
    return len(re.findall(r" gather\(", hlo))


@pytest.mark.parametrize("engine,gathers", [("jnp", 8), ("select", 7)])
def test_cycle_body_gathers(one_chip, fig1_graph, monkeypatch, engine,
                            gathers):
    # One gather per index of each static table group: the edge word at
    # the fanout cursor, the node word at the ejected and the picked slot.
    # The rest read the dynamic state: value at the active node; pending,
    # computed and operands at the ejected slot; the RDY word of the jnp
    # pick.
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    text = _compile_chunk(fig1_graph, one_chip, engine).as_text()
    assert _gathers(text) == gathers


def test_batched_chunk_program_compiles(one_chip, fig1_graph):
    # The batched jnp program (the sweep cell's) at fig1 shapes, B = 4: it
    # compiles, no loop of it holds a point scatter either, and it gathers
    # as the solo jnp body does in each of its two loops.
    batch = jax.ShapeDtypeStruct((4,), jnp.int32, sharding=one_chip)
    compiled = overlay._run_batch_jit.lower(
        _shapes(fig1_graph, one_chip), OverlayConfig(), ("ooo",), batch,
        batch, batch, GRID, GRID).compile()
    assert not _loop_scatters(compiled.as_text())
    assert _gathers(compiled.as_text()) == 16
