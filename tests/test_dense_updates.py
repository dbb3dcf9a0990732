"""The cycle body's one-hot dense updates against the scatters they replaced.

Stage 4 (``ovl.eject_fire``) and the schedulers write one slot, RDY word or
FIFO cell per PE each cycle. They used to do it with point scatters; they
now do it with masked dense passes over the row, one-hot at the entry. The
scatter formulation is kept here as the oracle: patched into the same cycle
body, it must give a state bit-identical to the dense one after every
cycle, for every policy, both eject capacities, solo and batched.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro
from repro.core import bitvec, overlay, schedulers
from repro.core import workloads as wl
from repro.core.overlay import OverlayConfig
from repro.core.partition import build_graph_memory

POLICIES = ("ooo", "scan", "lru_flat", "inorder")


# ---- the oracle: the scatter formulation the dense updates replaced ------

def _grid(nx, ny):
    ix = jnp.arange(nx)[:, None] * jnp.ones((1, ny), jnp.int32)
    iy = jnp.arange(ny)[None, :] * jnp.ones((nx, 1), jnp.int32)
    return ix, iy


def _hints(E):
    return dict(mode="promise_in_bounds",
                unique_indices=E == 1, indices_are_sorted=E == 1)


def _deliver_scatter(operands, pending, valid, slot, opidx, val):
    ix, iy = _grid(*pending.shape[:2])
    hints = _hints(valid.shape[0])
    operands = operands.at[ix[None], iy[None], slot, opidx].add(
        jnp.where(valid, val, 0.0), **hints)
    pending = pending.at[ix[None], iy[None], slot].add(
        -valid.astype(jnp.int32), **hints)
    return operands, pending


def _record_fires_scatter(value, computed, slot, fired, fval):
    ix, iy = _grid(*value.shape[:2])
    value = value.at[ix[None], iy[None], slot].add(
        jnp.where(fired, fval, 0.0), **_hints(slot.shape[0]))
    computed = computed.at[ix[None], iy[None], slot].max(
        fired, mode="promise_in_bounds")
    return value, computed


def _set_bit_scatter(bits, slot, on):
    nx, ny, _ = bits.shape
    ix, iy = _grid(nx, ny)
    word, mask = bitvec.slot_word_mask(slot)
    row = bits[ix, iy, word]
    return bits.at[ix, iy, word].set(jnp.where(on, row | mask, row))


def _clear_bit_scatter(bits, slot, off):
    nx, ny, _ = bits.shape
    ix, iy = _grid(nx, ny)
    word, mask = bitvec.slot_word_mask(slot)
    row = bits[ix, iy, word]
    return bits.at[ix, iy, word].set(jnp.where(off, row & ~mask, row))


def _fifo_push_scatter(self, st, slot, ready):
    fifo, head, size = st["fifo"], st["head"], st["size"]
    ix, iy = _grid(*head.shape)
    tail = (head + size) % fifo.shape[-1]
    old = fifo[ix, iy, tail]
    fifo = fifo.at[ix, iy, tail].set(jnp.where(ready, slot, old))
    return dict(fifo=fifo, head=head, size=size + ready.astype(jnp.int32))


def _patch_in_scatter_oracle(monkeypatch):
    """Cycle bodies traced from here on use the scatter updates."""
    monkeypatch.setattr(overlay, "_deliver", _deliver_scatter)
    monkeypatch.setattr(overlay, "_record_fires", _record_fires_scatter)
    monkeypatch.setattr(bitvec, "set_bit", _set_bit_scatter)
    monkeypatch.setattr(bitvec, "clear_bit", _clear_bit_scatter)
    monkeypatch.setattr(schedulers.InorderScheduler, "on_ready",
                        _fifo_push_scatter)


# ---- trajectories ---------------------------------------------------------

GRAPHS = {
    # 4x4 PEs; a bordered block-diagonal LU DAG.
    "arrow_lu": lambda: (wl.arrow_lu_graph(3, 6, 4, seed=5), 4, 4),
    # 3x5 PEs; a power-law PageRank DAG whose top vertex feeds 52 others.
    "kron6": lambda: (wl.kron_pagerank_graph(6, seed=1), 3, 5),
}


def _memory(name, sched):
    g, nx, ny = GRAPHS[name]()
    order = schedulers.get(sched).wants_criticality_order
    return build_graph_memory(g, nx, ny, criticality_order=order)


def _negative_zeros(state):
    """Every operand cell and every not-yet-computed value at -0.0: a cell
    that a port hits without a packet turns to +0.0, as under a
    scatter-add, and every other cell keeps its sign."""
    return dict(state,
                operands=jnp.full_like(state["operands"], -0.0),
                value=jnp.where(state["computed"], state["value"], -0.0))


def _trajectory(g, cfg, cycles, batch=None, negzero=False):
    """The full state after each of ``cycles`` cycles, as numpy arrays;
    ``batch`` names the policies of a vmapped sweep, set up as the batched
    engine sets them up."""
    if batch is None:
        state = overlay.init_state(g, cfg)
        step = overlay.make_cycle_fn(g, cfg)
    else:
        sched = schedulers.BatchedScheduler(batch)
        words = g["opcode"].shape[2] // bitvec.FLAGS_PER_WORD
        lats = [schedulers.get(p).sel_lat(cfg, words) for p in batch]

        def init_one(pid, lat):
            s = overlay.init_state(g, cfg, scheduler=sched)
            s["sched"]["policy_id"] = pid
            s["sel_lat"] = lat
            s["sel_wait"] = jnp.full_like(s["sel_wait"], lat - 1)
            return s

        state = jax.vmap(init_one)(jnp.arange(len(batch), dtype=jnp.int32),
                                   jnp.asarray(lats, jnp.int32))
        step = jax.vmap(overlay.make_cycle_fn(g, cfg, scheduler=sched))
    if negzero:
        state = _negative_zeros(state)

    def body(s, _):
        s = step(s)
        return s, s

    _, states = jax.jit(lambda s: jax.lax.scan(body, s, None,
                                               length=cycles))(state)
    return jax.tree.map(np.asarray, states)


def _assert_bit_identical(got, want):
    flat_got, _ = jax.tree.flatten_with_path(got)
    flat_want = jax.tree.leaves(want)
    assert len(flat_got) == len(flat_want)
    for (path, a), b in zip(flat_got, flat_want):
        assert a.dtype == b.dtype and a.shape == b.shape, path
        if a.dtype == np.float32:  # bit patterns: -0.0 differs from +0.0
            a, b = a.view(np.uint32), b.view(np.uint32)
        bad = np.argwhere(a != b)
        assert bad.size == 0, (
            f"{jax.tree_util.keystr(path)} first differs at cycle "
            f"{bad[0][0] + 1}, index {tuple(bad[0][1:])}")


def _check(gm, cfg, monkeypatch, batch=None, negzero=False):
    # Run to completion and a couple of cycles past it, the fixed point.
    runs = [repro.run(gm, dataclasses.replace(cfg, scheduler=p))
            for p in batch or (cfg.scheduler,)]
    cycles = max(r.cycles for r in runs) + 2
    g = dict(overlay.device_graph(gm))
    got = _trajectory(g, cfg, cycles, batch, negzero)
    _patch_in_scatter_oracle(monkeypatch)
    want = _trajectory(g, cfg, cycles, batch, negzero)
    _assert_bit_identical(got, want)
    assert np.asarray(got["done"])[-1].all()
    assert np.asarray(got["cycle"])[-1].max() == cycles


@pytest.mark.parametrize("eject_capacity", [1, 2])
@pytest.mark.parametrize("sched", POLICIES)
@pytest.mark.parametrize("graph", sorted(GRAPHS))
def test_solo_cycle_bit_identical_to_scatter_oracle(
        graph, sched, eject_capacity, monkeypatch):
    cfg = OverlayConfig(scheduler=sched, eject_capacity=eject_capacity)
    _check(_memory(graph, sched), cfg, monkeypatch)


@pytest.mark.parametrize("eject_capacity", [1, 2])
@pytest.mark.parametrize("graph", sorted(GRAPHS))
def test_batched_cycle_bit_identical_to_scatter_oracle(
        graph, eject_capacity, monkeypatch):
    cfg = OverlayConfig(scheduler=POLICIES[0], eject_capacity=eject_capacity)
    _check(_memory(graph, "ooo"), cfg, monkeypatch, batch=POLICIES)


@pytest.mark.parametrize("eject_capacity", [1, 2])
@pytest.mark.parametrize("sched", ["ooo", "inorder"])
def test_negative_zero_cells_bit_identical_to_scatter_oracle(
        sched, eject_capacity, monkeypatch):
    cfg = OverlayConfig(scheduler=sched, eject_capacity=eject_capacity)
    _check(_memory("arrow_lu", sched), cfg, monkeypatch, negzero=True)


def test_select_engine_cycle_bit_identical_to_scatter_oracle(monkeypatch):
    # The Pallas pick clears RDY itself; the dense set is still the hook's.
    cfg = OverlayConfig(scheduler="ooo", engine="select")
    _check(_memory("arrow_lu", "ooo"), cfg, monkeypatch)
