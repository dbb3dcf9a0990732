"""Chunked stepping engine: bit-exactness against the ``check_every=1``
per-cycle reference for every registered policy, through all three entry
points (solo, batched sweep, sharded) and both engines (``jnp`` and the
Pallas ``select`` pick), plus the engine-keyed ``check_every`` autotune.

The chunked engine's correctness argument is that a completed overlay is a
fixed point of the cycle function and the exact completion cycle is repaired
from the per-cycle done trace — these tests pin that argument down for every
policy, several chunk depths (including one that doesn't divide the run
length), heterogeneous cycle budgets, and the cross-shard reduction path.
"""
import os
import subprocess
import sys
import warnings

import numpy as np
import pytest

import repro
from repro.core import schedulers
from repro.core import workloads as wl
from repro.core.graph import reference_evaluate
from repro.core.overlay import (ENGINES, OverlayConfig, resolve_check_every,
                                simulate, simulate_batch)
from repro.core.partition import build_graph_memory

ALL_POLICIES = sorted(schedulers.REGISTRY)
CHECK_EVERYS = (1, 7, 32)


def _gm(sched, nx=2, ny=2):
    g = wl.arrow_lu_graph(3, 6, 4, seed=5)
    policy = schedulers.get(sched)
    return build_graph_memory(g, nx, ny,
                              criticality_order=policy.wants_criticality_order)


def _stats(r):
    return (r.done, r.cycles, r.deflections, r.busy_cycles, r.delivered)


@pytest.fixture(scope="module")
def reference_runs():
    """check_every=1 reference result per policy (compiled once per policy)."""
    out = {}
    for sched in ALL_POLICIES:
        gm = _gm(sched)
        out[sched] = simulate(gm, OverlayConfig(
            scheduler=sched, max_cycles=500_000, check_every=1))
        assert out[sched].done
    return out


@pytest.mark.parametrize("check_every", CHECK_EVERYS)
@pytest.mark.parametrize("sched", ALL_POLICIES)
def test_simulate_chunked_bit_identical(sched, check_every, reference_runs):
    gm = _gm(sched)
    r = simulate(gm, OverlayConfig(scheduler=sched, max_cycles=500_000,
                                   check_every=check_every))
    ref = reference_runs[sched]
    assert _stats(r) == _stats(ref), (sched, check_every)
    np.testing.assert_array_equal(r.values, ref.values)


def test_autotuned_check_every_bit_identical(reference_runs):
    for sched in ALL_POLICIES:
        gm = _gm(sched)
        r = simulate(gm, OverlayConfig(scheduler=sched, max_cycles=500_000))
        assert _stats(r) == _stats(reference_runs[sched]), sched
        np.testing.assert_array_equal(r.values, reference_runs[sched].values)


@pytest.mark.parametrize("check_every", CHECK_EVERYS)
def test_simulate_batch_chunked_bit_identical(check_every):
    g = wl.arrow_lu_graph(3, 6, 4, seed=5)
    gm = build_graph_memory(g, 4, 4, criticality_order=True)
    cfgs = [OverlayConfig(scheduler=p, max_cycles=500_000,
                          check_every=check_every) for p in ALL_POLICIES]
    # heterogeneous budget: freezes mid-chunk at its OWN max_cycles
    cfgs.append(OverlayConfig(scheduler="scan", max_cycles=100,
                              check_every=check_every))
    # an element that finishes long before the others keeps re-entering
    # chunks; its repaired cycle count must not drift
    cfgs.append(OverlayConfig(scheduler="ooo", select_latency=4,
                              max_cycles=500_000, check_every=check_every))
    for cfg, rb in zip(cfgs, simulate_batch(gm, cfgs)):
        rs = simulate(gm, OverlayConfig(
            scheduler=cfg.scheduler, select_latency=cfg.select_latency,
            max_cycles=cfg.max_cycles, check_every=1))
        assert _stats(rb) == _stats(rs), (cfg.scheduler, check_every)
        np.testing.assert_array_equal(rb.values, rs.values)


def test_batch_budget_on_chunk_boundary_is_exact():
    # Regression: an element whose max_cycles is an exact multiple of
    # check_every exhausts its budget precisely at a chunk boundary; it is
    # NOT a fixed point of the cycle function, so it must drop out of the
    # guard-free chunked phase instead of silently simulating on while the
    # longer-running element keeps chunking.
    g = wl.arrow_lu_graph(3, 6, 4, seed=5)
    gm = build_graph_memory(g, 4, 4, criticality_order=True)
    cfgs = [OverlayConfig(scheduler="scan", max_cycles=98, check_every=7),
            OverlayConfig(scheduler="ooo", max_cycles=500_000, check_every=7)]
    for cfg, rb in zip(cfgs, simulate_batch(gm, cfgs)):
        rs = simulate(gm, OverlayConfig(
            scheduler=cfg.scheduler, max_cycles=cfg.max_cycles, check_every=1))
        assert _stats(rb) == _stats(rs), cfg.scheduler
        np.testing.assert_array_equal(rb.values, rs.values)


def test_chunk_boundary_never_overshoots_budget():
    # max_cycles deliberately NOT a multiple of check_every: the freeze guard
    # must stop the cycle counter exactly at the budget.
    g = wl.arrow_lu_graph(3, 6, 4, seed=5)
    gm = build_graph_memory(g, 2, 2, criticality_order=True)
    r = simulate(gm, OverlayConfig(scheduler="ooo", max_cycles=101,
                                   check_every=32))
    ref = simulate(gm, OverlayConfig(scheduler="ooo", max_cycles=101,
                                     check_every=1))
    assert not r.done and not ref.done
    assert _stats(r) == _stats(ref)
    np.testing.assert_array_equal(r.values, ref.values)


def test_check_every_zero_rejected():
    with pytest.raises(ValueError, match="check_every"):
        OverlayConfig(check_every=0)


def test_sharded_chunked_bit_identical():
    import jax

    from repro.core.distributed import simulate_sharded

    mesh = jax.make_mesh((1, 1), ("data", "model"))
    g = wl.arrow_lu_graph(2, 5, 3, seed=4)
    ref_vals = reference_evaluate(g)
    gm = build_graph_memory(g, 2, 2, criticality_order=True)
    for sched in ALL_POLICIES:
        ref = simulate(gm, OverlayConfig(scheduler=sched, max_cycles=500_000,
                                         check_every=1))
        for check_every in (8, None):
            r = simulate_sharded(gm, mesh, OverlayConfig(
                scheduler=sched, max_cycles=500_000, check_every=check_every))
            assert _stats(r) == _stats(ref), (sched, check_every)
        np.testing.assert_array_equal(r.values, ref_vals)


def test_simulate_batch_sharded_matches_serial():
    import jax

    from repro.core.distributed import simulate_batch_sharded

    mesh = jax.make_mesh((1, 1), ("data", "model"))
    g = wl.arrow_lu_graph(2, 5, 3, seed=4)
    gm = build_graph_memory(g, 2, 2, criticality_order=True)
    cfgs = [OverlayConfig(scheduler=p, max_cycles=500_000)
            for p in ALL_POLICIES]
    cfgs.append(OverlayConfig(scheduler="scan", max_cycles=60))
    for cfg, rb in zip(cfgs, simulate_batch_sharded(gm, mesh, cfgs)):
        rs = simulate(gm, OverlayConfig(scheduler=cfg.scheduler,
                                        max_cycles=cfg.max_cycles,
                                        check_every=1))
        assert _stats(rb) == _stats(rs), cfg.scheduler
        np.testing.assert_array_equal(rb.values, rs.values)


# ---------------------------------------------------------------------------
# engine="select": the scheduler pick through the Pallas LOD kernels
# (interpret mode on CPU, the same kernels the TPU path compiles), bit-exact
# against the check_every=1 jnp reference at every chunk depth and entry point.
# ---------------------------------------------------------------------------

SELECT_CHECK_EVERYS = (1, 8, 32)
#: Policies whose ``step(use_pallas=True)`` routes the pick through a kernel.
KERNEL_POLICIES = {"ooo", "scan", "lru_flat"}


def _dag_gm(sched):
    """``layered_dag(4, 6)`` on 2x2: L = 32 slots, one RDY word per PE."""
    return build_graph_memory(
        wl.layered_dag(4, 6, seed=3), 2, 2,
        criticality_order=schedulers.get(sched).wants_criticality_order)


@pytest.fixture(scope="module")
def dag_reference_runs():
    """check_every=1 jnp reference per policy on the small layered DAG."""
    out = {}
    for sched in ALL_POLICIES:
        out[sched] = repro.run(_dag_gm(sched), OverlayConfig(
            scheduler=sched, max_cycles=100_000, check_every=1))
        assert out[sched].done
    return out


@pytest.mark.parametrize("check_every", SELECT_CHECK_EVERYS)
@pytest.mark.parametrize("sched", ALL_POLICIES)
def test_select_engine_bit_identical(sched, check_every, dag_reference_runs):
    r = repro.run(_dag_gm(sched), OverlayConfig(
        scheduler=sched, max_cycles=100_000, check_every=check_every,
        engine="select"))
    ref = dag_reference_runs[sched]
    assert _stats(r) == _stats(ref), (sched, check_every)
    np.testing.assert_array_equal(r.values, ref.values)


@pytest.mark.parametrize("sched", ALL_POLICIES)
def test_select_engine_multiword_bit_identical(sched, reference_runs):
    # L = 512 slots on 2x2 is 16 RDY words per PE: the LOD kernel's
    # multi-word pick, at the chunk depth the chip runs.
    gm = _gm(sched)
    assert gm.lmax // 32 == 16
    r = repro.run(gm, OverlayConfig(scheduler=sched, max_cycles=500_000,
                                    check_every=32, engine="select"))
    ref = reference_runs[sched]
    assert _stats(r) == _stats(ref), sched
    np.testing.assert_array_equal(r.values, ref.values)


@pytest.mark.parametrize("check_every", SELECT_CHECK_EVERYS)
def test_select_engine_batched_bit_identical(check_every):
    # The kernels batch under the vmapped engine, and a member whose own
    # budget runs out mid-chunk freezes there, as its solo run does.
    gm = build_graph_memory(wl.layered_dag(4, 6, seed=3), 2, 2,
                            criticality_order=True)
    cfgs = [OverlayConfig(scheduler=p, max_cycles=100_000,
                          check_every=check_every, engine="select")
            for p in ALL_POLICIES]
    cfgs.append(OverlayConfig(scheduler="scan", max_cycles=20,
                              check_every=check_every, engine="select"))
    for cfg, rb in zip(cfgs, repro.run(gm, batch=cfgs)):
        rs = repro.run(gm, OverlayConfig(
            scheduler=cfg.scheduler, max_cycles=cfg.max_cycles, check_every=1))
        assert _stats(rb) == _stats(rs), (cfg.scheduler, check_every)
        np.testing.assert_array_equal(rb.values, rs.values)


def test_select_engine_sharded_bit_identical():
    import jax

    mesh = jax.make_mesh((1, 1), ("data", "model"))
    gm = build_graph_memory(wl.layered_dag(4, 6, seed=3), 2, 2,
                            criticality_order=True)
    ref = repro.run(gm, OverlayConfig(scheduler="ooo", max_cycles=100_000,
                                      check_every=1))
    r = repro.run(gm, OverlayConfig(scheduler="ooo", max_cycles=100_000,
                                    check_every=8, engine="select"),
                  mesh=mesh)
    assert _stats(r) == _stats(ref)
    np.testing.assert_array_equal(r.values, ref.values)
    cfgs = [OverlayConfig(scheduler=p, max_cycles=100_000, engine="select")
            for p in ("ooo", "scan")]
    for cfg, rb in zip(cfgs, repro.run(gm, mesh=mesh, batch=cfgs)):
        rs = repro.run(gm, OverlayConfig(scheduler=cfg.scheduler,
                                         max_cycles=100_000, check_every=1))
        assert _stats(rb) == _stats(rs), cfg.scheduler
        np.testing.assert_array_equal(rb.values, rs.values)


def _primitives(jaxpr) -> list:
    """Every primitive of ``jaxpr``, nested jaxprs (scan, cond, jit,
    custom_vjp bodies) included."""
    from jax.extend import core

    out = []
    for eqn in jaxpr.eqns:
        out.append(eqn.primitive.name)
        todo = list(eqn.params.values())
        while todo:
            v = todo.pop()
            if isinstance(v, core.ClosedJaxpr):
                out += _primitives(v.jaxpr)
            elif isinstance(v, core.Jaxpr):
                out += _primitives(v)
            elif isinstance(v, (tuple, list)):
                todo += v
    return out


def _chunk_primitives(sched, engine):
    import jax

    from repro.core.overlay import (device_graph, init_state, make_chunk_fn,
                                    make_cycle_fn)

    cfg = OverlayConfig(scheduler=sched, engine=engine)
    g = device_graph(_dag_gm(sched))
    chunk = make_chunk_fn(make_cycle_fn(g, cfg), 8)
    return _primitives(jax.make_jaxpr(chunk)(init_state(g, cfg)).jaxpr)


@pytest.mark.parametrize("sched", ALL_POLICIES)
def test_select_chunk_launches_kernel(sched):
    # A select chunk is a scan whose cycle body calls the LOD kernel for
    # exactly the policies that route their pick through it: what the
    # select cell measures on the chip is the kernel.
    prims = _chunk_primitives(sched, "select")
    assert "scan" in prims
    assert ("pallas_call" in prims) == (sched in KERNEL_POLICIES), sched


def test_jnp_chunk_has_no_pallas_call():
    prims = _chunk_primitives("ooo", "jnp")
    assert "scan" in prims
    assert "pallas_call" not in prims


@pytest.mark.parametrize("backend", ["cpu", "tpu"])
def test_resolve_check_every_keys_on_engine(backend):
    # jnp autotunes from the slot count (8 / 16 / 32); select takes at
    # least 16 (one kernel dispatch per cycle), and so does any engine on
    # a TPU. At paper shapes (16x16, L = 1,856) both engines run 32.
    kw = dict(backend=backend, num_devices=1)
    small = {"cpu": {"jnp": 8, "select": 16},
             "tpu": {"jnp": 16, "select": 16}}[backend]
    for engine in ENGINES:
        cfg = OverlayConfig(engine=engine)
        assert resolve_check_every(cfg, 2, 2, 32, **kw) == small[engine]
        assert resolve_check_every(cfg, 16, 16, 1856, **kw) == 32
        # an explicit check_every always wins over the engine keying
        assert resolve_check_every(OverlayConfig(engine=engine, check_every=4),
                                   2, 2, 32, **kw) == 4
        # a multi-device mesh amortizes its per-chunk collectives: 32
        assert resolve_check_every(cfg, 2, 2, 32, backend=backend,
                                   num_devices=8) == 32


def test_use_pallas_removed():
    # engine= is the only spelling, and only the two engines exist
    with pytest.raises(TypeError):
        OverlayConfig(use_pallas=True)
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        OverlayConfig(engine="select")
    assert not caught
    assert ENGINES == ("jnp", "select")
    for gone in ("megakernel", "turbo"):
        with pytest.raises(ValueError, match="engine"):
            OverlayConfig(engine=gone)


def test_simulate_batch_rejects_mixed_engine():
    g = wl.reduction_tree(16)
    gm = build_graph_memory(g, 2, 2)
    with pytest.raises(ValueError, match="engine"):
        simulate_batch(gm, [OverlayConfig(engine="jnp"),
                            OverlayConfig(engine="select")])


SHARDED_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys; sys.path.insert(0, "src")
import numpy as np, jax
from repro.core import workloads as wl
from repro.core.partition import build_graph_memory
from repro.core.overlay import OverlayConfig, simulate
from repro.core.distributed import simulate_sharded, simulate_batch_sharded
mesh = jax.make_mesh((2, 4), ("data", "model"))
g = wl.arrow_lu_graph(4, 8, 6, seed=2)
gm = build_graph_memory(g, 4, 8, criticality_order=True)
ref = simulate(gm, OverlayConfig(scheduler="ooo", max_cycles=500000, check_every=1))
r = simulate_sharded(gm, mesh, OverlayConfig(scheduler="ooo", max_cycles=500000, check_every=7))
assert r.done and r.cycles == ref.cycles, (r.cycles, ref.cycles)
assert (r.deflections, r.busy_cycles, r.delivered) == (
    ref.deflections, ref.busy_cycles, ref.delivered)
np.testing.assert_array_equal(r.values, ref.values)
cfgs = [OverlayConfig(scheduler="ooo", max_cycles=500000),
        OverlayConfig(scheduler="inorder", max_cycles=500000),
        OverlayConfig(scheduler="scan", max_cycles=200)]
for cfg, b in zip(cfgs, simulate_batch_sharded(gm, mesh, cfgs)):
    s = simulate(gm, OverlayConfig(scheduler=cfg.scheduler,
                                   max_cycles=cfg.max_cycles, check_every=1))
    assert (b.done, b.cycles, b.deflections, b.busy_cycles) == (
        s.done, s.cycles, s.deflections, s.busy_cycles), cfg.scheduler
    np.testing.assert_array_equal(b.values, s.values)
print("CHUNKED_SHARDED_OK")
"""


@pytest.mark.slow
def test_chunked_sharded_multidevice_exact():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", SHARDED_SCRIPT],
                         cwd=os.getcwd(), capture_output=True, text=True,
                         env=env, timeout=420)
    assert "CHUNKED_SHARDED_OK" in out.stdout, out.stderr[-2000:]
