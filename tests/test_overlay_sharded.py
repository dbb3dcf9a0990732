"""shard_map overlay: cycle-exact equivalence with the single-device sim,
for the jnp and the select engine (subprocesses with fake host devices)."""
import os
import subprocess
import sys

import pytest

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys; sys.path.insert(0, "src")
import numpy as np, jax
from repro.core import workloads as wl
from repro.core.graph import reference_evaluate
from repro.core.partition import build_graph_memory
from repro.core.overlay import OverlayConfig, simulate
from repro.core.distributed import simulate_sharded
mesh = jax.make_mesh((2, 4), ("data", "model"))
g = wl.arrow_lu_graph(4, 8, 6, seed=2)
ref = reference_evaluate(g)
gm = build_graph_memory(g, 4, 8, criticality_order=True)
r1 = simulate(gm, OverlayConfig(scheduler="ooo", max_cycles=500000))
r2 = simulate_sharded(gm, mesh, OverlayConfig(scheduler="ooo", max_cycles=500000))
assert r2.done and r1.cycles == r2.cycles, (r1.cycles, r2.cycles)
np.testing.assert_allclose(r2.values, ref, rtol=1e-5, atol=1e-5)
print("SHARDED_EXACT_OK")
"""


SELECT_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import sys; sys.path.insert(0, "src")
import numpy as np
import repro
from repro.core import workloads as wl
from repro.core.overlay import OverlayConfig
from repro.core.partition import build_graph_memory
from repro.launch.mesh import make_local_mesh
mesh = make_local_mesh(2, 2)
gm = build_graph_memory(wl.layered_dag(4, 6, seed=3), 2, 2,
                        criticality_order=True)
def same(r, ref, ctx):
    assert r.done and (r.cycles, r.deflections, r.busy_cycles,
                       r.delivered) == (ref.cycles, ref.deflections,
                                        ref.busy_cycles, ref.delivered), ctx
    np.testing.assert_array_equal(r.values, ref.values)
ref = {p: repro.run(gm, OverlayConfig(scheduler=p, check_every=1))
       for p in ("ooo", "scan")}
same(repro.run(gm, OverlayConfig(engine="select"), mesh=mesh), ref["ooo"],
     "solo")
cfgs = [OverlayConfig(scheduler=p, engine="select") for p in ("ooo", "scan")]
for cfg, r in zip(cfgs, repro.run(gm, mesh=mesh, batch=cfgs)):
    same(r, ref[cfg.scheduler], cfg.scheduler)
print("SELECT_MESH_OK")
"""


def _run_script(script: str, timeout: int):
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    return subprocess.run([sys.executable, "-c", script], cwd=os.getcwd(),
                          capture_output=True, text=True, env=env,
                          timeout=timeout)


@pytest.mark.slow
def test_sharded_overlay_cycle_exact():
    out = _run_script(SCRIPT, 420)
    assert "SHARDED_EXACT_OK" in out.stdout, out.stderr[-2000:]


def test_select_engine_on_multi_shard_mesh():
    # The select kernel runs shard-local, with the torus exchange and the
    # per-chunk reductions outside it: a 2x2 mesh equals one device.
    out = _run_script(SELECT_SCRIPT, 120)
    assert "SELECT_MESH_OK" in out.stdout, out.stderr[-2000:]
