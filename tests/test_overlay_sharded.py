"""shard_map overlay: cycle-exact equivalence with the single-device sim,
and the megakernel refusing a multi-shard mesh (subprocesses with fake host
devices)."""
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


MEGAKERNEL_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import sys; sys.path.insert(0, "src")
import repro
from repro.core import workloads as wl
from repro.core.overlay import OverlayConfig
from repro.core.partition import build_graph_memory
from repro.launch.mesh import make_local_mesh
mesh = make_local_mesh(2, 2)
gm = build_graph_memory(wl.layered_dag(4, 6, seed=3), 2, 2)
cfg = OverlayConfig(engine="megakernel")
for call in (lambda: repro.run(gm, cfg, mesh=mesh),
             lambda: repro.run(gm, mesh=mesh, batch=[cfg, cfg])):
    try:
        call()
    except ValueError as e:
        assert "multi-shard mesh" in str(e), e
    else:
        raise AssertionError("engine='megakernel' ran on a 2x2 mesh")
print("MEGAKERNEL_MESH_RAISES_OK")
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


def test_megakernel_on_multi_shard_mesh_raises():
    out = _run_script(MEGAKERNEL_SCRIPT, 120)
    assert "MEGAKERNEL_MESH_RAISES_OK" in out.stdout, out.stderr[-2000:]
