"""Scheduler-kernel microbenchmarks: per-policy pick rate.

Times (a) the jnp reference LOD scheduler step (select + clear) at the
paper's geometry (256 PEs x 256 flag words == 8 BRAMs' worth of flags) and
larger, (b) every registered scheduler policy's full ``select`` + ``commit``
step on randomized scheduler state — the simulator's actual hot spot per
cycle — and (c) the fused Pallas scheduler kernels (``schedule_step`` and
the rotating-pointer variant) that ``OverlayConfig(engine="select")`` routes
the pick through. On this CPU container the Pallas rows run in interpret
mode (flagged ``interpret: true`` in run.py's JSON snapshot): the timing is
not physical TPU performance, but it tracks kernel-level regressions per PR
and becomes real on a TPU backend.

Output CSV: name,us_per_call,derived (derived = selects/s).
"""
from __future__ import annotations

import math
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import schedulers
from repro.kernels import ref


def _time(fn, *args, iters=50):
    jax.tree.leaves(fn(*args))[0].block_until_ready()
    t0 = time.time()
    for _ in range(iters):
        out = fn(*args)
    jax.tree.leaves(out)[0].block_until_ready()
    return (time.time() - t0) / iters


def _policy_state(policy, nx, ny, words, rng, fill_rounds=16):
    """Randomized scheduler state at [nx, ny] PEs x ``words`` flag words,
    built purely through the Scheduler protocol (init + on_ready), so any
    registered policy — including future ones — benchmarks on a populated
    queue rather than its empty init state."""
    from repro.core.overlay import OverlayConfig

    L = words * 32
    g = dict(
        opcode=jnp.zeros((nx, ny, L), jnp.int32),
        fanin=jnp.full((nx, ny, L), 2, jnp.int32),
        fo_count=jnp.ones((nx, ny, L), jnp.int32),
        valid=jnp.ones((nx, ny, L), bool),
    )
    st = policy.init(g, OverlayConfig(scheduler=policy.name))
    for _ in range(fill_rounds):
        slot = jnp.asarray(rng.integers(0, L, size=(nx, ny), dtype=np.int32))
        ready = jnp.asarray(rng.random(size=(nx, ny)) < 0.75)
        st = policy.on_ready(st, slot, ready)
    return jax.tree.map(jnp.asarray, st)


def run():
    rows = []
    rng = np.random.default_rng(0)
    step = jax.jit(ref.schedule_step_ref)
    for pes, words in [(256, 8), (256, 64), (256, 256), (1024, 64)]:
        bits = jnp.asarray(
            rng.integers(0, 2**32, size=(pes, words), dtype=np.uint32))
        us = _time(step, bits) * 1e6
        rows.append({
            "name": f"lod_schedule_{pes}x{words}",
            "us_per_call": round(us, 2),
            "derived": round(pes / (us * 1e-6), 0),
        })

    # Full select+commit step for every registered policy (vmapped sweep and
    # solo simulators both run exactly this per cycle).
    idle_cache = {}
    for name in sorted(schedulers.REGISTRY):
        policy = schedulers.REGISTRY[name]
        for pes, words in [(256, 8), (256, 64)]:
            side = int(math.isqrt(pes))
            st = _policy_state(policy, side, pes // side, words, rng)
            if pes not in idle_cache:
                idle_cache[pes] = jnp.ones((side, pes // side), bool)
            idle = idle_cache[pes]

            @jax.jit
            def pick(st, idle=idle, policy=policy):
                cand, have = policy.select(st, idle)
                return cand, policy.commit(st, idle & have, cand)

            us = _time(pick, st) * 1e6
            rows.append({
                "name": f"pick_{name}_{pes}x{words}",
                "us_per_call": round(us, 2),
                "derived": round(pes / (us * 1e-6), 0),
            })

    # Fused Pallas scheduler kernels (the engine="select" pick path).
    from repro.kernels import ops
    from repro.kernels.ops import _interpret

    interp = _interpret()
    for pes, words in [(256, 8), (256, 64)]:
        bits = jnp.asarray(
            rng.integers(0, 2**32, size=(pes, words), dtype=np.uint32))
        gate = jnp.asarray(rng.random(pes) < 0.75)
        ptr = jnp.asarray(
            rng.integers(0, words * 32, size=pes, dtype=np.int32))
        iters = 10 if interp else 50
        us = _time(ops.schedule_step, bits, gate, iters=iters) * 1e6
        rows.append({
            "name": f"pallas_schedule_step_{pes}x{words}",
            "us_per_call": round(us, 2),
            "derived": round(pes / (us * 1e-6), 0),
            "interpret": interp,
        })
        us = _time(ops.rotating_schedule_step, bits, ptr, gate,
                   iters=iters) * 1e6
        rows.append({
            "name": f"pallas_rotating_step_{pes}x{words}",
            "us_per_call": round(us, 2),
            "derived": round(pes / (us * 1e-6), 0),
            "interpret": interp,
        })
    return rows


def main():
    print("name,us_per_call,derived")
    for r in run():
        print(f"{r['name']},{r['us_per_call']},{r['derived']}")


if __name__ == "__main__":
    main()
