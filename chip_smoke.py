"""Smoke run of the overlay simulator's main path on TPU.

    python chip_smoke.py             # one chip: phases A, B and C
    python chip_smoke.py --chips 4   # the sharded path on a 2x2 mesh, only

Phase A  The paper-scale fig1 graph (arrow-LU, blocks=128, block_size=10,
         border=8, seed=3: 470,772 nodes) on the 16x16 overlay through
         ``repro.run``: ``ooo`` solo on the criticality-ordered layout, then
         every registered policy as one batched program per layout group.
         Every run must finish, match ``graph.reference_evaluate``, and the
         tracked cycle counts of ``fig1_arrow_n470772`` in
         ``BENCH_overlay.json`` must reproduce exactly.
Phase B  The solo ``ooo`` query again with ``engine="select"``: the Pallas
         LOD kernels compiled for the chip, bit-identical to phase A.
Phase C  The placement service: an annealed placement of a 29,772-node
         fig1-family graph on 16x16, its repeat answered from the cache
         with zero simulations, and a surrogate-guided placement of a
         1,380-node service-stream graph on 4x4; both searches must give
         the cycle counts the CPU backend gives.

``--chips 4`` runs phase A's solo and batched queries with the PE grid
sharded over a 2x2 mesh and compares them bit-exactly with the same queries
on one device of the same process.

The script has no CPU path: without a TPU it exits non-zero before any
phase. Timings are one-shot smoke timings (a first call includes
compilation), not benchmark numbers. Any failed check raises, so the script
exits non-zero; the last line of stdout is one JSON object naming the
device, printed only when every phase passed.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

GRID = 16
#: (blocks, block_size, border, seed) of the paper-scale fig1 graph.
PAPER_GRAPH = (128, 10, 8, 3)
#: BENCH_overlay.json ``fig1_arrow_n470772``: ooo on the criticality
#: layout, inorder on the naive layout.
TRACKED_CYCLES = {"ooo": 54943, "inorder": 66669}
#: (blocks, block_size, border, seed) of the service graph: 29,772 nodes.
SERVICE_GRAPH = (8, 10, 8, 3)
#: Phase C's answers on the CPU backend (anneal: the service graph on
#: 16x16; guided: the first service-stream graph on 4x4). Placement search
#: and simulation are integer and seeded, so the chip must give the same
#: cycle counts.
SERVICE_CYCLES = {"anneal": 4796, "guided": 428}
_STATS = ("cycles", "done", "delivered", "deflections", "busy_cycles",
          "noc_deflections", "eject_deflections")


def timed(label, fn):
    t0 = time.perf_counter()
    out = fn()
    print(f"[smoke timing] {label}: {time.perf_counter() - t0:.3f} s",
          flush=True)
    return out


def check(cond, msg):
    if not cond:
        raise AssertionError(msg)


def check_result(label, r, ref, cycles=None):
    """A finished run whose node values match the reference evaluator."""
    import numpy as np

    check(r.done, f"{label}: did not finish in {r.cycles} cycles")
    check(np.allclose(r.values, ref, rtol=1e-5, atol=1e-5),
          f"{label}: node values differ from reference_evaluate")
    if cycles is not None:
        check(r.cycles == cycles,
              f"{label}: {r.cycles} cycles, tracked count is {cycles}")


def check_identical(label, a, b):
    import numpy as np

    for k in _STATS:
        check(getattr(a, k) == getattr(b, k),
              f"{label}: {k} {getattr(a, k)} != {getattr(b, k)}")
    check(np.array_equal(a.values, b.values), f"{label}: values differ")


def device_info(chips: int) -> dict:
    import jax

    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    print(f"device: {info}", flush=True)
    if info["platform"] != "tpu":
        raise SystemExit(f"no TPU found (JAX platform {info['platform']!r}); "
                         f"this smoke run has no CPU path")
    if info["count"] < chips:
        raise SystemExit(f"--chips {chips} needs {chips} TPU devices, "
                         f"found {info['count']}")
    return info


def paper_graph():
    from repro.core import workloads as wl
    from repro.core.graph import reference_evaluate

    b, s, w, seed = PAPER_GRAPH
    g = timed("build paper-scale graph",
              lambda: wl.arrow_lu_graph(b, s, w, seed=seed))
    print(f"graph: {g.num_nodes} nodes, {g.num_edges} edges", flush=True)
    ref = timed("reference_evaluate", lambda: reference_evaluate(g))
    return g, ref


def pack(g, criticality_order):
    from repro.core.partition import build_graph_memory

    return timed(f"pack criticality_order={criticality_order}",
                 lambda: build_graph_memory(g, GRID, GRID,
                                            criticality_order=criticality_order))


def run_twice(label, fn):
    """First call (compile + run) and a second, steady-state call; both
    must agree."""
    first = timed(f"{label}, first call (compile + run)", fn)
    second = timed(f"{label}, second call", fn)
    for a, b in (zip(first, second) if isinstance(first, list)
                 else [(first, second)]):
        check_identical(f"{label} repeat", a, b)
    return first


def phase_a(g, ref):
    import repro
    from repro.core import schedulers
    from repro.core.overlay import OverlayConfig

    groups: dict = {}
    for p in sorted(schedulers.REGISTRY):
        wants = schedulers.get(p).wants_criticality_order
        groups.setdefault(wants, []).append(p)
    gms = {wants: pack(g, wants) for wants in groups}

    solo = run_twice("A solo ooo", lambda: repro.run(
        gms[True], OverlayConfig(scheduler="ooo")))
    check_result("A solo ooo", solo, ref, TRACKED_CYCLES["ooo"])
    print(f"A solo ooo: {solo.cycles} cycles (tracked "
          f"{TRACKED_CYCLES['ooo']}), values match reference", flush=True)

    sweep = {}
    for wants, names in groups.items():
        cfgs = [OverlayConfig(scheduler=p) for p in names]
        # Timed once: scan's naive-layout run is the longest of the script.
        rs = timed(f"A sweep {names} (compile + run)",
                   lambda gm=gms[wants], cfgs=cfgs: repro.run(gm, batch=cfgs))
        for p, r in zip(names, rs):
            # ooo runs on the criticality layout, inorder on the naive one:
            # the layouts the tracked counts were recorded on.
            check_result(f"A sweep {p}", r, ref, TRACKED_CYCLES.get(p))
            sweep[p] = r
    check_identical("A batched ooo vs solo", sweep["ooo"], solo)
    print("A sweep: " + ", ".join(f"{p} {r.cycles}"
                                  for p, r in sorted(sweep.items()))
          + f" cycles (tracked inorder {TRACKED_CYCLES['inorder']}); "
          "batched ooo == solo; all values match reference", flush=True)
    return gms[True], solo


def phase_b(gm, solo):
    import repro
    from repro.core.overlay import OverlayConfig
    from repro.kernels import ops

    check(ops._interpret() is False,
          "the Pallas kernels would run interpreted, not compiled")
    r = run_twice("B solo ooo engine=select", lambda: repro.run(
        gm, OverlayConfig(scheduler="ooo", engine="select")))
    check_identical("B select vs A jnp", r, solo)
    print(f"B select: {r.cycles} cycles, bit-identical to phase A; "
          "LOD kernels compiled (interpret=False)", flush=True)


def phase_c():
    import numpy as np

    from repro.core import workloads as wl
    from repro.core.overlay import OverlayConfig
    from repro.place import PlacementSpec
    from repro.service import PlacementQuery, PlacementService

    b, s, w, seed = SERVICE_GRAPH
    g = wl.arrow_lu_graph(b, s, w, seed=seed)
    print(f"C service graph: {g.num_nodes} nodes on {GRID}x{GRID}",
          flush=True)
    svc = PlacementService()

    def query(graph, grid, placement):
        return PlacementQuery(graph=graph, nx=grid, ny=grid,
                              cfg=OverlayConfig(placement=placement))

    first = timed("C anneal query",
                  lambda: svc.query(query(g, GRID, "anneal")))
    check(not first.cached, "C anneal: no fresh answer")
    check(first.cycles == SERVICE_CYCLES["anneal"],
          f"C anneal: {first.cycles} cycles, CPU backend gave "
          f"{SERVICE_CYCLES['anneal']}")
    sims = svc.counters["simulations"]
    again = timed("C repeat query",
                  lambda: svc.query(query(g, GRID, "anneal")))
    check(again.cached, "C repeat: missed the cache")
    check(svc.counters["simulations"] == sims, "C repeat: ran a simulation")
    check(again.cycles == first.cycles and again.stats == first.stats
          and np.array_equal(again.node_pe, first.node_pe),
          "C repeat: cached answer differs from the fresh one")
    # The surrogate fit simulates 24 placements: on the 29,772-node graph on
    # 16x16 the guided query took 89.6 s on one v5e, so it runs on the
    # first service-stream graph on 4x4.
    name, small = wl.service_stream()[0]
    guided = timed(f"C guided query on {name} ({small.num_nodes} nodes, "
                   f"4x4; surrogate fit + guided anneal)",
                   lambda: svc.query(query(small, 4, PlacementSpec(
                       strategy="anneal", guide="surrogate"))))
    check(not guided.cached, "C guided: no fresh answer")
    check(guided.cycles == SERVICE_CYCLES["guided"],
          f"C guided: {guided.cycles} cycles, CPU backend gave "
          f"{SERVICE_CYCLES['guided']}")
    check(svc.counters["surrogate_fits"] == 1, "C guided: no surrogate fit")
    print(f"C service: anneal {first.cycles} cycles, repeat cached with 0 "
          f"new simulations, guided {guided.cycles} cycles; counters "
          f"{svc.report()}", flush=True)


def phase_sharded(g, ref):
    import jax

    import repro
    from repro.core import overlay
    from repro.core.overlay import OverlayConfig
    from repro.launch.mesh import make_local_mesh

    # Observe the engines' raw device output before it is copied to the
    # host: the sharded engines must leave it on all four devices.
    spans = []
    unpack = overlay._unpack_result

    def spy(final, *args, **kwargs):
        spans.append(min(len(leaf.sharding.device_set)
                         for leaf in jax.tree.leaves(final)))
        return unpack(final, *args, **kwargs)

    overlay._unpack_result = spy
    try:
        mesh = make_local_mesh(2, 2)
        gm = pack(g, True)
        cfg = OverlayConfig(scheduler="ooo")
        one = timed("4chip solo ooo, one device",
                    lambda: repro.run(gm, cfg))
        sharded = timed("4chip solo ooo, 2x2 mesh",
                        lambda: repro.run(gm, cfg, mesh=mesh))
        check(spans == [1, 4], f"4chip solo: output spans {spans} devices")
        check_result("4chip solo ooo", sharded, ref, TRACKED_CYCLES["ooo"])
        check_identical("4chip solo: mesh vs one device", sharded, one)
        print(f"4chip solo ooo: {sharded.cycles} cycles on the 2x2 mesh, "
              f"bit-identical to one device", flush=True)

        cfgs = [OverlayConfig(scheduler="ooo"),
                OverlayConfig(scheduler="inorder")]
        spans.clear()
        ones = timed("4chip batch [ooo, inorder], one device",
                     lambda: repro.run(gm, batch=cfgs))
        shs = timed("4chip batch [ooo, inorder], 2x2 mesh",
                    lambda: repro.run(gm, mesh=mesh, batch=cfgs))
        check(spans == [1, 1, 4, 4],
              f"4chip batch: output spans {spans} devices")
        for c, a, b in zip(cfgs, shs, ones):
            check_result(f"4chip batch {c.scheduler}", a, ref)
            check_identical(f"4chip batch {c.scheduler}", a, b)
        print("4chip batch: " + ", ".join(
            f"{c.scheduler} {r.cycles}" for c, r in zip(cfgs, shs))
              + " cycles on the 2x2 mesh, bit-identical to one device",
              flush=True)
    finally:
        overlay._unpack_result = unpack
    for d in jax.devices():
        stats = d.memory_stats() or {}
        print(f"4chip {d}: peak_bytes_in_use "
              f"{stats.get('peak_bytes_in_use', 'not reported')}", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded phase on a 2x2 mesh")
    args = ap.parse_args(argv)

    from repro import compile_cache

    print(f"compile cache: {compile_cache.configure()}", flush=True)
    info = device_info(args.chips)
    g, ref = paper_graph()
    if args.chips == 4:
        phase_sharded(g, ref)
    else:
        gm, solo = phase_a(g, ref)
        phase_b(gm, solo)
        phase_c()
    print(json.dumps({"ok": True, "device": info}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
