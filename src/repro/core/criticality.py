"""Static one-time criticality labeling (paper §II-B).

The paper runs "a one-time software criticality evaluation on the application
dataflow graph" and stores nodes in each PE's local memory in *decreasing*
criticality order, so that the hierarchical leading-one detector implicitly
picks the most critical ready node.

We provide three metrics:
  * ``height``  — longest path (in nodes) from the node to any sink. This is
    the classic critical-path criticality: nodes on the critical path have
    maximal height at their depth. (default; what the paper's heuristic needs)
  * ``slack``   — ALAP(v) - ASAP(v); criticality = -slack (0-slack nodes are
    on the critical path).
  * ``fanout_height`` — height weighted by downstream fanout mass, a tiebreak
    that prefers nodes unlocking more parallelism.
"""
from __future__ import annotations

import numpy as np

from .graph import DataflowGraph


#: A peel step over a frontier narrower than this runs as a plain loop: on
#: a chain's one-node frontiers the whole-array calls cost more than they
#: save.
_NARROW = 16


def _peel(n: int, ptr: np.ndarray, nbr: np.ndarray,
          waits: np.ndarray) -> np.ndarray:
    """Level-synchronous peel of a DAG: [N] step at which each node leaves.

    ``ptr``/``nbr`` is a CSR of the nodes each node releases, ``waits`` [N]
    how many nodes each waits for. Step 0 removes the nodes that wait for
    none; step k removes those whose last release came at step k-1, so a
    node's step is one more than the largest of the nodes it waits for.
    """
    level = np.zeros(n, dtype=np.int64)
    left = waits.astype(np.int64)
    frontier = np.flatnonzero(left == 0)
    removed, step = 0, 0
    while frontier.size:
        level[frontier] = step
        removed += frontier.size
        if frontier.size < _NARROW:
            nxt = []
            for v in frontier.tolist():
                for u in nbr[ptr[v]:ptr[v + 1]].tolist():
                    left[u] -= 1
                    if left[u] == 0:
                        nxt.append(u)
            frontier = np.array(nxt, dtype=np.int64)
        else:
            # Ragged gather of every released node: the concatenated
            # [lo, hi) ranges of the frontier.
            lo, lens = ptr[frontier], ptr[frontier + 1] - ptr[frontier]
            idx = (np.repeat(lo - np.cumsum(lens) + lens, lens)
                   + np.arange(int(lens.sum())))
            hit, times = np.unique(nbr[idx], return_counts=True)
            left[hit] -= times
            frontier = hit[left[hit] == 0]
        step += 1
    if removed != n:
        raise ValueError("graph has a cycle")
    return level


def asap_levels(g: DataflowGraph) -> np.ndarray:
    """[N] earliest firing level (INPUTs at 0)."""
    indeg = np.bincount(g.fanout_dst, minlength=g.num_nodes)
    return _peel(g.num_nodes, g.fanout_ptr, g.fanout_dst, indeg)


def height(g: DataflowGraph) -> np.ndarray:
    """[N] longest path to a sink, in edges (sinks have height 0)."""
    n = g.num_nodes
    # Fanin CSR: each edge's source, grouped by destination (stable).
    by_dst = np.argsort(g.fanout_dst, kind="stable")
    src = np.repeat(np.arange(n), g.fanout_count())[by_dst]
    rptr = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(np.bincount(g.fanout_dst, minlength=n), out=rptr[1:])
    return _peel(n, rptr, src, g.fanout_count())


def _slack(g: DataflowGraph, h: np.ndarray) -> np.ndarray:
    asap = asap_levels(g)
    depth = int((asap + h).max()) if g.num_nodes else 0
    alap = depth - h
    return alap - asap


def slack(g: DataflowGraph) -> np.ndarray:
    """[N] ALAP - ASAP. Zero slack == critical path."""
    return _slack(g, height(g))


def _fanout_height(g: DataflowGraph, h: np.ndarray) -> np.ndarray:
    fo = g.fanout_count().astype(np.float64)
    return h.astype(np.float64) + fo / (fo.max() + 1.0)


def fanout_height(g: DataflowGraph) -> np.ndarray:
    """Height with a fractional fanout tiebreak in [0, 1)."""
    return _fanout_height(g, height(g))


_METRICS = {
    "height": lambda g, h: h,
    "neg_slack": lambda g, h: -_slack(g, h),
    "fanout_height": _fanout_height,
}


def criticality_levels(g: DataflowGraph,
                       metric: str = "height") -> tuple[np.ndarray, int]:
    """[N] criticality labels (larger == more critical) and the graph's depth
    in levels: the number of steps the peel behind ``height`` took."""
    try:
        fn = _METRICS[metric]
    except KeyError:
        raise ValueError(f"unknown criticality metric {metric!r}; have {sorted(_METRICS)}")
    h = height(g)
    return np.asarray(fn(g, h)), int(h.max(initial=-1)) + 1


def criticality(g: DataflowGraph, metric: str = "height") -> np.ndarray:
    """[N] criticality labels; larger == more critical."""
    return criticality_levels(g, metric)[0]
