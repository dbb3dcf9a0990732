"""Operator DAG of GAP's PageRank (``pr``, pulled over in-neighbours) on
GAP's ``kron`` graph, unrolled for a fixed number of iterations.

The graph is the Graph500 Kronecker generator's ``edge_factor * 2**scale``
edges (initiator A 0.57, B 0.19, C 0.19), from ``np.random.default_rng(seed)``:
per bit level, a row bit with P(1) = 1 - A - B, then a column bit with P(1) =
C / (1 - A - B) after a row bit 1 and B / (A + B) after a 0; then a
permutation of the ``2**scale`` labels. It is made undirected, and self
loops and duplicate edges are dropped.

With N = 2**scale and V the vertices of nonzero degree in ascending label,
each iteration computes, for every v in V, ``contrib_v = score_v * inv_deg_v``,
``incoming_v`` as a balanced pairwise ADD tree of the neighbours' ``contrib``
in ascending label, and ``score_v = base_v + incoming_v * damp_v``. The
constants are inputs of their one consumer (``inv_deg`` of one per
iteration): ``score0 = 1/N``, ``inv_deg = 1/deg``, ``damp = damping``,
``base = (1 - damping)/N``.

Node ids: inputs ``score0``, ``inv_deg``, then each iteration's ``damp`` and
``base``, V each; then per iteration ``contrib``, the ADD trees level by
level (each vertex's list paired 0-1, 2-3, ..., an odd last element carried
to the end of the next level's list), the ``incoming * damp`` products and
the scores. Edges are sorted by (producer, consumer, slot). So the graph is
the same, id for id, as the program's own ``kron_pagerank_graph``.
"""
from __future__ import annotations

import numpy as np

OP_INPUT, OP_ADD, OP_MUL = 0, 1, 3


def _edges(scale: int, edge_factor: int, seed: int):
    n = 2 ** scale
    m = edge_factor * n
    rng = np.random.default_rng(seed)
    row = np.zeros(m, np.int64)
    col = np.zeros(m, np.int64)
    for level in range(scale):
        r = rng.random(m) > 0.57 + 0.19
        c = rng.random(m) > np.where(r, 0.19 / (1 - 0.57 - 0.19),
                                     0.57 / (0.57 + 0.19))
        row += r * 2 ** level
        col += c * 2 ** level
    label = rng.permutation(n)
    row, col = label[row], label[col]
    loop = row == col
    both = np.stack([np.concatenate([row[~loop], col[~loop]]),
                     np.concatenate([col[~loop], row[~loop]])], axis=1)
    return np.unique(both, axis=0)  # rows sorted by (u, v)


def build(scale: int, edge_factor: int, iterations: int, damping: float,
          seed: int) -> dict:
    """Graph arrays: ``opcode``, ``fanout_ptr``, ``fanout_dst``,
    ``fanout_slot`` and ``initial_values``."""
    n = 2 ** scale
    uv = _edges(scale, edge_factor, seed)
    verts, first, deg = np.unique(uv[:, 0], return_index=True,
                                  return_counts=True)
    rank = np.full(n, -1, np.int64)
    rank[verts] = np.arange(verts.size)
    nbr = rank[uv[:, 1]]
    nv = verts.size

    op, init, edges = [], [], []  # edges: (producer, consumer, slot) arrays

    def new_inputs(values) -> np.ndarray:
        start = sum(a.size for a in op)
        op.append(np.zeros(nv, np.int8))
        init.append(np.asarray(values, np.float32) * np.ones(nv, np.float32))
        return start + np.arange(nv)

    def new_ops(code, a, b) -> np.ndarray:
        start = sum(x.size for x in op)
        ids = start + np.arange(len(a))
        op.append(np.full(len(a), code, np.int8))
        init.append(np.zeros(len(a), np.float32))
        edges.append((a, ids, 0))
        edges.append((b, ids, 1))
        return ids

    score = new_inputs(np.float32(1.0 / n))
    inv_deg = new_inputs((1.0 / deg).astype(np.float32))
    damp = [new_inputs(np.float32(damping)) for _ in range(iterations)]
    base = [new_inputs(np.float32((1.0 - damping) / n))
            for _ in range(iterations)]
    for it in range(iterations):
        contrib = new_ops(OP_MUL, score, inv_deg)
        # One list per vertex; each pass pairs neighbours, all vertices at once.
        lists = np.split(contrib[nbr], first[1:])
        while max(len(x) for x in lists) > 1:
            pairs = [(x[0:len(x) - 1:2], x[1::2]) for x in lists]
            a = np.concatenate([p[0] for p in pairs])
            b = np.concatenate([p[1] for p in pairs])
            summed = new_ops(OP_ADD, a, b)
            out, k = [], 0
            for x, (pa, _) in zip(lists, pairs):
                nxt = summed[k:k + len(pa)]
                k += len(pa)
                out.append(np.concatenate([nxt, x[-1:]]) if len(x) % 2 else nxt)
            lists = out
        incoming = np.concatenate(lists)
        score = new_ops(OP_ADD, base[it], new_ops(OP_MUL, incoming, damp[it]))

    total = sum(a.size for a in op)
    src = np.concatenate([np.asarray(e[0], np.int64) for e in edges])
    dst = np.concatenate([e[1] for e in edges])
    slot = np.concatenate([np.full(len(e[1]), e[2], np.int8) for e in edges])
    order = np.lexsort((slot, dst, src))
    ptr = np.zeros(total + 1, np.int64)
    np.add.at(ptr, src + 1, 1)
    return dict(
        opcode=np.concatenate(op),
        fanout_ptr=np.cumsum(ptr),
        fanout_dst=dst[order],
        fanout_slot=slot[order],
        initial_values=np.concatenate(init),
    )
