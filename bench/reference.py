"""Plain reference evaluator of a dataflow graph, independent of the program.

It evaluates the same float32 ALU semantics as the overlay (add, sub, mul and
the "safe" divide ``a / (b + eps*sign(b))``) level by level: every node of one
dependence level is computed in one numpy call. A node's value does not depend
on the order in which independent nodes are evaluated, so the result equals a
node-by-node topological evaluation bit for bit.

The graph is taken as plain arrays (opcode, fanout CSR, operand slot, initial
values), so nothing here imports the simulator.
"""
from __future__ import annotations

import dataclasses

import ml_dtypes
import numpy as np

OP_INPUT, OP_ADD, OP_SUB, OP_MUL, OP_DIV = 0, 1, 2, 3, 4
DIV_EPS = 1e-3


def apply_op(opcode: np.ndarray, a: np.ndarray, b: np.ndarray,
             dtype=np.float32) -> np.ndarray:
    """The overlay's ALU on ``dtype`` operands, rounded to ``dtype``.

    With ``dtype=np.float32`` this is the configuration's stated precision;
    a narrower ``dtype`` (bfloat16) computes each operation in float32 and
    rounds its result, which is what the lower-precision control runs.
    """
    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    safe_b = b + np.where(b >= 0, DIV_EPS, -DIV_EPS).astype(np.float32)
    out = np.select(
        [opcode == OP_ADD, opcode == OP_SUB, opcode == OP_MUL, opcode == OP_DIV],
        [a + b, a - b, a * b, a / safe_b],
        default=np.float32(0),
    )
    return out.astype(np.float32).astype(dtype)


@dataclasses.dataclass(frozen=True)
class Levels:
    """A graph's structure, levelized once and reused for every value set.

    ``order`` lists the non-input nodes by dependence level and ``bounds``
    cuts it into levels; ``src_a`` / ``src_b`` are each node's operand
    producers (slot 0 and slot 1).
    """

    opcode: np.ndarray
    src_a: np.ndarray
    src_b: np.ndarray
    order: np.ndarray
    bounds: np.ndarray


def levelize(opcode, fanout_ptr, fanout_dst, fanout_slot) -> Levels:
    """Operand producers and dependence levels of a CSR-encoded DAG.

    Raises ``ValueError`` when a non-input node lacks an operand or the graph
    has a cycle.
    """
    opcode = np.asarray(opcode)
    ptr = np.asarray(fanout_ptr, np.int64)
    dst = np.asarray(fanout_dst, np.int64)
    slot = np.asarray(fanout_slot, np.int64)
    n = opcode.shape[0]
    src = np.repeat(np.arange(n, dtype=np.int64), np.diff(ptr))
    operand = np.full((n, 2), -1, np.int64)
    operand[dst, slot] = src
    is_op = opcode != OP_INPUT
    if (operand[is_op] < 0).any():
        raise ValueError("a non-input node lacks an operand")
    indeg = np.bincount(dst, minlength=n)
    level = np.zeros(n, np.int64)
    frontier = np.flatnonzero(indeg == 0)
    seen = frontier.size
    depth = 0
    while frontier.size:
        starts, stops = ptr[frontier], ptr[frontier + 1]
        counts = stops - starts
        # Edge indices of every frontier node's fanout, concatenated.
        offs = np.repeat(starts - np.cumsum(counts) + counts, counts)
        succ = dst[offs + np.arange(counts.sum())]
        indeg -= np.bincount(succ, minlength=n)
        nxt = np.unique(succ)
        frontier = nxt[indeg[nxt] == 0]
        depth += 1
        level[frontier] = depth
        seen += frontier.size
    if seen != n:
        raise ValueError("graph has a cycle")
    ops = np.flatnonzero(is_op)
    order = ops[np.argsort(level[ops], kind="stable")]
    bounds = np.searchsorted(level[order], np.arange(1, depth + 2))
    return Levels(opcode=opcode, src_a=operand[:, 0], src_b=operand[:, 1],
                  order=order, bounds=bounds)


def evaluate(lv: Levels, initial_values: np.ndarray,
             dtype=np.float32) -> np.ndarray:
    """[N] node values in ``dtype``, inputs from ``initial_values``."""
    value = np.asarray(initial_values, np.float32).astype(dtype)
    value[lv.order] = 0
    for lo, hi in zip(lv.bounds[:-1], lv.bounds[1:]):
        nodes = lv.order[lo:hi]
        value[nodes] = apply_op(lv.opcode[nodes], value[lv.src_a[nodes]],
                                value[lv.src_b[nodes]], dtype)
    return value


#: The precision one step below the configuration's float32.
CONTROL_DTYPE = ml_dtypes.bfloat16


def value_gap(values: np.ndarray, ref: np.ndarray) -> float:
    """Widest gap of ``values`` from the reference, node by node.

    Each node's gap is measured against the larger of its own reference
    magnitude and the median one, since LU updates cancel to values near
    zero whose relative error says nothing. A value that is not finite, or
    a length mismatch, reads as an infinite gap.
    """
    values = np.asarray(values, np.float64)
    ref = np.asarray(ref, np.float64)
    if values.shape != ref.shape or not np.isfinite(values).all():
        return float("inf")
    scale = np.maximum(np.abs(ref), np.median(np.abs(ref)))
    scale[scale == 0] = 1.0
    return float(np.max(np.abs(values - ref) / scale, initial=0.0))
