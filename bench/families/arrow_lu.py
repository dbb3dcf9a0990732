"""Operator DAG of a right-looking sparse LU factorization of a bordered
block-diagonal ("arrow") matrix: the structure of circuit matrices after
ordering.

``blocks`` dense diagonal blocks of ``block_size`` rows are coupled by a
dense border of ``border`` rows and columns. Every matrix entry version is a
token: ``L[i,k] = A[i,k] / U[k,k]`` is a DIV node, each update
``A[i,j] -= L[i,k] * U[k,j]`` a MUL and a SUB node, and fill-in a SUB from a
zero input. The structure depends on the sizes alone; ``seed`` draws the
input values (off-diagonal uniform(0.5, 2), diagonal that times ``n``).

Node ids follow creation order, and edges are sorted by producer, so the
graph is the same, id for id, as the program's own ``arrow_lu_graph``.
"""
from __future__ import annotations

import numpy as np

OP_INPUT, OP_SUB, OP_MUL, OP_DIV = 0, 2, 3, 4


def build(blocks: int, block_size: int, border: int, seed: int) -> dict:
    """Graph arrays: ``opcode``, ``fanout_ptr``, ``fanout_dst``,
    ``fanout_slot`` and ``initial_values``."""
    rng = np.random.default_rng(seed)
    n = blocks * block_size + border
    op: list[int] = []
    init: list[float] = []
    src: list[int] = []
    dst: list[int] = []
    slot: list[int] = []

    def add_input(value: float) -> int:
        op.append(OP_INPUT)
        init.append(value)
        return len(op) - 1

    def add_op(opcode: int, a: int, b: int) -> int:
        op.append(opcode)
        init.append(0.0)
        v = len(op) - 1
        src.extend((a, b))
        dst.extend((v, v))
        slot.extend((0, 1))
        return v

    edge = blocks * block_size
    rows = []
    for i in range(n):
        row = {}
        for j in range(n):
            if i == j or i >= edge or j >= edge or i // block_size == j // block_size:
                row[j] = add_input(rng.uniform(0.5, 2.0) * (n if i == j else 1.0))
        rows.append(row)
    for k in range(n):
        pivot = rows[k][k]
        for i in range(k + 1, n):
            if k not in rows[i]:
                continue
            lik = add_op(OP_DIV, rows[i][k], pivot)
            del rows[i][k]
            for j, ukj in list(rows[k].items()):
                if j <= k:
                    continue
                prod = add_op(OP_MUL, lik, ukj)
                if j in rows[i]:
                    rows[i][j] = add_op(OP_SUB, rows[i][j], prod)
                else:
                    rows[i][j] = add_op(OP_SUB, add_input(0.0), prod)
    src_a = np.asarray(src, np.int64)
    order = np.argsort(src_a, kind="stable")
    ptr = np.zeros(len(op) + 1, np.int64)
    np.add.at(ptr, src_a + 1, 1)
    return dict(
        opcode=np.asarray(op, np.int8),
        fanout_ptr=np.cumsum(ptr),
        fanout_dst=np.asarray(dst, np.int64)[order],
        fanout_slot=np.asarray(slot, np.int8)[order],
        initial_values=np.asarray(init, np.float32),
    )
