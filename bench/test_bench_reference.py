"""The benchmark's plain reference against the program's own evaluator."""
import numpy as np
import pytest

from bench import reference
from repro.core import graph as G
from repro.core import workloads as wl


def _levels(g):
    return reference.levelize(g.opcode, g.fanout_ptr, g.fanout_dst, g.fanout_slot)


@pytest.mark.parametrize("make", [
    lambda: wl.arrow_lu_graph(4, 6, 4, seed=1),
    lambda: wl.banded_lu_graph(30, 3, seed=2),
    lambda: wl.random_dag(500, seed=3),
    lambda: wl.random_dag(300, seed=4, input_frac=0.05),
], ids=["arrow", "banded", "random", "random_deep"])
def test_levelized_equals_topological_bit_for_bit(make):
    g = make()
    got = reference.evaluate(_levels(g), g.initial_values)
    want = G.reference_evaluate(g)
    assert got.dtype == np.float32
    np.testing.assert_array_equal(got.view(np.uint32), want.view(np.uint32))


def test_alu_matches_program_alu_on_edge_operands():
    a = np.array([1.0, -2.0, 3.5, 0.0, 7.0, -1e-3, 5.0, 1.0], np.float32)
    b = np.array([0.0, -0.0, -1e-3, 2.0, 1e-3, 0.0, -3.0, -1.0], np.float32)
    for op in (G.OP_ADD, G.OP_SUB, G.OP_MUL, G.OP_DIV):
        got = reference.apply_op(np.full(a.shape, op), a, b)
        want = G.apply_op(np.full(a.shape, op), a, b)
        np.testing.assert_array_equal(got.view(np.uint32), want.view(np.uint32))


def test_control_precision_is_far_from_reference():
    g = wl.arrow_lu_graph(4, 6, 4, seed=1)
    lv = _levels(g)
    ref = reference.evaluate(lv, g.initial_values)
    low = reference.evaluate(lv, g.initial_values, reference.CONTROL_DTYPE)
    assert reference.value_gap(low, ref) > 1e-3
    assert reference.value_gap(ref, ref) == 0.0


def test_value_gap_reads_bad_answers_as_infinite():
    ref = np.ones(4, np.float32)
    assert reference.value_gap(np.array([1, 1, np.nan, 1], np.float32), ref) == np.inf
    assert reference.value_gap(np.ones(3, np.float32), ref) == np.inf
    # A gap is measured against the median magnitude where a value is near 0.
    ref2 = np.array([1e-9, 1.0, 1.0], np.float32)
    assert reference.value_gap(np.array([1e-6, 1.0, 1.0], np.float32), ref2) < 1e-5


def test_levelize_rejects_a_cycle():
    # 0 -> 1 -> 2 -> 1: node 1 and 2 wait on each other.
    opcode = np.array([0, 1, 1], np.int8)
    ptr = np.array([0, 2, 3, 4])
    dst = np.array([1, 2, 2, 1])
    slot = np.array([0, 0, 1, 1], np.int8)
    with pytest.raises(ValueError, match="cycle"):
        reference.levelize(opcode, ptr, dst, slot)
