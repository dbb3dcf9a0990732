"""Pallas kernels (interpret=True on CPU) vs pure-jnp oracles: shape/dtype
sweeps as required per kernel."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
from _hyp import given, settings, st

from repro.core import bitvec
from repro.kernels import ops, ref


@pytest.mark.parametrize("p,w", [(1, 1), (7, 3), (64, 1), (256, 13), (300, 40), (8, 128)])
def test_lod_matches_ref(p, w):
    rng = np.random.default_rng(p * 1000 + w)
    bits = rng.integers(0, 2**32, size=(p, w), dtype=np.uint32)
    bits[rng.random((p, w)) < 0.5] = 0
    bits[0] = 0  # empty row -> -1
    got = ops.lod(jnp.asarray(bits))
    want = ref.lod_ref(jnp.asarray(bits))
    np.testing.assert_array_equal(got, want)
    assert int(got[0]) == -1


@pytest.mark.parametrize("p,w", [(4, 2), (256, 13), (128, 40)])
def test_schedule_step_matches_ref(p, w):
    rng = np.random.default_rng(p + w)
    bits = rng.integers(0, 2**32, size=(p, w), dtype=np.uint32)
    bits[rng.random((p, w)) < 0.5] = 0
    s_got, nb_got = ops.schedule_step(jnp.asarray(bits))
    s_want, nb_want = ref.schedule_step_ref(jnp.asarray(bits))
    np.testing.assert_array_equal(s_got, s_want)
    np.testing.assert_array_equal(nb_got, nb_want)


@pytest.mark.parametrize("p,w", [(4, 2), (256, 13), (37, 5)])
def test_schedule_step_gated(p, w):
    rng = np.random.default_rng(p * 7 + w)
    bits = rng.integers(0, 2**32, size=(p, w), dtype=np.uint32)
    bits[rng.random((p, w)) < 0.5] = 0
    gate = rng.random(p) < 0.5
    s_got, nb_got = ops.schedule_step(jnp.asarray(bits), jnp.asarray(gate))
    s_want, nb_want = ref.schedule_step_ref(jnp.asarray(bits), jnp.asarray(gate))
    np.testing.assert_array_equal(s_got, s_want)
    np.testing.assert_array_equal(nb_got, nb_want)
    # ungated rows still pick but must keep their bits intact
    np.testing.assert_array_equal(np.asarray(nb_got)[~gate], bits[~gate])
    s_all, _ = ops.schedule_step(jnp.asarray(bits))
    np.testing.assert_array_equal(s_got, s_all)


@pytest.mark.parametrize("p,w", [(4, 2), (256, 13), (37, 5)])
def test_rotating_schedule_step_matches_ref(p, w):
    rng = np.random.default_rng(p * 13 + w)
    bits = rng.integers(0, 2**32, size=(p, w), dtype=np.uint32)
    bits[rng.random((p, w)) < 0.5] = 0
    ptr = rng.integers(0, w * 32, size=p, dtype=np.int32)
    gate = rng.random(p) < 0.7
    got = ops.rotating_schedule_step(jnp.asarray(bits), jnp.asarray(ptr),
                                     jnp.asarray(gate))
    want = ref.rotating_schedule_step_ref(jnp.asarray(bits), jnp.asarray(ptr),
                                          jnp.asarray(gate))
    for a, b in zip(got, want):
        np.testing.assert_array_equal(a, b)


def test_rotating_schedule_step_semantics():
    # one row, flags at slots 3 and 40 (W=2): ptr selects the rotating window
    bits = bitvec.set_bit(jnp.zeros((1, 2), jnp.uint32), jnp.asarray([3]),
                          jnp.asarray([True]))
    bits = bitvec.set_bit(bits, jnp.asarray([40]), jnp.asarray([True]))
    for ptr, want in [(0, 3), (3, 3), (4, 40), (40, 40), (41, 3)]:
        slot, nb = ops.rotating_schedule_step(bits, jnp.asarray([ptr]))
        assert int(slot[0]) == want, (ptr, int(slot[0]))
        assert not bool(bitvec.test_bit(nb, jnp.asarray([want]))[0])
    # and the rotating ref matches the jnp scheduler policy's select
    from repro.core import schedulers
    rng = np.random.default_rng(5)
    rbits = jnp.asarray(rng.integers(0, 2**32, size=(1, 24, 3), dtype=np.uint32))
    rptr = jnp.asarray(rng.integers(0, 96, size=(1, 24), dtype=np.int32))
    pol = schedulers.get("lru_flat")
    cand, have = pol.select(dict(rdy=rbits, ptr=rptr), jnp.ones((1, 24), bool))
    slot, _ = ops.rotating_schedule_step(rbits.reshape(24, 3), rptr.reshape(24))
    np.testing.assert_array_equal(np.asarray(cand).reshape(-1), np.asarray(slot))


def test_schedule_step_drains_all_bits():
    rng = np.random.default_rng(0)
    bits = jnp.asarray(rng.integers(0, 2**32, size=(8, 4), dtype=np.uint32))
    total = int(bitvec.count_set(bits).sum())
    for _ in range(total):
        slot, bits = ops.schedule_step(bits)
    assert int(bitvec.count_set(bits).sum()) == 0
    slot, _ = ops.schedule_step(bits)
    assert (np.asarray(slot) == -1).all()


@given(st.integers(1, 64), st.integers(1, 8), st.integers(0, 10_000))
@settings(max_examples=20, deadline=None)
def test_lod_property(p, w, seed):
    rng = np.random.default_rng(seed)
    bits = rng.integers(0, 2**32, size=(p, w), dtype=np.uint32)
    got = np.asarray(ops.lod(jnp.asarray(bits)))
    for i in range(p):
        row = bits[i]
        if row.any():
            word = int(np.argmax(row != 0))
            bit = 31 - int(np.floor(np.log2(row[word])))
            assert got[i] == word * 32 + bit
        else:
            assert got[i] == -1


@pytest.mark.parametrize(
    "b,hq,hkv,tq,tkv,d,causal,dtype",
    [
        (2, 4, 2, 128, 128, 64, True, np.float32),
        (1, 2, 1, 64, 256, 128, True, np.float32),
        (1, 4, 4, 128, 128, 80, False, np.float32),
        (2, 2, 2, 96, 160, 64, True, np.float32),
        (1, 2, 2, 128, 128, 64, True, np.dtype("bfloat16")),
    ],
)
def test_flash_attention_matches_ref(b, hq, hkv, tq, tkv, d, causal, dtype):
    rng = np.random.default_rng(3)
    q = rng.standard_normal((b, hq, tq, d)).astype(dtype)
    k = rng.standard_normal((b, hkv, tkv, d)).astype(dtype)
    v = rng.standard_normal((b, hkv, tkv, d)).astype(dtype)
    got = ops.flash_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                              causal=causal, block_q=64, block_k=64)
    want = ref.flash_attention_ref(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                                   causal=causal)
    tol = 2e-5 if dtype == np.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), rtol=tol, atol=tol)


def test_bitvec_set_clear_roundtrip():
    bits = jnp.zeros((4, 2), jnp.uint32)
    slot = jnp.asarray([0, 31, 32, 63])
    on = jnp.asarray([True, True, True, False])
    bits = bitvec.set_bit(bits, slot, on)
    got = bitvec.test_bit(bits, slot)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(on))
    assert int(bitvec.count_set(bits).sum()) == 3
    off = jnp.asarray([False, True, True, True])
    bits = bitvec.clear_bit(bits, slot, off)
    np.testing.assert_array_equal(np.asarray(bitvec.test_bit(bits, slot)),
                                  np.asarray([True, False, False, False]))
    assert int(bitvec.count_set(bits).sum()) == 1
