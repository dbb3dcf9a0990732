"""Packed RDY bit-flag vectors (paper §II-B), in JAX.

Slot ``s`` of a PE maps to word ``s // 32``, bit position ``31 - s % 32`` —
slot 0 occupies the *most significant* bit of word 0, so the paper's
"leading-one detector" (find the first 1 scanning from the MSB of word 0)
returns the lowest slot index == the most critical ready node.

These are the pure-jnp reference semantics; ``repro.kernels.lod`` implements
the same hierarchical detect as a Pallas TPU kernel.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

FLAGS_PER_WORD = 32
_U32 = jnp.uint32


def slot_word_mask(slot):
    """slot -> (word index, uint32 single-bit mask)."""
    slot = slot.astype(jnp.int32)
    word = slot // FLAGS_PER_WORD
    bitpos = (31 - (slot % FLAGS_PER_WORD)).astype(_U32)
    return word, (_U32(1) << bitpos)


def slot_bits(slot, W):
    """[...] slot -> [..., W] uint32 row holding only the slot's flag.

    Setting or clearing one flag per row ORs this row in or masks it out:
    one dense pass over the row, which on a TPU costs less than a scatter
    of one word per row (a scatter's cost is per index, not per byte).
    """
    word, mask = slot_word_mask(slot)
    hit = jnp.arange(W, dtype=jnp.int32) == word[..., None]
    return jnp.where(hit, mask[..., None], _U32(0))


def set_bit(bits, slot, on):
    """Set flag ``slot`` of each row where ``on``. bits: [..., W];
    slot/on: [...]."""
    flag = slot_bits(slot, bits.shape[-1])
    return bits | jnp.where(on[..., None], flag, _U32(0))


def clear_bit(bits, slot, off):
    """Clear flag ``slot`` of each row where ``off``. bits: [..., W];
    slot/off: [...]."""
    flag = slot_bits(slot, bits.shape[-1])
    return bits & ~jnp.where(off[..., None], flag, _U32(0))


def test_bit(bits, slot):
    """Whether flag ``slot`` of each row is set. bits: [..., W]; slot: [...]."""
    return (bits & slot_bits(slot, bits.shape[-1])).any(axis=-1)


def smear(w):
    """Propagate the leading one to all lower bits (uint32)."""
    w = w | (w >> 1)
    w = w | (w >> 2)
    w = w | (w >> 4)
    w = w | (w >> 8)
    w = w | (w >> 16)
    return w


def popcount(w):
    """SWAR population count (uint32) — the form the Pallas kernel uses."""
    w = w - ((w >> 1) & _U32(0x55555555))
    w = (w & _U32(0x33333333)) + ((w >> 2) & _U32(0x33333333))
    w = (w + (w >> 4)) & _U32(0x0F0F0F0F)
    return (w * _U32(0x01010101)) >> 24


def mask_slots_ge(ptr, W):
    """[...] slot pointer -> [..., W] uint32 mask of slots >= ptr.

    Slot ``s`` lives at word s // 32, bit position 31 - s % 32, so within the
    pointer's word the surviving bits are positions 0 .. 31 - ptr % 32. This
    is the rotating-priority window of the ``scan``/``lru_flat`` policies;
    ``repro.kernels.lod`` implements the same mask inside the Pallas rotating
    select kernel.
    """
    word_ids = jnp.arange(W, dtype=jnp.int32)
    pw = ptr // FLAGS_PER_WORD
    pb = (ptr % FLAGS_PER_WORD).astype(_U32)
    full = _U32(0xFFFFFFFF)
    eq = (full >> pb)[..., None]
    return jnp.where(
        word_ids > pw[..., None], full,
        jnp.where(word_ids < pw[..., None], _U32(0), eq))


def lod_word(w):
    """Leading-one position inside a word: 0 == MSB. Undefined for w == 0."""
    # clz(w) = 32 - popcount(smear(w)); leading-one slot offset == clz.
    return (_U32(32) - popcount(smear(w))).astype(jnp.int32)


def leading_one(bits):
    """Hierarchical leading-one detect over packed rows.

    bits: [..., W] uint32. Returns int32 slot index of the first set flag in
    (word, MSB-first-bit) order, or -1 if the row is empty. This is the jnp
    reference for the OuterLOD/InnerLOD circuit pair.
    """
    w = bits.shape[-1]
    nonzero = bits != 0
    any_set = nonzero.any(axis=-1)
    # OuterLOD: first nonzero word (argmax returns the first True).
    word_idx = jnp.argmax(nonzero, axis=-1).astype(jnp.int32)
    sel = jnp.take_along_axis(bits, word_idx[..., None].astype(jnp.int32), axis=-1)[..., 0]
    # InnerLOD: leading-one position within the selected word.
    slot = word_idx * FLAGS_PER_WORD + lod_word(sel)
    return jnp.where(any_set, slot, jnp.int32(-1))


def count_set(bits):
    """Total set flags per row ([..., W] -> [...])."""
    return popcount(bits).astype(jnp.int32).sum(axis=-1)
