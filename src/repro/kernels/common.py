"""Shared in-kernel PRNG: SplitMix32 chain, bit-identical to repro.core.prng.

The kernels regenerate the projection vector v per VMEM tile from
``(seed, row, col)`` — v never exists in HBM.  These helpers are plain
uint32 jnp ops, so the same code runs inside a Pallas kernel body, in
interpret mode, and in the pure-jnp oracle (ref.py); bit-equality across
the three is what the kernel tests assert.
"""
from __future__ import annotations

import jax.numpy as jnp

_TAG_U1 = 0x9E3779B9
_TAG_U2 = 0x85EBCA6B

# Walsh-Hadamard / sparse constants — must match repro.core.prng exactly.
_TAG_HAD_MR = 0xC2B2AE35
_TAG_HAD_MC = 0x27D4EB2F
_TAG_HAD_TR = 0x165667B1
_TAG_HAD_TC = 0x9E3779F9
_HAD_MASK_FALLBACK = 0x9E3779B9
SPARSE_S = 4


def _u32(x):
    return jnp.asarray(x, jnp.uint32)


def splitmix32(x):
    x = _u32(x)
    x = x + _u32(0x9E3779B9)
    x = x ^ (x >> 16)
    x = x * _u32(0x21F0AAAD)
    x = x ^ (x >> 15)
    x = x * _u32(0x735A2D97)
    x = x ^ (x >> 15)
    return x


def hash_u32(seed, hi, lo, tag):
    h = splitmix32(_u32(seed) ^ _u32(tag))
    h = splitmix32(h ^ _u32(hi))
    h = splitmix32(h ^ _u32(lo))
    return h


def fold_seed(seed, leaf_tag):
    return splitmix32(_u32(seed) ^ splitmix32(_u32(leaf_tag)))


def u32_to_f32(bits):
    """uint32 → float32, correctly rounded, without a uint32→float32 cast.

    Mosaic has no unsigned-to-float conversion.  Each 16-bit half
    converts exactly through int32, ``hi·65536`` is exact, and the one
    add rounds once — so this equals ``bits.astype(float32)`` to the bit
    (an FMA contraction of the product cannot change that either).
    """
    bits = _u32(bits)
    hi = (bits >> 16).astype(jnp.int32).astype(jnp.float32)
    lo = (bits & _u32(0xFFFF)).astype(jnp.int32).astype(jnp.float32)
    return hi * jnp.float32(65536.0) + lo


def flat_index(row, col, orig_cols: int):
    """Leaf-local flat index of (row, col) as float32 (the block-mask
    domain, exact below 2²⁴).  Coordinates are below 2³¹, so they
    convert through int32, which Mosaic supports."""
    return (row.astype(jnp.int32).astype(jnp.float32) * jnp.float32(orig_cols)
            + col.astype(jnp.int32).astype(jnp.float32))


def uniform01(bits):
    return (u32_to_f32(bits) + 1.0) * jnp.float32(2.0**-32)


def parity32(x):
    """XOR-fold parity of each uint32 lane (no popcount: Pallas-legal)."""
    x = _u32(x)
    x = x ^ (x >> 16)
    x = x ^ (x >> 8)
    x = x ^ (x >> 4)
    x = x ^ (x >> 2)
    x = x ^ (x >> 1)
    return x & _u32(1)


def gen_tile(seed_folded, row, col, distribution: str):
    """v values for a tile of global (row, col) uint32 coordinate arrays.

    Matches ``repro.core.prng.random_for_shape`` exactly for every
    direction family (DESIGN.md §6): the caller folds the leaf tag into
    the seed first (``fold_seed``).
    """
    if distribution == "rademacher":
        bits = hash_u32(seed_folded, row, col, _TAG_U1)
        sign = (bits >> 8) & _u32(1)
        return jnp.where(sign == 1, 1.0, -1.0).astype(jnp.float32)
    if distribution == "gaussian":
        u1 = uniform01(hash_u32(seed_folded, row, col, _TAG_U1))
        u2 = uniform01(hash_u32(seed_folded, row, col, _TAG_U2))
        r = jnp.sqrt(-2.0 * jnp.log(u1))
        return r * jnp.cos(jnp.float32(2.0 * jnp.pi) * u2)
    if distribution == "sparse_rademacher":
        bits = hash_u32(seed_folded, row, col, _TAG_U1)
        active = (bits & _u32(SPARSE_S - 1)) == 0
        sign = jnp.where((bits >> 8) & _u32(1) == 1, 1.0, -1.0)
        return jnp.where(active, sign * jnp.float32(float(SPARSE_S) ** 0.5),
                         jnp.float32(0.0))
    if distribution == "hadamard":
        s = _u32(seed_folded)
        m_r = splitmix32(s ^ _u32(_TAG_HAD_MR))
        m_r = jnp.where(m_r == 0, _u32(_HAD_MASK_FALLBACK), m_r)
        m_c = splitmix32(s ^ _u32(_TAG_HAD_MC))
        m_c = jnp.where(m_c == 0, _u32(_HAD_MASK_FALLBACK), m_c)
        t_r = splitmix32(s ^ _u32(_TAG_HAD_TR))
        t_c = splitmix32(s ^ _u32(_TAG_HAD_TC))
        bit = parity32((_u32(row) ^ t_r) & m_r) ^ parity32((_u32(col) ^ t_c) & m_c)
        return jnp.where(bit == 0, 1.0, -1.0).astype(jnp.float32)
    raise ValueError(distribution)


# ---------------------------------------------------------------------------
# Factored direction chain: the per-element hash split at its natural
# seams.  ``hash_u32(s, row, col, tag)`` is three chained SplitMix32
# rounds; the first depends only on the seed, the second only on
# (seed, row).  ``row_state`` evaluates those two rounds once per
# (seed, row) — over a column of a tile, or a whole (chunk, rows)
# batch — and ``tile_from_state`` finishes with the single per-element
# round (plus the family's value map).  Because this is a pure
# re-bracketing of the *same* chain, values are bit-identical to
# ``gen_tile`` / ``repro.core.prng.random_for_shape``; it exists so the
# fused reconstruct+apply path and the projection kernel share one
# generator whose per-element integer work is one SplitMix round, not
# three (DESIGN §11).
# ---------------------------------------------------------------------------


def row_state(seed_folded, row, distribution: str) -> tuple:
    """Hoisted per-(seed, row) chain state for ``tile_from_state``.

    ``seed_folded`` and ``row`` broadcast against each other (e.g.
    ``(cb, 1, 1)`` seeds × ``(1, R, 1)`` rows → ``(cb, R, 1)`` states).
    """
    s = _u32(seed_folded)
    r = _u32(row)
    if distribution in ("rademacher", "sparse_rademacher"):
        return (splitmix32(splitmix32(s ^ _u32(_TAG_U1)) ^ r),)
    if distribution == "gaussian":
        return (splitmix32(splitmix32(s ^ _u32(_TAG_U1)) ^ r),
                splitmix32(splitmix32(s ^ _u32(_TAG_U2)) ^ r))
    if distribution == "hadamard":
        m_r = splitmix32(s ^ _u32(_TAG_HAD_MR))
        m_r = jnp.where(m_r == 0, _u32(_HAD_MASK_FALLBACK), m_r)
        m_c = splitmix32(s ^ _u32(_TAG_HAD_MC))
        m_c = jnp.where(m_c == 0, _u32(_HAD_MASK_FALLBACK), m_c)
        t_r = splitmix32(s ^ _u32(_TAG_HAD_TR))
        t_c = splitmix32(s ^ _u32(_TAG_HAD_TC))
        return (parity32((r ^ t_r) & m_r), m_c, t_c)
    raise ValueError(distribution)


def tile_from_state(state: tuple, col, distribution: str):
    """v values from a :func:`row_state` tuple and a broadcastable col."""
    c = _u32(col)
    if distribution == "rademacher":
        bits = splitmix32(state[0] ^ c)
        sign = (bits >> 8) & _u32(1)
        return jnp.where(sign == 1, 1.0, -1.0).astype(jnp.float32)
    if distribution == "gaussian":
        u1 = uniform01(splitmix32(state[0] ^ c))
        u2 = uniform01(splitmix32(state[1] ^ c))
        r = jnp.sqrt(-2.0 * jnp.log(u1))
        return r * jnp.cos(jnp.float32(2.0 * jnp.pi) * u2)
    if distribution == "sparse_rademacher":
        bits = splitmix32(state[0] ^ c)
        active = (bits & _u32(SPARSE_S - 1)) == 0
        sign = jnp.where((bits >> 8) & _u32(1) == 1, 1.0, -1.0)
        return jnp.where(active, sign * jnp.float32(float(SPARSE_S) ** 0.5),
                         jnp.float32(0.0))
    if distribution == "hadamard":
        pr, m_c, t_c = state
        bit = pr ^ parity32((c ^ t_c) & m_c)
        return jnp.where(bit == 0, 1.0, -1.0).astype(jnp.float32)
    raise ValueError(distribution)
