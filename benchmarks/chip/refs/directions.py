"""Seeded Rademacher directions, written from the protocol's definition.

A client uploads a 32-bit seed; client and server regenerate the same
direction from it.  The definition (32-bit unsigned arithmetic, wrapping):

    mix(x)       = SplitMix32 finalizer: x += 0x9E3779B9; x ^= x >> 16;
                   x *= 0x21F0AAAD; x ^= x >> 15; x *= 0x735A2D97;
                   x ^= x >> 15
    block seed   = mix(seed ^ (0xA511E9B3 + j))         (projection j)
    leaf seed    = mix(block seed ^ mix(leaf ordinal))  (tree_leaves order)
    bits(r, c)   = mix(mix(mix(leaf seed ^ 0x9E3779B9) ^ r) ^ c)
    v[r, c]      = +1 if bit 8 of bits(r, c) is set, else -1

where (r, c) index the leaf viewed as a matrix: all leading dimensions
flattened into rows, the last dimension as columns (a scalar is 1x1, a
vector one row).  Nothing here is imported from the system under test.
"""
from __future__ import annotations

import math

import jax.numpy as jnp

__all__ = ["mix", "leaf_seed", "rademacher", "view2d"]

TAG = 0x9E3779B9
PROJ_SALT = 0xA511E9B3


def _u(x):
    return jnp.asarray(x, jnp.uint32)


def mix(x):
    x = _u(x) + _u(0x9E3779B9)
    x = x ^ (x >> 16)
    x = x * _u(0x21F0AAAD)
    x = x ^ (x >> 15)
    x = x * _u(0x735A2D97)
    return x ^ (x >> 15)


def leaf_seed(seed, leaf_ordinal: int, j: int = 0):
    block = mix(_u(seed) ^ (_u(PROJ_SALT) + _u(j)))
    return mix(block ^ mix(_u(leaf_ordinal)))


def rademacher(lseed, row, col):
    """±1.0 (float32) at broadcast (leaf seed, row, col)."""
    h = mix(mix(mix(_u(lseed) ^ _u(TAG)) ^ _u(row)) ^ _u(col))
    return jnp.where(((h >> 8) & _u(1)) == 1, 1.0, -1.0).astype(jnp.float32)


def view2d(shape) -> tuple[int, int]:
    if len(shape) == 0:
        return 1, 1
    if len(shape) == 1:
        return 1, int(shape[0])
    return int(math.prod(shape[:-1])), int(shape[-1])
