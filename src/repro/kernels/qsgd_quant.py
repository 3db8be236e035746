"""Pallas TPU kernel: QSGD stochastic quantize→dequantize round trip.

The QSGD baseline's hot loop.  Unbiased stochastic rounding to
``levels`` magnitude levels, with the rounding uniforms drawn from the
same counter-based hash as the projection kernels — so the kernel is
deterministic given (seed, coordinates) and the oracle reproduces it
bit-for-bit.  The global L2 norm is computed outside (one pass) and
passed in SMEM; the kernel fuses |x|/s scaling, stochastic round and
dequantize in one VMEM pass.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.qsgd import QSGD_TAG
from repro.kernels.common import fold_seed, hash_u32, uniform01

__all__ = ["qsgd_kernel_call"]

DEFAULT_BLOCK = (256, 512)
# Stream tag of the rounding uniforms — single source: repro.core.qsgd,
# so kernel, jnp oracle and the core round-trip hash identically.
_TAG_Q = QSGD_TAG


def _qsgd_kernel(seed_ref, norm_ref, x_ref, o_ref, *, levels: int,
                 block: tuple, row_offset: int, col_offset: int):
    pi = pl.program_id(0)
    pj = pl.program_id(1)
    br, bc = block
    row = (jax.lax.broadcasted_iota(jnp.uint32, (br, bc), 0)
           + jnp.uint32(row_offset) + pi.astype(jnp.uint32) * jnp.uint32(br))
    col = (jax.lax.broadcasted_iota(jnp.uint32, (br, bc), 1)
           + jnp.uint32(col_offset) + pj.astype(jnp.uint32) * jnp.uint32(bc))
    u = uniform01(hash_u32(seed_ref[0], row, col, _TAG_Q))

    x = x_ref[...].astype(jnp.float32)
    norm = norm_ref[0]
    scaled = jnp.abs(x) / norm * jnp.float32(levels)
    floor = jnp.floor(scaled)
    level = floor + (u < (scaled - floor)).astype(jnp.float32)
    q = norm * jnp.sign(x) * level / jnp.float32(levels)
    o_ref[...] = q.astype(o_ref.dtype)


def qsgd_kernel_call(
    x2d: jax.Array,
    seed,
    leaf_tag: int,
    bits: int = 8,
    block: tuple = DEFAULT_BLOCK,
    row_offset: int = 0,
    col_offset: int = 0,
    interpret: bool | None = None,
) -> jax.Array:
    rows, cols = x2d.shape
    br, bc = block
    assert rows % br == 0 and cols % bc == 0, (x2d.shape, block)
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    if interpret:
        interpret = pltpu.InterpretParams()
    levels = (1 << (bits - 1)) - 1
    norm = jnp.linalg.norm(x2d.astype(jnp.float32).reshape(-1))
    norm = jnp.where(norm == 0, 1.0, norm).reshape(1)
    seed_folded = fold_seed(seed, leaf_tag).reshape(1)

    kern = functools.partial(_qsgd_kernel, levels=levels, block=block,
                             row_offset=row_offset, col_offset=col_offset)
    return pl.pallas_call(
        kern,
        grid=(rows // br, cols // bc),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((br, bc), lambda i, j: (i, j)),
        ],
        out_specs=pl.BlockSpec((br, bc), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((rows, cols), x2d.dtype),
        interpret=interpret,
    )(seed_folded, norm, x2d)
