"""Pallas TPU kernel: fused seeded projection  rⱼ = ⟨x, vⱼ(ξ)⟩, j < k.

The client-side hot loop of FedScalar at large d.  A naive
implementation streams both δ (d floats) **and** a materialized v
(d floats) from HBM — 2d·4 bytes for 2d FLOPs, arithmetic intensity
0.25.  This kernel regenerates each VMEM tile of v from
``(seed, row, col)`` with the SplitMix32 chain (~20 int ops/element,
all VPU) and fuses generate → multiply → reduce, so HBM traffic is just
δ itself: half the memory-bound lower bound, and v never exists as a
tensor anywhere.

Grid: 3-D — **block index × (row-blocks, col-blocks)** of the operand
viewed as a matrix (leading dims flattened to rows).  The k-block-
scalar upload (DESIGN.md §6) makes the projection ordinal a real grid
dimension: block j uses its own per-block seed and, in BLOCK mode, a
flat-index mask restricting it to its contiguous slice of the leaf, so
one compiled kernel emits all k scalars of ``r ∈ ℝᵏ`` in a single
sweep over δ.  TPU grid iteration is sequential, so block j's (8, bc)
float32 output tile accumulates sublane partial sums across its (i, j)
steps, and the wrapper reduces those 8·bc partials to the scalar.

``row_offset``/``col_offset`` shift the global coordinates so a shard
of a model-parallel leaf projects exactly its slice — composition with
shard_map needs no other change.  They are **runtime** scalars (read
from SMEM, not baked into the grid), so a single compiled kernel serves
every shard of a mesh: inside ``shard_map`` the offset is derived from
``jax.lax.axis_index`` and per-block seeds stay identical under any
shard layout.  ``k=1`` lowers to exactly the pre-block kernel body (no
mask is applied), keeping the paper path bit-identical.

Shapes/dtypes: x2d is a block-aligned float matrix; per-block seeds are
uint32 ``(k,)``; block bounds are leaf-local flat indices as float32
``(k,)`` (exact below 2²⁴ elements per leaf — the jnp BLOCK path has
the same float-mask domain); output is float32 ``(k,)``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import (
    flat_index,
    fold_seed,
    row_state,
    tile_from_state,
)

__all__ = ["projection_kernel_call", "projection_blocks_kernel_call",
           "DEFAULT_BLOCK"]

DEFAULT_BLOCK = (256, 512)


def _sublane_partial(t):
    """(br, bc) → (8, bc) float32 partial sums: whole-vreg adds only, so
    the accumulator keeps the (8, 128) tiling Mosaic can store."""
    br, bc = t.shape
    return jnp.sum(t.reshape(br // 8, 8, bc), axis=0)


def _proj_kernel(seeds_ref, lo_ref, hi_ref, offs_ref, x_ref, o_ref, *,
                 distribution: str, block: tuple, masked: bool,
                 orig_cols: int):
    pb = pl.program_id(0)
    pi = pl.program_id(1)
    pj = pl.program_id(2)
    br, bc = block
    seed_folded = seeds_ref[pb]
    row_offset = offs_ref[0]
    col_offset = offs_ref[1]

    # Factored direction chain (common.row_state/tile_from_state): the
    # first two SplitMix32 rounds run once per row on a (br, 1) column,
    # the per-element round on broadcast against a (1, bc) col vector —
    # values bit-identical to the old full-tile gen_tile, one mixer
    # round per element instead of three (shared with the fused
    # reconstruct+apply megakernel, DESIGN §11).
    row = (jax.lax.broadcasted_iota(jnp.uint32, (br, 1), 0)
           + row_offset + pi.astype(jnp.uint32) * jnp.uint32(br))
    col = (jax.lax.broadcasted_iota(jnp.uint32, (1, bc), 1)
           + col_offset + pj.astype(jnp.uint32) * jnp.uint32(bc))
    st = row_state(seed_folded, row, distribution)

    # Block b's (8, bc) output tile stays resident over its (i, j) sweep
    # and accumulates sublane partials; the caller reduces it.
    @pl.when(jnp.logical_and(pi == 0, pj == 0))
    def _init():
        o_ref[...] = jnp.zeros((8, bc), jnp.float32)

    if not masked:
        # Paper k=1 path and FULL-mode multi-projections: every scalar
        # spans the whole leaf — no mask multiply (bit-identical k=1,
        # and no float32 flat-index domain limit).
        v = tile_from_state(st, col, distribution)
        o_ref[...] += _sublane_partial(x_ref[...].astype(jnp.float32) * v)
    else:
        # Skip (tile, block) pairs with provably empty intersection —
        # blocks partition the flat index space, so each tile overlaps
        # only ~1-2 of the k blocks and the rest cost one comparison.
        r0 = (row_offset.astype(jnp.int32).astype(jnp.float32)
              + pi.astype(jnp.float32) * jnp.float32(br))
        tile_lo = r0 * jnp.float32(orig_cols)
        tile_hi = (r0 + jnp.float32(br - 1) + 1.0) * jnp.float32(orig_cols)
        overlap = jnp.logical_and(tile_lo < hi_ref[pb], tile_hi > lo_ref[pb])

        @pl.when(overlap)
        def _():
            v = tile_from_state(st, col, distribution)
            flat = flat_index(row, col, orig_cols)
            mask = jnp.logical_and(flat >= lo_ref[pb], flat < hi_ref[pb])
            o_ref[...] += _sublane_partial(
                x_ref[...].astype(jnp.float32) * v * mask.astype(jnp.float32))


def projection_blocks_kernel_call(
    x2d: jax.Array,
    seeds: jax.Array,          # (k,) per-block projection seeds (pre-leaf-fold)
    leaf_tag: int,
    lo: jax.Array,             # (k,) leaf-local flat lower bounds (float32)
    hi: jax.Array,             # (k,) leaf-local flat upper bounds (float32)
    distribution: str = "rademacher",
    block: tuple = DEFAULT_BLOCK,
    row_offset=0,
    col_offset=0,
    orig_cols: int | None = None,
    interpret: bool | None = None,
    masked: bool | None = None,
) -> jax.Array:
    """→ float32 ``(k,)`` block scalars ⟨x2d·𝟙[block j], vⱼ⟩.

    x2d must be 2-D and block-aligned (ops.py handles padding/reshape
    for arbitrary leaves; zero padding is exact).  Padded tail elements
    may fall outside every block's bounds — they carry x = 0 either
    way, so masking them in or out is exact.  ``masked=False`` (FULL
    mode: every projection spans the whole leaf) skips the flat-index
    mask entirely; the lo/hi bounds are then ignored.
    ``row_offset``/``col_offset`` may be Python ints or traced uint32
    scalars (the shard_map path passes ``axis_index``-derived offsets).
    """
    rows, cols = x2d.shape
    br, bc = block
    assert rows % br == 0 and cols % bc == 0 and br % 8 == 0, (x2d.shape,
                                                               block)
    k = seeds.shape[0]
    if masked is None:
        masked = k > 1
    if orig_cols is None:
        orig_cols = cols
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    if interpret:
        interpret = pltpu.InterpretParams()
    seeds_folded = jax.vmap(lambda s: fold_seed(s, leaf_tag))(seeds)
    offs = jnp.stack([jnp.asarray(row_offset, jnp.uint32),
                      jnp.asarray(col_offset, jnp.uint32)])

    kern = functools.partial(
        _proj_kernel, distribution=distribution, block=block, masked=masked,
        orig_cols=orig_cols)
    out = pl.pallas_call(
        kern,
        grid=(k, rows // br, cols // bc),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((br, bc), lambda b, i, j: (i, j)),
        ],
        out_specs=pl.BlockSpec((8, bc), lambda b, i, j: (b, 0)),
        out_shape=jax.ShapeDtypeStruct((k * 8, bc), jnp.float32),
        interpret=interpret,
    )(seeds_folded, jnp.asarray(lo, jnp.float32), jnp.asarray(hi, jnp.float32),
      offs, x2d)
    return jnp.sum(out.reshape(k, 8 * bc), axis=1)


def projection_kernel_call(
    x2d: jax.Array,
    seed,
    leaf_tag: int,
    distribution: str = "rademacher",
    block: tuple = DEFAULT_BLOCK,
    row_offset=0,
    col_offset=0,
    interpret: bool | None = None,
) -> jax.Array:
    """→ float32 scalar ⟨x2d, v⟩ — the k=1 face of the block kernel."""
    size = float(x2d.shape[0]) * float(x2d.shape[1])
    out = projection_blocks_kernel_call(
        x2d, jnp.asarray(seed, jnp.uint32).reshape(1), leaf_tag,
        jnp.zeros((1,), jnp.float32), jnp.full((1,), size, jnp.float32),
        distribution, block, row_offset, col_offset, interpret=interpret)
    return out[0]
