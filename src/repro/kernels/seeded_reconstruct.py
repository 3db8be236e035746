"""Pallas TPU kernel: fused seeded reconstruction  y = x + s·Σₙⱼ rₙⱼ·vₙⱼ(ξₙ).

The server-side hot loop (Algorithm 1 lines 8–13) for all N cohort
members at once, fused with the global-model update.  A naive server
materializes each vₙ (N·d floats of HBM traffic plus N·d of writes);
this kernel streams the params once and regenerates every vₙ tile
in-register:

    HBM traffic:  read x (d) + write y (d)           — independent of N
    compute:      N·k hash-chains + FMA per element  — VPU-bound
    cohort state: N (r ∈ ℝᵏ, ξ) pairs in SMEM        — O(k) per client

which is the paper's "upload two scalars" insight transplanted to the
memory system: reconstruction cost no longer scales with N in bytes,
only in (cheap, hidable) integer ops.

Grid: 4-D — tiles of the parameter matrix × **block index** × **client
chunks** (DESIGN.md §6/§2).  The k-block-scalar upload makes the block
ordinal a grid dimension: step (i, j, b, c) regenerates block b's
direction for client chunk c over tile (i, j), masks it to block b's
flat-index slice, and FMAs ``rₙ,b``.  The cohort axis stays a real grid
dimension, not a static unroll, so one compiled kernel serves any
cohort size (the federation runtime pads the (r, ξ) buffers to a chunk
multiple; padded slots carry r = 0 and are exact no-ops).  Per-block
seeds are derived **in-kernel** from the round seed (the same
SplitMix32 fold the jnp path uses), so SMEM holds one uint32 per
client regardless of k.  Partial sums live in a float32 VMEM
accumulator that persists across the (sequential) (b, c) iterations of
each tile, so low-precision param dtypes never see intermediate
rounding.  ``num_blocks=1`` skips the mask multiply entirely — the
paper path lowers to exactly the pre-block kernel body.

Shapes/dtypes: x2d is a block-aligned float matrix; seeds are uint32
``(N,)`` **round** seeds (unfolded); rs is float32 ``(N, k)`` with all
aggregation/block weights pre-folded by the caller; block bounds are
leaf-local flat indices as float32 ``(k,)`` (exact below 2²⁴ elements
per leaf, like the jnp BLOCK mask).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.prng import PROJ_SALT
from repro.kernels.common import (
    flat_index,
    fold_seed,
    gen_tile,
    splitmix32,
)

__all__ = ["reconstruct_kernel_call", "CLIENT_CHUNK"]

DEFAULT_BLOCK = (256, 512)
CLIENT_CHUNK = 32     # cohort members regenerated per grid step

# Per-projection seed salt — single source: repro.core.prng.
_PROJ_SALT = PROJ_SALT


def _rec_kernel(seeds_ref, rs_ref, scale_ref, lo_ref, hi_ref, offs_ref, x_ref,
                o_ref, acc_ref, *, distribution: str, chunk: int,
                num_chunks: int, num_blocks: int, masked: bool, block: tuple,
                leaf_tag: int, orig_cols: int):
    pi = pl.program_id(0)
    pj = pl.program_id(1)
    pb = pl.program_id(2)
    pc = pl.program_id(3)
    br, bc = block
    row_offset = offs_ref[0]
    col_offset = offs_ref[1]
    row = (jax.lax.broadcasted_iota(jnp.uint32, (br, bc), 0)
           + row_offset + pi.astype(jnp.uint32) * jnp.uint32(br))
    col = (jax.lax.broadcasted_iota(jnp.uint32, (br, bc), 1)
           + col_offset + pj.astype(jnp.uint32) * jnp.uint32(bc))

    @pl.when(jnp.logical_and(pb == 0, pc == 0))
    def _():
        acc_ref[...] = jnp.zeros((br, bc), jnp.float32)

    base = pc * chunk
    salt = jnp.uint32(_PROJ_SALT) + pb.astype(jnp.uint32)

    def chunk_sum(mask):
        def body(i, acc):
            seed_b = splitmix32(seeds_ref[base + i] ^ salt)
            v = gen_tile(fold_seed(seed_b, leaf_tag), row, col, distribution)  # fedlint: allow[FS004] kernel body IS the pinned numeric spec; conformance tests pin it bitwise vs the eager oracle
            if mask is not None:
                v = v * mask
            return acc + rs_ref[base + i, pb] * v  # fedlint: allow[FS004] accumulation order fixed by the loop; bitwise-pinned vs oracle

        acc_ref[...] = jax.lax.fori_loop(0, chunk, body, acc_ref[...])

    if not masked:
        # Paper k=1 path and FULL-mode multi-projections span the whole
        # leaf: no mask, no float32 flat-index domain limit.
        chunk_sum(None)
    else:
        # Skip (tile, block) combos with provably empty intersection —
        # blocks partition the flat index space, so each tile overlaps
        # only ~1-2 of the k blocks; the other grid steps cost one
        # comparison instead of a chunk of hash-chains.
        r0 = (row_offset.astype(jnp.int32).astype(jnp.float32)
              + pi.astype(jnp.float32) * jnp.float32(br))
        tile_lo = r0 * jnp.float32(orig_cols)
        tile_hi = (r0 + jnp.float32(br - 1) + 1.0) * jnp.float32(orig_cols)
        overlap = jnp.logical_and(tile_lo < hi_ref[pb], tile_hi > lo_ref[pb])

        @pl.when(overlap)
        def _():
            flat = flat_index(row, col, orig_cols)
            mask = jnp.logical_and(flat >= lo_ref[pb], flat < hi_ref[pb])
            chunk_sum(mask.astype(jnp.float32))

    @pl.when(jnp.logical_and(pb == num_blocks - 1, pc == num_chunks - 1))
    def _():
        y = x_ref[...].astype(jnp.float32) + scale_ref[0] * acc_ref[...]
        o_ref[...] = y.astype(o_ref.dtype)


def reconstruct_kernel_call(
    x2d: jax.Array,
    seeds: jax.Array,          # (N,) uint32 round seeds (unfolded)
    rs: jax.Array,             # (N,) or (N, k) float32 scalars (0 = padding)
    leaf_tag: int,
    scale,                     # server_lr / N  (or 1 with pre-weighted rs)
    distribution: str = "rademacher",
    block: tuple = DEFAULT_BLOCK,
    row_offset=0,
    col_offset=0,
    interpret: bool | None = None,
    client_chunk: int = CLIENT_CHUNK,
    lo: jax.Array | None = None,   # (k,) leaf-local flat bounds (float32)
    hi: jax.Array | None = None,
    orig_cols: int | None = None,
    masked: bool | None = None,
) -> jax.Array:
    """→ updated params tile  x + scale·Σₙⱼ rₙⱼ vₙⱼ  (shape/dtype of x2d).

    With 1-D ``rs`` (or ``lo``/``hi`` omitted) this is the paper's
    single-scalar update; 2-D ``rs`` of width k runs the k-block-scalar
    decode with block index joining the grid.  ``masked=False`` (FULL
    mode: every projection spans the whole leaf) skips the flat-index
    mask; the lo/hi bounds are then ignored.  ``row_offset``/
    ``col_offset`` may be Python ints or traced uint32 scalars — the
    mesh-sharded server derives them from ``jax.lax.axis_index`` inside
    ``shard_map``, so one compiled kernel reconstructs any shard's
    slice of the direction chain (DESIGN §7).
    """
    rows, cols = x2d.shape
    br, bc = block
    assert rows % br == 0 and cols % bc == 0, (x2d.shape, block)
    rs = jnp.asarray(rs, jnp.float32)
    if rs.ndim == 1:
        rs = rs[:, None]
    n, k = rs.shape
    assert seeds.shape == (n,), (seeds.shape, rs.shape)
    if masked is None:
        masked = k > 1
    if lo is None or hi is None:
        assert not masked, "masked k-block calls must pass leaf-local lo/hi"
        lo = jnp.zeros((k,), jnp.float32)
        hi = jnp.full((k,), float(rows) * float(cols), jnp.float32)
    if orig_cols is None:
        orig_cols = cols
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    if interpret:
        interpret = pltpu.InterpretParams()
    chunk = min(client_chunk, n)
    pad = (-n) % chunk
    if pad:
        # Padding slots contribute rₙ·vₙ = 0·vₙ exactly.
        seeds = jnp.concatenate([seeds, jnp.zeros((pad,), seeds.dtype)])
        rs = jnp.concatenate([rs, jnp.zeros((pad, k), jnp.float32)])
    num_chunks = (n + pad) // chunk
    scale_arr = jnp.asarray(scale, jnp.float32).reshape(1)
    offs = jnp.stack([jnp.asarray(row_offset, jnp.uint32),
                      jnp.asarray(col_offset, jnp.uint32)])

    kern = functools.partial(
        _rec_kernel, distribution=distribution, chunk=chunk,
        num_chunks=num_chunks, num_blocks=k, masked=masked, block=block,
        leaf_tag=leaf_tag, orig_cols=orig_cols)
    return pl.pallas_call(
        kern,
        grid=(rows // br, cols // bc, k, num_chunks),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((br, bc), lambda i, j, b, c: (i, j)),
        ],
        out_specs=pl.BlockSpec((br, bc), lambda i, j, b, c: (i, j)),
        out_shape=jax.ShapeDtypeStruct((rows, cols), x2d.dtype),
        scratch_shapes=[pltpu.VMEM((br, bc), jnp.float32)],
        interpret=interpret,
    )(jnp.asarray(seeds, jnp.uint32), rs, scale_arr,
      jnp.asarray(lo, jnp.float32), jnp.asarray(hi, jnp.float32), offs, x2d)
