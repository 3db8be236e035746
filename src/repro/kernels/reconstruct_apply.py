"""Fused reconstruct+apply megakernel: y = x + s·Σₙⱼ rₙⱼ·vₙⱼ(ξₙ), chunked.

One pass over the model state folds the whole server-side round close
(DESIGN §11): per-client per-block directions regenerated from the
32-bit round seeds, Wiener block weights and Horvitz–Thompson
coefficients pre-folded into the ``(N, k)`` scalars (``ops.
fold_upload_weights``), and the aggregated update applied to x — with
no ``(cohort, d)`` intermediate anywhere.  It differs from the original
``seeded_reconstruct`` kernel in its **accumulation contract**, and the
contract is the whole point:

    rs ← scale · rs                            # folded once, on the host
    pad N to a multiple of FUSED_CHUNK (zero seeds, zero scalars);
    for block b = 0..k-1:                      # sequential
      for chunk c = 0..N/cb-1:                 # sequential
        acc += fold( rs[c·cb+i, b] · v_i · mask_b  for i < cb )
    y = x + acc                                # float32 acc throughout

where ``fold`` is the left fold c₀ + c₁ + … + c_{cb-1} (``fold_chunk``).

The scale is folded into the scalars *before* the sum, not applied to
the accumulator after it, deliberately: a trailing ``x + scale·acc``
is a mul+add the compiler may (or may not) contract into an FMA, which
makes the output bits lowering-dependent — the Pallas interpreter and
the XLA-jitted mirror disagreed on exactly that contraction.  A bare
``x + acc`` add is one correctly-rounded op everywhere.

The per-chunk fold over the cb=FUSED_CHUNK client axis is elementwise
across the tile: each chunk's products are generated batched and
materialized, then folded — which breaks the loop-carried add chain of
the per-client fori kernel.  The fold's association is written out as
explicit adds, so no lowering (XLA on CPU or TPU, Mosaic, the
interpreter) can pick its own.  The price: chunk partials added into
the accumulator are a different float association than the original
kernel's strictly sequential per-client adds, so the fused path is
**its own numeric spec** — bit-identical across the Pallas kernel, the
jnp mirror below and the independent ``ref.server_update_fused_ref`` oracle (asserted in
``tests/test_kernel_differential.py``), and allclose (not bitwise) to
the legacy fori/kernel paths.

FUSED_CHUNK is a **numerics constant, not a tuning knob**: the chunk
length fixes the reduction tree, so changing it changes output bits.
The autotuner (``kernels/tune.py``) only sweeps parameters that cannot
move bits — Pallas (br, bc) tile shapes and the mirror's row-slab
height — because every element's value is a pure function of its global
(row, col) and the chunk partials are elementwise (verified: the
chunk fold is bitwise invariant to spatial tiling).

Generation uses the factored direction chain (``common.row_state`` /
``tile_from_state``): stages 1–2 of the SplitMix32 chain are hoisted
per (client, row), leaving one mixer round per element.  The projection
kernel shares the same factored generator, so uplink encode and
downlink decode literally run one generator (DESIGN §11).

Orientation (``fused_plan``).  The Pallas kernel tiles a leaf in one of
two orientations, chosen from its shape.  Where the rows are a multiple
of 128 and the columns of 8 — every large matrix of a transformer — it
runs over tiles of xᵀ: the leaf's rows lie along the vector lanes and
its columns along the sublanes.  The hoisted row state is then a
``(1, tr)`` vector that every sublane shares, where the natural
orientation's ``(tr, 1)`` column costs one lane-replicated vreg per 8
rows, and the column tile is a multiple of 8 that divides the columns,
so a 960- or 320-column leaf computes no pad columns.  Any other leaf
(vectors, short norms, the paper MLP) is tiled as it lies and padded to
its tile.  Orientation, pad and the transposes around the call live in
the Pallas dispatch; callers pass the unpadded 2-D view.  Values are a
function of (row, col) alone and the chunk fold is elementwise, so both
orientations give the same bits.

Shapes/dtypes: x2d is any 2-D float matrix; seeds are uint32 ``(N,)``
**round** seeds (unfolded); rs is float32 ``(N, k)`` with every
aggregation weight pre-folded; block bounds are leaf-local flat float32
``(k,)`` as in the other kernels.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.prng import PROJ_SALT
from repro.kernels.common import (
    flat_index,
    fold_seed,
    row_state,
    splitmix32,
    tile_from_state,
)

__all__ = ["fused_reconstruct_apply", "fused_plan", "FusedPlan",
           "FUSED_CHUNK", "DEFAULT_FUSED_BLOCK"]

# Clients regenerated per chunk partial.  Pinned: part of the numeric
# spec (see module docstring), NOT autotunable.
FUSED_CHUNK = 16

# Default Pallas tile budget: at most br rows by bc columns of the leaf
# per grid step (``fused_plan``).  Smaller than the two-kernel default
# because the kernel holds a float32 product scratch of FUSED_CHUNK
# tiles in VMEM: 16·128·256·4 B = 2 MiB, under budget with x, acc and y.
DEFAULT_FUSED_BLOCK = (128, 256)


def _pad_cohort(seeds: jax.Array, rs: jax.Array):
    """Zero-pad (seeds, rs) to a FUSED_CHUNK multiple (exact no-ops)."""
    n, k = rs.shape
    pad = (-n) % FUSED_CHUNK
    if pad:
        seeds = jnp.concatenate([seeds, jnp.zeros((pad,), seeds.dtype)])
        rs = jnp.concatenate([rs, jnp.zeros((pad, k), jnp.float32)])
    return seeds, rs, (n + pad) // FUSED_CHUNK


def fold_chunk(term, n: int):
    """The spec's chunk reduction: the left fold t₀ + t₁ + … + t_{n-1}
    of ``term(i)``, one add per step.

    A loop of single adds leaves no lowering (XLA on CPU or TPU, Mosaic,
    the interpreter) room to pick its own association, as a reduce
    would: XLA's jitted ``sum`` over the chunk axis does not add in this
    order.  The Pallas kernel and the mirror both call this; the eager
    oracle spells the same adds on its own.
    """
    return jax.lax.fori_loop(1, n, lambda i, t: t + term(i), term(0))


def _chunk_partial(folded, rr, row, col, distribution, mask):
    """sum over the chunk axis of rₙ·vₙ(·mask) — the spec's inner term.

    ``folded``/``rr`` carry the chunk axis; ``row``/``col``/``mask``
    broadcast over it.  The contribution is computed exactly as the
    oracle writes it — (r · v) · mask, v from the shared chain — and
    reduced by :func:`fold_chunk`, so equality with
    ``ref.server_update_fused_ref`` is bitwise.

    The optimization barrier pins the spec's "materialize products,
    then reduce" order in compiled lowerings: without it a fusion
    context (jit) may contract the multiply into the reduction's adds
    as FMAs — which moves bits exactly for the one family whose
    products round (gaussian; ±1/±2-valued families have exact products
    and cannot tell).  The eager oracle materializes the product array
    by construction; the Pallas kernel stores every product to a VMEM
    scratch before it reduces.  Generation is the other
    context-sensitive piece (see the mirror's chunk loop).
    """
    st = row_state(folded, row, distribution)
    v = tile_from_state(st, col, distribution)
    contrib = rr * v
    if mask is not None:
        contrib = contrib * mask
    contrib = jax.lax.optimization_barrier(contrib)
    return fold_chunk(lambda i: contrib[i], contrib.shape[0])


# ---------------------------------------------------------------------------
# Pallas megakernel
# ---------------------------------------------------------------------------


class FusedPlan(NamedTuple):
    """How the Pallas kernel tiles one ``(rows, cols)`` leaf.

    ``lanes_rows``: the kernel works on tiles of xᵀ, the leaf's rows
    along lanes and its columns along sublanes; otherwise it tiles x as
    it lies.  ``tile`` is the ``(rows, cols)`` of the leaf one grid step
    covers, in either orientation.  ``pad`` is the elements the kernel
    computes and throws away, per client.
    """
    lanes_rows: bool
    tile: tuple
    pad: int


def _largest_tile(n: int, step: int, cap: int) -> int:
    """Largest multiple of ``step`` that divides ``n`` and is ≤ ``cap``
    (``step`` itself at least; ``n`` is a multiple of it)."""
    for t in range(max(cap, step) // step * step, step, -step):
        if n % t == 0:
            return t
    return step


def fused_plan(rows: int, cols: int,
               block: tuple = DEFAULT_FUSED_BLOCK) -> FusedPlan:
    """The Pallas tiling of a ``(rows, cols)`` leaf under the ``block``
    budget ``(br, bc)`` (at most br rows by bc columns per tile).

    Where the rows tile the lanes (rows % 128 == 0) and the columns the
    sublanes (cols % 8 == 0), the kernel puts rows along lanes: the
    hoisted per-row state is then a ``(1, tr)`` vector shared by every
    sublane, and the column tile is a multiple of 8 that divides
    ``cols``, so nothing is padded.  Any other leaf (``(1, n)`` vectors,
    short matrices) is tiled as it lies and padded up to its tile.
    """
    br, bc = block
    if rows % 128 == 0 and cols % 8 == 0:
        return FusedPlan(True, (_largest_tile(rows, 128, br),
                                _largest_tile(cols, 8, bc)), 0)
    tr = min(br, -(-rows // 8) * 8)
    tc = min(bc, -(-cols // 128) * 128)
    pad = -(-rows // tr) * tr * (-(-cols // tc) * tc) - rows * cols
    return FusedPlan(False, (tr, tc), pad)


def _fused_kernel(seeds_ref, rs_ref, lo_ref, hi_ref, offs_ref,
                  x_ref, o_ref, acc_ref, prod_ref, *, distribution: str,
                  num_chunks: int, num_blocks: int, masked: bool,
                  tile: tuple, lanes_rows: bool, leaf_tag: int,
                  orig_cols: int, padded_cohort: int):
    pi = pl.program_id(0)
    pj = pl.program_id(1)
    pb = pl.program_id(2)
    pc = pl.program_id(3)
    tr, tc = tile
    row_offset = offs_ref[0]
    col_offset = offs_ref[1]
    # Coordinate vectors that broadcast to the kernel's tile: the
    # factored chain touches rows only until the last mixer round, so
    # its stage 2 runs on the row vector alone.  Rows along lanes make
    # that vector (1, tr), one sublane-broadcast row of vregs; as the
    # leaf lies it is a (tr, 1) column, lane-replicated and 8× dearer.
    iota = functools.partial(jax.lax.broadcasted_iota, jnp.uint32)
    if lanes_rows:
        row, col = iota((1, tr), 1), iota((tc, 1), 0)
    else:
        row, col = iota((tr, 1), 0), iota((1, tc), 1)
    row = row + row_offset + pi.astype(jnp.uint32) * jnp.uint32(tr)
    col = col + col_offset + pj.astype(jnp.uint32) * jnp.uint32(tc)

    @pl.when(jnp.logical_and(pb == 0, pc == 0))
    def _():
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    base = pc * FUSED_CHUNK
    salt = jnp.uint32(PROJ_SALT) + pb.astype(jnp.uint32)

    def chunk_sum(mask):
        # Materialize the chunk's (cb, ·, ·) products in VMEM, then
        # fold them: the store/load boundary is what keeps the multiply
        # out of the adds (the mirror's optimization barrier), and the
        # fold is the spec's own association (``fold_chunk``).
        rs_base = pb * padded_cohort + base     # rs is flat block-major

        def product(i, carry):
            folded = fold_seed(splitmix32(seeds_ref[base + i] ^ salt),
                               leaf_tag)
            v = tile_from_state(row_state(folded, row, distribution), col,  # fedlint: allow[FS004] kernel body IS the pinned numeric spec; interpret mode is pinned bitwise vs the mirror, the chip vs the oracle (DESIGN §11)
                                distribution)
            contrib = rs_ref[rs_base + i] * v
            if mask is not None:
                contrib = contrib * mask
            prod_ref[i] = contrib
            return carry

        jax.lax.fori_loop(0, FUSED_CHUNK, product, 0)
        acc_ref[...] += fold_chunk(lambda i: prod_ref[i], FUSED_CHUNK)

    if not masked:
        chunk_sum(None)
    else:
        # Same provably-empty-intersection skip as the two-kernel path,
        # over the flat range of the tile's tr rows.
        r0 = (row_offset.astype(jnp.int32).astype(jnp.float32)
              + pi.astype(jnp.float32) * jnp.float32(tr))
        tile_lo = r0 * jnp.float32(orig_cols)
        tile_hi = (r0 + jnp.float32(tr - 1) + 1.0) * jnp.float32(orig_cols)
        overlap = jnp.logical_and(tile_lo < hi_ref[pb], tile_hi > lo_ref[pb])

        @pl.when(overlap)
        def _():
            flat = flat_index(row, col, orig_cols)
            mask = jnp.logical_and(flat >= lo_ref[pb], flat < hi_ref[pb])
            chunk_sum(mask.astype(jnp.float32))

    @pl.when(jnp.logical_and(pb == num_blocks - 1, pc == num_chunks - 1))
    def _():
        y = x_ref[...].astype(jnp.float32) + acc_ref[...]
        o_ref[...] = y.astype(o_ref.dtype)


def _fused_pallas(x2d, seeds, rs, leaf_tag, distribution, block,
                  row_offset, col_offset, lo, hi, orig_cols, masked,
                  interpret):
    """The kernel over one leaf: orientation, pad and slice from
    :func:`fused_plan`, so callers pass the leaf as it is."""
    rows, cols = x2d.shape
    plan = fused_plan(rows, cols, block)
    tr, tc = plan.tile
    if plan.lanes_rows:
        xk, kblock = x2d.T, (tc, tr)
    else:
        pr, pc = (-rows) % tr, (-cols) % tc
        xk = jnp.pad(x2d, ((0, pr), (0, pc))) if pr or pc else x2d
        kblock = (tr, tc)

    def index(i, j, b, c):      # grid (row tile, col tile, block, chunk)
        return (j, i) if plan.lanes_rows else (i, j)

    k = rs.shape[1]
    seeds, rs, num_chunks = _pad_cohort(seeds, rs)
    padded_cohort = num_chunks * FUSED_CHUNK
    offs = jnp.stack([jnp.asarray(row_offset, jnp.uint32),
                      jnp.asarray(col_offset, jnp.uint32)])
    kern = functools.partial(
        _fused_kernel, distribution=distribution, num_chunks=num_chunks,
        num_blocks=k, masked=masked, tile=plan.tile,
        lanes_rows=plan.lanes_rows, leaf_tag=leaf_tag, orig_cols=orig_cols,
        padded_cohort=padded_cohort)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    y = pl.pallas_call(
        kern,
        grid=(-(-rows // tr), -(-cols // tc), k, num_chunks),
        in_specs=[smem, smem, smem, smem, smem, pl.BlockSpec(kblock, index)],
        out_specs=pl.BlockSpec(kblock, index),
        out_shape=jax.ShapeDtypeStruct(xk.shape, x2d.dtype),
        scratch_shapes=[pltpu.VMEM(kblock, jnp.float32),
                        pltpu.VMEM((FUSED_CHUNK,) + kblock, jnp.float32)],
        interpret=interpret,
        # The compiled custom call takes this name under any enclosing
        # jit or named scope; the benchmark finds the kernel by it.
        name="apply_fused",
    )(seeds, rs.T.reshape(-1), lo, hi, offs, xk)
    return y.T if plan.lanes_rows else y[:rows, :cols]


# ---------------------------------------------------------------------------
# jnp mirror — the CPU fast path, same spec to the bit
# ---------------------------------------------------------------------------


def _mirror_span(x2d, folded, rs, distribution, rowg, colg, lo, hi,
                 orig_cols, masked, num_chunks):
    """Apply the fused spec to one row span of the matrix."""
    rows, cols = x2d.shape
    n, k = rs.shape
    row3 = rowg[None, :, None]
    col3 = colg[None, None, :]
    acc = jnp.zeros((rows, cols), jnp.float32)
    if masked:
        flat = flat_index(rowg[:, None], colg[None, :], orig_cols)
    for b in range(k):
        mask = None
        if masked:
            mask = jnp.logical_and(flat >= lo[b], flat < hi[b]) \
                .astype(jnp.float32)[None]
        fb = folded[:, b]

        # Static Python loop, NOT fori_loop: a compiled loop body is a
        # fusion context, and XLA's fused transcendentals (gaussian's
        # log/cos) are vectorized differently there than as standalone
        # per-primitive programs — bits move on lane-remainder shapes.
        # Eagerly executed, every chunk runs the same canonical per-op
        # kernels the oracle uses, so eager mirror ≡ eager oracle holds
        # for all families on all shapes.  num_chunks is static; under
        # an enclosing jit the loop unrolls (≤ cohort/16 bodies).
        for c in range(num_chunks):
            sf = fb[c * FUSED_CHUNK:(c + 1) * FUSED_CHUNK]
            rr = rs[c * FUSED_CHUNK:(c + 1) * FUSED_CHUNK, b]
            acc = acc + _chunk_partial(
                sf[:, None, None], rr[:, None, None], row3, col3,
                distribution, mask)
    return (x2d.astype(jnp.float32) + acc).astype(x2d.dtype)


def _fused_mirror(x2d, seeds, rs, leaf_tag, distribution, row_offset,
                  col_offset, lo, hi, orig_cols, masked, row_slab):
    rows, cols = x2d.shape
    n, k = rs.shape
    seeds, rs, num_chunks = _pad_cohort(seeds, rs)
    # (N, k) folded seeds: the same in-kernel derivation, batched.
    salts = jnp.uint32(PROJ_SALT) + jnp.arange(k, dtype=jnp.uint32)
    folded = fold_seed(splitmix32(seeds[:, None] ^ salts[None, :]), leaf_tag)
    ro = jnp.asarray(row_offset, jnp.uint32)
    co = jnp.asarray(col_offset, jnp.uint32)
    colg = jnp.arange(cols, dtype=jnp.uint32) + co

    def span(x_span, r0: int):
        rowg = (jnp.arange(x_span.shape[0], dtype=jnp.uint32)
                + ro + jnp.uint32(r0))
        return _mirror_span(
            x_span, folded, rs, distribution, rowg, colg, lo, hi,
            orig_cols, masked, num_chunks)

    # The row-slab height is a spatial partition only — per-element
    # values and the chunk-axis reduction are unchanged (bits cannot
    # move); it exists as the mirror's cache-locality tuning knob.
    if row_slab is None or row_slab >= rows:
        return span(x2d, 0)
    parts = [span(x2d[r0:min(r0 + row_slab, rows)], r0)
             for r0 in range(0, rows, row_slab)]
    return jnp.concatenate(parts, axis=0)


# ---------------------------------------------------------------------------
# dispatch
# ---------------------------------------------------------------------------


def fused_reconstruct_apply(
    x2d: jax.Array,
    seeds: jax.Array,          # (N,) uint32 round seeds (unfolded)
    rs: jax.Array,             # (N,) or (N, k) float32 scalars (0 = padding)
    leaf_tag: int,
    scale,                     # pre-folded (ops.fold_upload_weights)
    distribution: str = "rademacher",
    block: tuple = DEFAULT_FUSED_BLOCK,
    row_offset=0,
    col_offset=0,
    lo: jax.Array | None = None,
    hi: jax.Array | None = None,
    orig_cols: int | None = None,
    masked: bool | None = None,
    use_pallas: bool | None = None,
    interpret: bool | None = None,
    row_slab: int | None = None,
) -> jax.Array:
    """→ x + scale·Σₙⱼ rₙⱼ vₙⱼ in one fused pass (shape/dtype of x2d).

    ``use_pallas=None`` dispatches by backend: the Pallas megakernel on
    TPU, the jnp mirror elsewhere (CPU interpret mode executes the
    kernel orders of magnitude too slowly to be a serving path — the
    mirror lowers the *same* chunked spec through XLA directly, so the
    two are bit-identical and the differential suite pins both).
    ``block`` (Pallas) and ``row_slab`` (mirror) are the autotunable,
    bits-invariant performance knobs; FUSED_CHUNK is not one.  ``x2d``
    may have any shape: the Pallas path tiles it by :func:`fused_plan`.

    ``row_offset``/``col_offset`` may be Python ints or traced uint32
    scalars — the mesh-sharded server passes ``shard_ordinal``-derived
    offsets, preserving the runtime-SMEM-offset contract of the
    two-kernel path (DESIGN §7).
    """
    rs = jnp.asarray(rs, jnp.float32)
    if rs.ndim == 1:
        rs = rs[:, None]
    # Fold the scale into the scalars (spec line 1): the final apply is
    # then a bare add, immune to FMA-contraction differences between
    # lowerings (see module docstring).
    rs = rs * jnp.asarray(scale, jnp.float32)
    n, k = rs.shape
    seeds = jnp.asarray(seeds, jnp.uint32)
    assert seeds.shape == (n,), (seeds.shape, rs.shape)
    if masked is None:
        masked = k > 1
    rows, cols = x2d.shape
    if lo is None or hi is None:
        assert not masked, "masked k-block calls must pass leaf-local lo/hi"
        lo = jnp.zeros((k,), jnp.float32)
        hi = jnp.full((k,), float(rows) * float(cols), jnp.float32)
    lo = jnp.asarray(lo, jnp.float32)
    hi = jnp.asarray(hi, jnp.float32)
    if orig_cols is None:
        orig_cols = cols
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu"
    if not use_pallas:
        return _fused_mirror(x2d, seeds, rs, leaf_tag, distribution,
                             row_offset, col_offset, lo, hi, orig_cols,
                             masked, row_slab)
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    if interpret:
        interpret = pltpu.InterpretParams()
    return _fused_pallas(x2d, seeds, rs, leaf_tag, distribution, block,
                         row_offset, col_offset, lo, hi, orig_cols, masked,
                         interpret)
