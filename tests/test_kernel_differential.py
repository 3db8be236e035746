"""Differential kernel sweep: Pallas ≡ kernels/ref.py, family × k × awkward d.

Property-style contracts (DESIGN §3/§6/§7):

* every registered direction family, every scalars-per-upload k, and the
  awkward dimension regimes — d smaller than one kernel tile, d not a
  multiple of tile·shards, k exceeding the number of tiles a leaf spans —
  agree with the pure-jnp oracles within float reduction order;
* the **offset parameter**: calling the kernels on row-slices of the
  operand with ``row_offset`` set (the mesh-shard composition) and
  concatenating the slices is **bit-identical** to the offset-0
  full-width call for reconstruction, and sums to the full projection
  within fp32 reassociation for the projection.

Kernels run in TPU interpret mode on CPU; the shapes are deliberately
tiny so the whole sweep stays in the fast test tier.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.directions import FAMILIES
from repro.core.projection import ProjectionMode, _proj_seed
from repro.kernels import ops, ref
from repro.kernels.reconstruct_apply import fused_plan, fused_reconstruct_apply
from repro.kernels.seeded_projection import projection_blocks_kernel_call
from repro.kernels.seeded_reconstruct import reconstruct_kernel_call

# d < one tile; d not a multiple of tile (or tile·shards); k > #tiles.
AWKWARD_SHAPES = [(17,), (100,), (3, 130), (40, 180)]
KS = [1, 3, 8]
# Fast-tier subset: one sub-tile shape + one tile-misaligned shape, k ≤ 3.
QUICK_SHAPES = [(17,), (3, 130)]
QUICK_KS = [1, 3]


def _tree(shape, seed):
    arr = np.random.RandomState(seed).randn(*shape)
    return {"x": jnp.asarray(arr, jnp.float32)}


def _projection_sweep(family, shapes, ks):
    dist = FAMILIES[family].distribution
    for si, shape in enumerate(shapes):
        tree = _tree(shape, si)
        d = int(np.prod(shape))
        for k in ks:
            mode = ProjectionMode.BLOCK if k > 1 else ProjectionMode.FULL
            rk = np.asarray(ops.project_tree_kernel(
                tree, 31 + si, dist, num_blocks=k, mode=mode))
            rr = np.asarray(ref.project_tree_ref(
                tree, 31 + si, dist, num_projections=k, mode=mode))
            assert rk.shape == (k,)
            np.testing.assert_allclose(
                rk, rr, rtol=1e-4, atol=1e-4 * max(d, 1),
                err_msg=f"{family} shape={shape} k={k}")


def _reconstruct_sweep(family, shapes, ks):
    dist = FAMILIES[family].distribution
    n = 3
    seeds = jnp.arange(n, dtype=jnp.uint32) + 11
    for si, shape in enumerate(shapes):
        tree = _tree(shape, 10 + si)
        for k in ks:
            mode = ProjectionMode.BLOCK if k > 1 else ProjectionMode.FULL
            rs = jnp.asarray(np.random.RandomState(k).randn(n, k), jnp.float32)
            uk = ops.server_update_kernel(tree, rs, seeds, 0.5, dist, mode=mode)
            ur = ref.server_update_ref(tree, rs, seeds, 0.5, dist,
                                       num_projections=k, mode=mode)
            np.testing.assert_allclose(
                np.asarray(uk["x"]), np.asarray(ur["x"]), rtol=1e-4, atol=1e-4,
                err_msg=f"{family} shape={shape} k={k}")


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_projection_differential_quick(family):
    _projection_sweep(family, QUICK_SHAPES, QUICK_KS)


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_reconstruct_differential_quick(family):
    _reconstruct_sweep(family, QUICK_SHAPES, QUICK_KS)


@pytest.mark.slow
@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_projection_differential_sweep(family):
    _projection_sweep(family, AWKWARD_SHAPES, KS)


@pytest.mark.slow
@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_reconstruct_differential_sweep(family):
    _reconstruct_sweep(family, AWKWARD_SHAPES, KS)


def _leaf_bounds_full(rows, cols, k, mode):
    lo, hi = ops.leaf_block_bounds(0, rows * cols, rows * cols, k, mode)
    return jnp.asarray(lo, jnp.float32), jnp.asarray(hi, jnp.float32)


# The offset contract is family-uniform (offsets only shift the hash
# coordinates) — the two paper families stay in the fast tier, the
# beyond-paper ones ride the nightly full sweep.
FAMILY_PARAMS = [
    f if f in ("gaussian", "rademacher") else
    pytest.param(f, marks=pytest.mark.slow)
    for f in sorted(FAMILIES)
]


@pytest.mark.parametrize("family", FAMILY_PARAMS)
@pytest.mark.parametrize("k", [1, 4])
def test_reconstruct_offset_shards_bit_identical(family, k):
    """Offset-sliced reconstruction concatenated over shards ≡ offset-0 call.

    The mesh-shard contract: slicing the operand into S row-shards, each
    reconstructed with its global ``row_offset`` (passed as a *traced*
    scalar, as shard_map does), concatenates to the bit-exact full-width
    result — the per-block seed chain never notices the shard layout.
    """
    dist = FAMILIES[family].distribution.value
    rows, cols, block = 32, 256, (8, 128)
    x = jnp.asarray(np.random.RandomState(5).randn(rows, cols), jnp.float32)
    n = 4
    seeds = jnp.arange(n, dtype=jnp.uint32) + 2
    rs = jnp.asarray(np.random.RandomState(6).randn(n, k), jnp.float32)
    mode = ProjectionMode.BLOCK if k > 1 else ProjectionMode.FULL
    lo, hi = _leaf_bounds_full(rows, cols, k, mode)
    masked = k > 1

    full = reconstruct_kernel_call(
        x, seeds, rs, 0, 0.25, dist, block, lo=lo, hi=hi,
        orig_cols=cols, masked=masked)

    call = jax.jit(lambda blk, ro: reconstruct_kernel_call(
        blk, seeds, rs, 0, 0.25, dist, block, row_offset=ro,
        lo=lo, hi=hi, orig_cols=cols, masked=masked))
    for s in (2, 4):
        per = rows // s
        parts = [call(x[i * per:(i + 1) * per], jnp.uint32(i * per))
                 for i in range(s)]
        cat = np.concatenate([np.asarray(p) for p in parts], axis=0)
        assert np.array_equal(cat, np.asarray(full)), (family, k, s)


@pytest.mark.parametrize("family", FAMILY_PARAMS)
@pytest.mark.parametrize("k", [1, 4])
def test_projection_offset_shards_sum(family, k):
    """Σ over row-shard projections == full-width projection (per block)."""
    dist = FAMILIES[family].distribution.value
    rows, cols, block = 32, 256, (8, 128)
    x = jnp.asarray(np.random.RandomState(7).randn(rows, cols), jnp.float32)
    mode = ProjectionMode.BLOCK if k > 1 else ProjectionMode.FULL
    lo, hi = _leaf_bounds_full(rows, cols, k, mode)
    masked = k > 1
    proj_seeds = jnp.stack([_proj_seed(9, j) for j in range(k)])

    full = np.asarray(projection_blocks_kernel_call(
        x, proj_seeds, 0, lo, hi, dist, block, orig_cols=cols, masked=masked))

    call = jax.jit(lambda blk, ro: projection_blocks_kernel_call(
        blk, proj_seeds, 0, lo, hi, dist, block, row_offset=ro,
        orig_cols=cols, masked=masked))
    per = rows // 4
    parts = sum(np.asarray(call(x[i * per:(i + 1) * per], jnp.uint32(i * per)))
                for i in range(4))
    np.testing.assert_allclose(parts, full, rtol=1e-4, atol=1e-3)


def test_offset_col_slices_bit_identical():
    """Col-offset slices (1-D leaves shard their cols) also concatenate
    bit-exactly — both offsets compose with traced values under jit."""
    rows, cols, block = 8, 512, (8, 128)
    x = jnp.asarray(np.random.RandomState(8).randn(rows, cols), jnp.float32)
    n, k = 3, 4
    seeds = jnp.arange(n, dtype=jnp.uint32) + 1
    rs = jnp.asarray(np.random.RandomState(9).randn(n, k), jnp.float32)
    lo, hi = _leaf_bounds_full(rows, cols, k, ProjectionMode.BLOCK)
    full = reconstruct_kernel_call(
        x, seeds, rs, 0, 1.0, "rademacher", block, lo=lo, hi=hi,
        orig_cols=cols, masked=True)
    call = jax.jit(lambda blk, co: reconstruct_kernel_call(
        blk, seeds, rs, 0, 1.0, "rademacher", block, col_offset=co,
        lo=lo, hi=hi, orig_cols=cols, masked=True))
    per = cols // 4
    parts = [call(x[:, i * per:(i + 1) * per], jnp.uint32(i * per))
             for i in range(4)]
    cat = np.concatenate([np.asarray(p) for p in parts], axis=1)
    assert np.array_equal(cat, np.asarray(full))


# ---------------------------------------------------------------------------
# Fused reconstruct+apply megakernel: bit-identity to its jnp oracle
# ---------------------------------------------------------------------------
#
# The fused kernel is its own numeric spec (chunk-batched reduction, scale
# folded into the scalars — reconstruct_apply.py docstring), so the
# contract against ref.server_update_fused_ref is **bitwise**; against the
# legacy two-kernel composition (a different reduction association) it is
# allclose only.

def _fused_sweep(family, shapes, ks):
    dist = FAMILIES[family].distribution
    n = 5                                  # awkward: not a FUSED_CHUNK multiple
    seeds = jnp.arange(n, dtype=jnp.uint32) + 11
    weights = jnp.asarray([2.0, 1.0, 0.5, 1.5, 3.0], jnp.float32)
    for si, shape in enumerate(shapes):
        tree = _tree(shape, 10 + si)
        for k in ks:
            mode = ProjectionMode.BLOCK if k > 1 else ProjectionMode.FULL
            rs = jnp.asarray(np.random.RandomState(k).randn(n, k), jnp.float32)
            bw = (jnp.asarray(np.random.RandomState(k + 1).rand(k) + 0.5,
                              jnp.float32) if k > 1 else None)
            plain = None
            for w in (None, weights):
                uf = ops.server_update_fused(
                    tree, rs, seeds, 0.5, dist, weights=w, mode=mode,
                    block_weights=bw, use_pallas=False)
                ur = ref.server_update_fused_ref(
                    tree, rs, seeds, 0.5, dist, num_projections=k, mode=mode,
                    weights=w, block_weights=bw)
                np.testing.assert_array_equal(
                    np.asarray(uf["x"]), np.asarray(ur["x"]),
                    err_msg=f"{family} shape={shape} k={k} weighted={w is not None}")
                if w is None:
                    plain = uf
            # cross-check against the legacy reduction order (allclose only)
            ul = ref.server_update_ref(tree, rs, seeds, 0.5, dist,
                                       num_projections=k, mode=mode,
                                       block_weights=bw)
            np.testing.assert_allclose(
                np.asarray(plain["x"]), np.asarray(ul["x"]), rtol=1e-4,
                atol=1e-4, err_msg=f"{family} shape={shape} k={k} (vs legacy)")


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_fused_differential_quick(family):
    _fused_sweep(family, QUICK_SHAPES, QUICK_KS)


@pytest.mark.slow
@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_fused_differential_sweep(family):
    _fused_sweep(family, AWKWARD_SHAPES, KS)


@pytest.mark.parametrize("family", FAMILY_PARAMS)
@pytest.mark.parametrize("k", [1, 4])
def test_fused_pallas_interpret_bit_identical_to_mirror(family, k):
    """Pallas lowering (interpret) ≡ the jnp mirror, bit for bit.

    This is the pin that makes the mirror a trustworthy CPU stand-in for
    the TPU kernel: both lowerings of the fused spec must produce the
    same float32 stream (scale is pre-folded so no FMA-contraction
    ambiguity survives — reconstruct_apply.py docstring).
    """
    dist = FAMILIES[family].distribution.value
    rows, cols, block = 16, 256, (8, 128)
    x = jnp.asarray(np.random.RandomState(3).randn(rows, cols), jnp.float32)
    n = 5
    seeds = jnp.arange(n, dtype=jnp.uint32) + 2
    rs = jnp.asarray(np.random.RandomState(4).randn(n, k), jnp.float32)
    mode = ProjectionMode.BLOCK if k > 1 else ProjectionMode.FULL
    lo, hi = _leaf_bounds_full(rows, cols, k, mode)
    masked = k > 1
    mirror = fused_reconstruct_apply(
        x, seeds, rs, 0, 0.25, dist, lo=lo, hi=hi, orig_cols=cols,
        masked=masked, use_pallas=False)
    pallas = fused_reconstruct_apply(
        x, seeds, rs, 0, 0.25, dist, block=block, lo=lo, hi=hi,
        orig_cols=cols, masked=masked, use_pallas=True, interpret=True)
    assert np.array_equal(np.asarray(mirror), np.asarray(pallas)), (family, k)


@pytest.mark.parametrize("row_slab", [8, 16, 64])
def test_fused_row_slab_is_bits_invariant(row_slab):
    """The mirror's row-slab tuning knob partitions space only — the
    autotuner may pick any slab without moving a single output bit."""
    rows, cols = 32, 192
    x = jnp.asarray(np.random.RandomState(5).randn(rows, cols), jnp.float32)
    n, k = 7, 3
    seeds = jnp.arange(n, dtype=jnp.uint32) + 9
    rs = jnp.asarray(np.random.RandomState(6).randn(n, k), jnp.float32)
    lo, hi = _leaf_bounds_full(rows, cols, k, ProjectionMode.BLOCK)
    base = fused_reconstruct_apply(
        x, seeds, rs, 0, 1.0, "rademacher", lo=lo, hi=hi, orig_cols=cols,
        masked=True, use_pallas=False, row_slab=None)
    slabbed = fused_reconstruct_apply(
        x, seeds, rs, 0, 1.0, "rademacher", lo=lo, hi=hi, orig_cols=cols,
        masked=True, use_pallas=False, row_slab=row_slab)
    assert np.array_equal(np.asarray(base), np.asarray(slabbed))


@pytest.mark.parametrize("family", FAMILY_PARAMS)
@pytest.mark.parametrize("k", [1, 4])
def test_fused_offset_shards_bit_identical(family, k):
    """Mesh-shard contract for the fused kernel: row-sliced calls with
    traced ``row_offset`` concatenate to the bit-exact full-width result."""
    dist = FAMILIES[family].distribution.value
    rows, cols = 32, 256
    x = jnp.asarray(np.random.RandomState(7).randn(rows, cols), jnp.float32)
    n = 4
    seeds = jnp.arange(n, dtype=jnp.uint32) + 3
    rs = jnp.asarray(np.random.RandomState(8).randn(n, k), jnp.float32)
    mode = ProjectionMode.BLOCK if k > 1 else ProjectionMode.FULL
    lo, hi = _leaf_bounds_full(rows, cols, k, mode)
    masked = k > 1
    full = fused_reconstruct_apply(
        x, seeds, rs, 0, 0.25, dist, lo=lo, hi=hi, orig_cols=cols,
        masked=masked, use_pallas=False)
    call = jax.jit(lambda blk, ro: fused_reconstruct_apply(
        blk, seeds, rs, 0, 0.25, dist, row_offset=ro, lo=lo, hi=hi,
        orig_cols=cols, masked=masked, use_pallas=False))
    for s in (2, 4):
        per = rows // s
        parts = [call(x[i * per:(i + 1) * per], jnp.uint32(i * per))
                 for i in range(s)]
        cat = np.concatenate([np.asarray(p) for p in parts], axis=0)
        assert np.array_equal(cat, np.asarray(full)), (family, k, s)


def test_fused_offset_col_slices_bit_identical():
    """Col-offset fused slices concatenate bit-exactly under jit too."""
    rows, cols = 8, 512
    x = jnp.asarray(np.random.RandomState(9).randn(rows, cols), jnp.float32)
    n, k = 3, 4
    seeds = jnp.arange(n, dtype=jnp.uint32) + 1
    rs = jnp.asarray(np.random.RandomState(10).randn(n, k), jnp.float32)
    lo, hi = _leaf_bounds_full(rows, cols, k, ProjectionMode.BLOCK)
    full = fused_reconstruct_apply(
        x, seeds, rs, 0, 1.0, "rademacher", lo=lo, hi=hi, orig_cols=cols,
        masked=True, use_pallas=False)
    call = jax.jit(lambda blk, co: fused_reconstruct_apply(
        blk, seeds, rs, 0, 1.0, "rademacher", col_offset=co, lo=lo, hi=hi,
        orig_cols=cols, masked=True, use_pallas=False))
    per = cols // 4
    parts = [call(x[:, i * per:(i + 1) * per], jnp.uint32(i * per))
             for i in range(4)]
    cat = np.concatenate([np.asarray(p) for p in parts], axis=1)
    assert np.array_equal(cat, np.asarray(full))


# ---------------------------------------------------------------------------
# Fused kernel orientation: rows along lanes (``fused_plan``)
# ---------------------------------------------------------------------------
#
# Leaves whose rows tile the lanes and columns the sublanes close over
# tiles of xᵀ.  Values depend on (row, col) alone and the chunk fold is
# elementwise, so the orientation moves no bit: the Pallas kernel in
# interpret mode must match the mirror and the oracle exactly, on
# column counts that are not multiples of 128, masked blocks included.

LANE_SHAPES = [(256, 320), (128, 960)]


def _lane_tree(shape):
    return {"x": jnp.asarray(np.random.RandomState(12).randn(*shape),
                             jnp.float32)}


@pytest.mark.parametrize("shape", LANE_SHAPES,
                         ids=[f"{r}x{c}" for r, c in LANE_SHAPES])
@pytest.mark.parametrize("k", [1, 4])
@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_fused_rows_along_lanes_bit_identical(family, k, shape):
    dist = FAMILIES[family].distribution
    plan = fused_plan(*shape)
    assert plan.lanes_rows and plan.pad == 0, plan
    tree = _lane_tree(shape)
    n = 5
    seeds = jnp.arange(n, dtype=jnp.uint32) + 21
    rs = jnp.asarray(np.random.RandomState(13).randn(n, k), jnp.float32)
    mode = ProjectionMode.BLOCK if k > 1 else ProjectionMode.FULL
    got = {p: np.asarray(ops.server_update_fused(
        tree, rs, seeds, 0.5, dist, mode=mode, use_pallas=p,
        interpret=True)["x"]) for p in (True, False)}
    want = np.asarray(ref.server_update_fused_ref(
        tree, rs, seeds, 0.5, dist, num_projections=k, mode=mode)["x"])
    assert np.array_equal(got[True], got[False]), (family, k, shape)
    assert np.array_equal(got[True], want), (family, k, shape)


@pytest.mark.parametrize("k", [1, 4])
@pytest.mark.parametrize("family", FAMILY_PARAMS)
def test_fused_rows_along_lanes_offsets_bit_identical(family, k):
    """Mesh-shard contract in the rows-along-lanes orientation: row
    shards of 128 rows and column shards of 240 columns, each closed by
    the Pallas kernel with its traced global offset, concatenate to the
    full-width call bit for bit."""
    dist = FAMILIES[family].distribution.value
    n = 4
    seeds = jnp.arange(n, dtype=jnp.uint32) + 5
    rs = jnp.asarray(np.random.RandomState(14).randn(n, k), jnp.float32)
    mode = ProjectionMode.BLOCK if k > 1 else ProjectionMode.FULL
    for (rows, cols), axis, shards in (((256, 320), 0, 2),
                                       ((128, 960), 1, 4)):
        x = _lane_tree((rows, cols))["x"]
        lo, hi = _leaf_bounds_full(rows, cols, k, mode)
        close = functools.partial(
            fused_reconstruct_apply, seeds=seeds, rs=rs, leaf_tag=3,
            scale=0.25, distribution=dist, lo=lo, hi=hi, orig_cols=cols,
            masked=k > 1, use_pallas=True, interpret=True)
        full = np.asarray(close(x))
        np.testing.assert_array_equal(
            full, np.asarray(close(x, use_pallas=False)))
        call = jax.jit(lambda blk, off: close(
            blk, **{("row_offset", "col_offset")[axis]: off}))
        per = x.shape[axis] // shards
        parts = [np.asarray(call(jax.lax.slice_in_dim(x, i * per,
                                                      (i + 1) * per,
                                                      axis=axis),
                                 jnp.uint32(i * per)))
                 for i in range(shards)]
        assert fused_plan(*parts[0].shape).lanes_rows
        assert np.array_equal(np.concatenate(parts, axis=axis), full), \
            (family, k, axis)


def _smollm_shapes():
    from repro.configs.registry import get_arch
    return jax.eval_shape(get_arch("smollm-360m").init, jax.random.PRNGKey(0))


def test_fused_plan_orientation_and_pad():
    """smollm-360m's eight large leaves close rows-along-lanes with no
    pad; its norms, ``(1, n)`` vectors and the paper MLP's leaves stay
    as they lie, padded to their tiles."""
    from repro.models.mlp_classifier import init_mlp

    shapes = _smollm_shapes()
    big = [s for s in jax.tree_util.tree_leaves(shapes) if s.ndim == 3
           or s.shape == (49152, 960)]
    assert len(big) == 8
    for s in big:
        rows, cols = ops.shape_2d(s.shape)
        plan = fused_plan(rows, cols)
        assert plan.lanes_rows and plan.pad == 0, (s.shape, plan)
        tr, tc = plan.tile
        assert rows % tr == 0 and tr % 128 == 0
        assert cols % tc == 0 and tc % 8 == 0
    for rows, cols in [(1, 960), (1, 4096), (32, 960)] + [
            ops.shape_2d(p.shape)
            for p in jax.tree_util.tree_leaves(init_mlp())]:
        assert not fused_plan(rows, cols).lanes_rows, (rows, cols)
    assert fused_plan(32, 960) == (False, (32, 256), 32 * 64)
    assert fused_plan(1, 960) == (False, (8, 256), 8 * 1024 - 960)
    # The whole tree on the Pallas path: 8 leaves along lanes, and the
    # pad left is the norms' alone (two (32, 960), one (1, 960)).
    assert ops.fused_tiling(shapes, use_pallas=True) == {
        "lane_rows_leaves": 8, "pad_elements": 2 * 2048 + 7232}
    assert ops.fused_tiling(shapes, use_pallas=False) == {
        "lane_rows_leaves": 0, "pad_elements": 0}
