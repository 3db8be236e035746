"""jit'd wrappers: arbitrary pytrees → block-aligned 2-D kernel calls.

These mirror the pure-jnp protocol functions bit-for-bit (same hash,
same (row, col) addressing, same per-projection seed folding), so the
kernel path can replace the jnp path anywhere:

* ``project_tree_kernel``    ≡ repro.core.projection.project_tree
  (any direction family, k=1 full or k block scalars — DESIGN.md §6)
* ``server_update_kernel``   ≡ repro.core.fedscalar.server_aggregate
* ``qsgd_roundtrip_kernel``  — kernelized QSGD quantize→dequantize

Leaves are viewed as (leading-dims, last-dim) matrices and zero-padded
to block multiples; zero padding contributes nothing to the projection
and padded outputs are sliced away, so results are exact, not
approximate.  The k-block partition is computed over the **global**
flattened tree (``repro.core.directions.block_bounds``) and translated
to leaf-local flat bounds here, so the kernels and the jnp oracle agree
on which scalar owns which weight.

Shapes/dtypes: uploads are float32 — ``(k,)`` from the projection,
``(N,)``/``(N, k)`` into the server update; seeds are uint32 round
seeds ``(N,)``; params keep their own dtypes (float32 accumulation
in-kernel).
"""
from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp

from repro.core.directions import block_bounds, check_block_mask_domain
from repro.core.prng import Distribution
from repro.core.projection import ProjectionMode, _proj_seed, leaf_layout
from repro.kernels.qsgd_quant import qsgd_kernel_call
from repro.kernels.reconstruct_apply import (
    DEFAULT_FUSED_BLOCK,
    fused_plan,
    fused_reconstruct_apply,
)
from repro.kernels.seeded_projection import projection_blocks_kernel_call
from repro.kernels.seeded_reconstruct import reconstruct_kernel_call

__all__ = [
    "shape_2d",
    "as_2d",
    "as_blocked_2d",
    "leaf_block_bounds",
    "fold_upload_weights",
    "project_tree_kernel",
    "server_update_kernel",
    "server_update_fused",
    "fused_tiling",
    "qsgd_roundtrip_kernel",
]

def _pick_block(rows: int, cols: int) -> tuple:
    br = min(256, -(-rows // 8) * 8)
    bc = min(512, -(-cols // 128) * 128)
    return br, bc


def shape_2d(shape) -> tuple[int, int]:
    """A leaf's shape → its (leading dims, last dim) matrix shape;
    scalars and vectors become one row."""
    if len(shape) < 2:
        return 1, int(math.prod(shape))
    return int(math.prod(shape[:-1])), int(shape[-1])


def as_2d(leaf):
    """leaf → its :func:`shape_2d` matrix view."""
    return leaf.reshape(shape_2d(leaf.shape))


def as_blocked_2d(leaf: jax.Array):
    """leaf → (padded 2-D view, block, original (rows, cols))."""
    x = as_2d(leaf)
    rows, cols = x.shape
    br, bc = _pick_block(rows, cols)
    pr = (-rows) % br
    pc = (-cols) % bc
    if pr or pc:
        x = jnp.pad(x, ((0, pr), (0, pc)))
    return x, (br, bc), (rows, cols)


def _dist_name(distribution: Distribution) -> str:
    return distribution.value


def leaf_block_bounds(
    leaf_offset: int, leaf_size: int, total: int, num_blocks: int,
    mode: ProjectionMode = ProjectionMode.BLOCK,
) -> tuple[list[float], list[float]]:
    """Leaf-local flat [lo, hi) of every global block (clamped, floats).

    Blocks that miss the leaf clamp to an empty range; FULL mode maps
    every projection onto the whole leaf.
    """
    if mode != ProjectionMode.BLOCK or num_blocks == 1:
        return [0.0] * num_blocks, [float(leaf_size)] * num_blocks
    check_block_mask_domain(leaf_size)
    los, his = [], []
    for j in range(num_blocks):
        blo, bhi = block_bounds(total, num_blocks, j)
        lo = min(max(blo - leaf_offset, 0), leaf_size)
        hi = min(max(bhi - leaf_offset, 0), leaf_size)
        los.append(float(lo))
        his.append(float(max(hi, lo)))
    return los, his


def fold_upload_weights(
    rs: jax.Array,
    server_lr: float,
    weights: jax.Array | None,
    mode: ProjectionMode,
    block_weights: jax.Array | None,
) -> tuple[jax.Array, jax.Array | float]:
    """Fold every aggregation coefficient into the scalars → ``(rs, scale)``.

    The decode step is then always the bare ``x + scale·Σₙⱼ rₙⱼ vₙⱼ``:
    FULL-mode 1/m averaging, per-block shrinkage, per-client
    Horvitz–Thompson weights, and the uniform 1/N mean all pre-multiply
    the ``(N, k)`` scalar matrix.  Shared by the single-device kernel
    path and the mesh-sharded server (:mod:`repro.sharding.fed_rules`),
    so both apply bit-identical coefficients.
    """
    rs = jnp.asarray(rs, jnp.float32)
    if rs.ndim == 1:
        rs = rs[:, None]
    n, k = rs.shape
    if mode == ProjectionMode.FULL and k > 1:
        rs = rs / k        # matches reconstruct_tree's unbiased 1/m mean
    if block_weights is not None:
        rs = rs * jnp.asarray(block_weights, jnp.float32).reshape(1, k)
    if weights is not None:
        rs = rs * weights.reshape(-1, 1).astype(jnp.float32)
        scale = server_lr
    else:
        scale = server_lr / n
    return rs, scale


def project_tree_kernel(
    delta: Any,
    seed,
    distribution: Distribution = Distribution.RADEMACHER,
    interpret: bool | None = None,
    num_blocks: int = 1,
    mode: ProjectionMode = ProjectionMode.FULL,
) -> jax.Array:
    """Kernelized FedScalar encode: → float32 ``(num_blocks,)``.

    ``num_blocks=1`` is the paper's single scalar; BLOCK mode emits the
    k-block-scalar upload ``r ∈ ℝᵏ`` in one fused sweep per leaf.
    """
    seeds = jnp.stack([_proj_seed(seed, j) for j in range(num_blocks)])
    leaves = jax.tree_util.tree_leaves(delta)
    layout = leaf_layout(delta)
    total = layout[-1].end if layout else 0
    masked = mode == ProjectionMode.BLOCK and num_blocks > 1
    acc = jnp.zeros((num_blocks,), jnp.float32)
    for ll, leaf in zip(layout, leaves):
        x2d, block, (rows, cols) = as_blocked_2d(leaf)
        lo, hi = leaf_block_bounds(ll.offset, ll.size, total, num_blocks, mode)
        acc = acc + projection_blocks_kernel_call(
            x2d, seeds, ll.tag, jnp.asarray(lo, jnp.float32),
            jnp.asarray(hi, jnp.float32), _dist_name(distribution), block,
            orig_cols=cols, interpret=interpret, masked=masked)
    return acc


def server_update_kernel(
    params: Any,
    rs: jax.Array,        # (N,), (N, 1) or (N, k) uploaded scalars
    seeds: jax.Array,     # (N,) round seeds
    server_lr: float = 1.0,
    distribution: Distribution = Distribution.RADEMACHER,
    interpret: bool | None = None,
    weights: jax.Array | None = None,   # (N,) per-client aggregation weights
    mode: ProjectionMode = ProjectionMode.FULL,
    block_weights: jax.Array | None = None,   # (k,) per-block shrinkage
) -> Any:
    """Kernelized Algorithm 1 lines 7–13: x ← x + (lr/N)·Σₙⱼ rₙⱼ vₙⱼ.

    With ``weights`` (the runtime's Horvitz–Thompson × staleness
    coefficients) the uniform 1/N mean becomes x ← x + lr·Σₙ wₙ rₙ vₙ.
    2-D ``rs`` runs the k-block-scalar decode (block index joins the
    kernel grid); ``block_weights`` applies the MSE-optimal per-block
    shrinkage (DESIGN §6).  All weights are folded into the scalars so
    the kernel is unchanged.
    """
    rs, scale = fold_upload_weights(rs, server_lr, weights, mode, block_weights)
    k = rs.shape[1]
    leaves, treedef = jax.tree_util.tree_flatten(params)
    layout = leaf_layout(params)
    total = layout[-1].end if layout else 0
    masked = mode == ProjectionMode.BLOCK and k > 1
    out = []
    for ll, leaf in zip(layout, leaves):
        x2d, block, (rows, cols) = as_blocked_2d(leaf)
        lo, hi = leaf_block_bounds(ll.offset, ll.size, total, k, mode)
        y = reconstruct_kernel_call(
            x2d, seeds, rs, ll.tag, scale, _dist_name(distribution), block,
            interpret=interpret, lo=jnp.asarray(lo, jnp.float32),
            hi=jnp.asarray(hi, jnp.float32), orig_cols=cols, masked=masked)
        out.append(y[:rows, :cols].reshape(leaf.shape))
    return jax.tree_util.tree_unflatten(treedef, out)


def server_update_fused(
    params: Any,
    rs: jax.Array,        # (N,), (N, 1) or (N, k) uploaded scalars
    seeds: jax.Array,     # (N,) round seeds
    server_lr: float = 1.0,
    distribution: Distribution = Distribution.RADEMACHER,
    interpret: bool | None = None,
    weights: jax.Array | None = None,   # (N,) per-client aggregation weights
    mode: ProjectionMode = ProjectionMode.FULL,
    block_weights: jax.Array | None = None,   # (k,) per-block shrinkage
    use_pallas: bool | None = None,
    block: tuple | None = None,         # Pallas (br, bc) tile (tuned)
    row_slab: int | None = None,        # mirror slab height (tuned)
) -> Any:
    """Fused-megakernel round close: same contract as server_update_kernel.

    Routes every leaf through :func:`repro.kernels.reconstruct_apply.
    fused_reconstruct_apply` — the chunk-batched numeric spec — instead
    of the per-client fori kernel.  Results are allclose (not bitwise)
    to ``server_update_kernel``/``server_update_ref``; the fused path's
    own bitwise oracle is ``ref.server_update_fused_ref``.  ``block``/
    ``row_slab`` take autotuned winners (``kernels.tune``); both are
    bits-invariant.  Leaves go in as their 2-D views, unpadded: the
    Pallas dispatch tiles each one itself (``fused_plan``).
    """
    rs, scale = fold_upload_weights(rs, server_lr, weights, mode, block_weights)
    k = rs.shape[1]
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu"
    leaves, treedef = jax.tree_util.tree_flatten(params)
    layout = leaf_layout(params)
    total = layout[-1].end if layout else 0
    masked = mode == ProjectionMode.BLOCK and k > 1
    out = []
    for ll, leaf in zip(layout, leaves):
        lo, hi = leaf_block_bounds(ll.offset, ll.size, total, k, mode)
        y = fused_reconstruct_apply(
            as_2d(leaf), seeds, rs, ll.tag, scale, _dist_name(distribution),
            block=block or DEFAULT_FUSED_BLOCK, lo=jnp.asarray(lo, jnp.float32),
            hi=jnp.asarray(hi, jnp.float32), masked=masked,
            use_pallas=use_pallas, interpret=interpret, row_slab=row_slab)
        out.append(y.reshape(leaf.shape))
    return jax.tree_util.tree_unflatten(treedef, out)


def fused_tiling(params: Any, block: tuple | None = None,
                 use_pallas: bool | None = None) -> dict:
    """How :func:`server_update_fused` tiles ``params`` (arrays or
    shapes): ``lane_rows_leaves``, the leaves the Pallas kernel closes
    with their rows along lanes, and ``pad_elements``, the elements it
    computes and throws away per client.  The jnp mirror (the default
    off the TPU) tiles nothing: both are 0 there."""
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu"
    plans = [fused_plan(*shape_2d(leaf.shape), block or DEFAULT_FUSED_BLOCK)
             for leaf in jax.tree_util.tree_leaves(params)] if use_pallas else []
    return {"lane_rows_leaves": sum(p.lanes_rows for p in plans),
            "pad_elements": sum(p.pad for p in plans)}


def qsgd_roundtrip_kernel(
    tree: Any,
    seed,
    bits: int = 8,
    interpret: bool | None = None,
) -> Any:
    """Kernelized per-leaf QSGD quantize→dequantize."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    out = []
    for tag, leaf in enumerate(leaves):
        x2d, block, (rows, cols) = as_blocked_2d(leaf)
        q = qsgd_kernel_call(x2d, seed, tag, bits, block, interpret=interpret)
        out.append(q[:rows, :cols].reshape(leaf.shape))
    return jax.tree_util.tree_unflatten(treedef, out)
