"""Mesh-sharded federation server: shard rules + shard_map decode paths.

The server-side reconstruction  x ← x + lr·Σₙⱼ coeffₙ·rₙⱼ·vⱼ(ξₙ)  is
embarrassingly parallel in the model dimension d: because the direction
chain is counter-based (``(seed ⊕ leaf_tag, row, col)`` — DESIGN §1/§3),
each device of a (``data``, ``model``) mesh can regenerate exactly its
contiguous slice of every vₙ from the same 32-bit seeds, with **zero
cross-device communication of directions**.  This module is the whole
sharded execution path (DESIGN §7):

* a **shard plan** — each leaf's 2-D view is split into equal contiguous
  slices along its larger axis (rows preferred), padded so every device
  owns the same local shape; the global (row, col) coordinate of a local
  element is ``local + shard_ordinal · per_shard``, which is all the
  offset the seeded kernels need;
* **PartitionSpecs** for the sharded 2-D views (rows or cols over the
  flattened mesh axes) and the replicated ``(N, k)`` upload buffers;
* ``shard_map`` **decode paths**: :func:`sharded_server_update` (no
  collective at all — reconstruction is elementwise in d) and
  :func:`sharded_project_tree` (one ``psum`` of the k block scalars,
  the round's entire collective budget on the downlink-projection side);
* per-shard **local bodies** (:func:`local_reconstruct_2d`,
  :func:`local_project_2d`) that mirror the Pallas kernel bodies op for
  op in plain jnp, so a (1, 1) mesh is bit-identical to the
  single-device kernel path and any N-shard mesh reconstructs
  bit-identically too (only the projection's psum reassociates floats).

Shapes/dtypes: uploads are float32 ``(N, k)`` with uint32 ``(N,)`` round
seeds, replicated on every device; sharded views are the leaf dtype;
accumulation is float32 everywhere (DESIGN §6 kernel contract).
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.prng import PROJ_SALT, Distribution
from repro.core.projection import (
    LeafLayout,
    ProjectionMode,
    _proj_seed,
    leaf_layout,
)
from repro.kernels.common import fold_seed, gen_tile, splitmix32

__all__ = [
    "FedShardPlan",
    "LeafShard",
    "plan_tree",
    "num_mesh_shards",
    "shard_ordinal",
    "fed_param_specs",
    "upload_spec",
    "to_sharded_2d",
    "from_sharded_2d",
    "local_project_2d",
    "local_reconstruct_2d",
    "shard_tree",
    "sharded_apply_blocks",
    "sharded_project_tree",
    "sharded_server_update",
]

# Single source: repro.core.prng.PROJ_SALT (the kernels' in-kernel
# per-block seed derivation uses the same constant).
_PROJ_SALT = PROJ_SALT


# ---------------------------------------------------------------------------
# Shard plan
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class LeafShard:
    """How one leaf's 2-D view is split across the mesh.

    ``axis`` is the sharded dimension of the view (0 = rows, 1 = cols);
    ``per_shard`` is the local extent along it; the view is padded to
    ``num_shards · per_shard`` so every device owns an identical local
    shape (padding is zero and is sliced away on unshard — exact).
    """

    layout: LeafLayout
    axis: int
    per_shard: int


@dataclasses.dataclass(frozen=True)
class FedShardPlan:
    """Shard assignments for every leaf of a parameter pytree."""

    num_shards: int
    total: int                      # global flat dimension d
    leaves: tuple[LeafShard, ...]

    def per_shard_elements(self) -> int:
        """Local elements per device (the sharded-path working set)."""
        out = 0
        for ls in self.leaves:
            rows, cols = ls.layout.rows, ls.layout.cols
            out += ls.per_shard * (cols if ls.axis == 0 else rows)
        return out

    def balance(self) -> float:
        """per-device work ÷ ideal d/S — 1.0 is a perfectly even split."""
        ideal = self.total / max(self.num_shards, 1)
        return self.per_shard_elements() / max(ideal, 1.0)


def _ceil_div(a: int, b: int) -> int:
    return -(-a // b)


def plan_tree(params: Any, num_shards: int) -> FedShardPlan:
    """→ :class:`FedShardPlan` splitting each leaf's larger view axis.

    Rows are preferred (they compose with the kernels' row-major flat
    addressing at zero extra masking); a leaf whose view has fewer rows
    than shards (1-D leaves seen as ``(1, n)``) shards its cols instead,
    so flat parameter vectors still spread across the mesh.
    """
    shards = []
    for ll in leaf_layout(params):
        if ll.rows >= num_shards or ll.rows >= ll.cols:
            axis, per = 0, _ceil_div(ll.rows, num_shards)
        else:
            axis, per = 1, _ceil_div(ll.cols, num_shards)
        shards.append(LeafShard(layout=ll, axis=axis, per_shard=per))
    total = shards[-1].layout.end if shards else 0
    return FedShardPlan(num_shards=num_shards, total=total,
                        leaves=tuple(shards))


def num_mesh_shards(mesh: Mesh) -> int:
    n = 1
    for s in mesh.devices.shape:
        n *= int(s)
    return n


def shard_ordinal(mesh: Mesh) -> jax.Array:
    """Flat shard index inside ``shard_map`` (row-major over mesh axes).

    Matches the device order of ``PartitionSpec((*axis_names,))`` on a
    contiguous dimension, so ordinal·per_shard is the global offset of
    this device's slice.
    """
    s = jnp.uint32(0)
    for name, size in zip(mesh.axis_names, mesh.devices.shape):
        s = s * jnp.uint32(int(size)) + jax.lax.axis_index(name).astype(jnp.uint32)
    return s


def _mesh_axes(mesh: Mesh) -> tuple:
    return tuple(mesh.axis_names)


def fed_param_specs(plan: FedShardPlan, mesh: Mesh) -> tuple:
    """Per-leaf ``PartitionSpec`` of the padded sharded 2-D views."""
    axes = _mesh_axes(mesh)
    return tuple(P(axes, None) if ls.axis == 0 else P(None, axes)
                 for ls in plan.leaves)


def upload_spec() -> P:
    """Replicated spec for the (N, k) scalars / (N,) seeds buffers."""
    return P()


def to_sharded_2d(tree: Any, plan: FedShardPlan) -> list[jax.Array]:
    """Leaves → padded 2-D views matching :func:`fed_param_specs`."""
    out = []
    for ls, leaf in zip(plan.leaves, jax.tree_util.tree_leaves(tree)):
        ll = ls.layout
        x = leaf.reshape(ll.rows, ll.cols)
        pr = ls.per_shard * plan.num_shards - ll.rows if ls.axis == 0 else 0
        pc = ls.per_shard * plan.num_shards - ll.cols if ls.axis == 1 else 0
        if pr or pc:
            x = jnp.pad(x, ((0, pr), (0, pc)))
        out.append(x)
    return out


def from_sharded_2d(arrs, plan: FedShardPlan, like: Any) -> Any:
    """Padded 2-D views → pytree shaped/dtyped like ``like``."""
    leaves = jax.tree_util.tree_leaves(like)
    out = []
    for ls, arr, leaf in zip(plan.leaves, arrs, leaves):
        ll = ls.layout
        out.append(arr[:ll.rows, :ll.cols].reshape(ll.shape).astype(leaf.dtype))
    return jax.tree_util.tree_unflatten(jax.tree_util.tree_structure(like), out)


def shard_tree(tree: Any, plan: FedShardPlan, mesh: Mesh) -> list[jax.Array]:
    """Device-put the padded views onto the mesh (persistent residency).

    Pair with :func:`sharded_apply_blocks` to keep the global model
    sharded across rounds so the per-round apply moves no parameter
    bytes — the §Sharding benchmark measures exactly this resident
    loop.  (The federation engine instead keeps params replicated: its
    client compute and eval stages consume the full model each round.)
    """
    specs = fed_param_specs(plan, mesh)
    return [jax.device_put(x, NamedSharding(mesh, s))
            for x, s in zip(to_sharded_2d(tree, plan), specs)]


# ---------------------------------------------------------------------------
# Local (per-shard) bodies — jnp mirrors of the Pallas kernel bodies
# ---------------------------------------------------------------------------


def _coords(shape, row_offset, col_offset):
    row = jax.lax.broadcasted_iota(jnp.uint32, shape, 0) \
        + jnp.asarray(row_offset, jnp.uint32)
    col = jax.lax.broadcasted_iota(jnp.uint32, shape, 1) \
        + jnp.asarray(col_offset, jnp.uint32)
    return row, col


def local_project_2d(
    x_local: jax.Array,
    seeds_folded: jax.Array,      # (k,) per-block seeds, leaf_tag pre-folded
    row_offset,
    col_offset,
    distribution: str,
    lo: jax.Array,                # (k,) leaf-local flat bounds (float32)
    hi: jax.Array,
    orig_cols: int,
    masked: bool,
) -> jax.Array:
    """→ (k,) partial block scalars of this shard's slice (caller psums).

    Identical arithmetic to ``seeded_projection._proj_kernel`` on one
    tile: regenerate v at global (row, col), multiply, reduce in
    float32.  Offsets may be traced (``shard_ordinal``-derived).
    """
    k = seeds_folded.shape[0]
    row, col = _coords(x_local.shape, row_offset, col_offset)
    xf = x_local.astype(jnp.float32)
    outs = []
    if masked:
        flat = (row.astype(jnp.float32) * jnp.float32(orig_cols)
                + col.astype(jnp.float32))
    for b in range(k):
        v = gen_tile(seeds_folded[b], row, col, distribution)
        if masked:
            m = jnp.logical_and(flat >= lo[b], flat < hi[b])
            v = v * m.astype(jnp.float32)
        outs.append(jnp.sum(xf * v))
    return jnp.stack(outs)


def local_reconstruct_2d(
    x_local: jax.Array,
    seeds: jax.Array,             # (N,) uint32 round seeds (unfolded)
    rs: jax.Array,                # (N, k) pre-folded scalars (0 = padding)
    scale,
    leaf_tag: int,
    row_offset,
    col_offset,
    distribution: str,
    lo: jax.Array,                # (k,) leaf-local flat bounds (float32)
    hi: jax.Array,
    orig_cols: int,
    masked: bool,
) -> jax.Array:
    """→ updated local slice  x + scale·Σₙⱼ rₙⱼ vₙⱼ  (shape/dtype of x_local).

    Mirrors ``seeded_reconstruct._rec_kernel`` op for op — same
    SplitMix32 per-block seed fold, same block-outer/client-inner
    accumulation order, same float32 accumulator — so a (1, 1) mesh
    reproduces the kernel path bit for bit, and any shard layout
    reproduces each element's arithmetic exactly (reconstruction is
    elementwise in d; there is nothing to reassociate).
    """
    n, k = rs.shape
    row, col = _coords(x_local.shape, row_offset, col_offset)
    acc = jnp.zeros(x_local.shape, jnp.float32)
    if masked:
        flat = (row.astype(jnp.float32) * jnp.float32(orig_cols)
                + col.astype(jnp.float32))
    for b in range(k):
        salt = jnp.uint32(_PROJ_SALT) + jnp.uint32(b)
        if masked:
            m = jnp.logical_and(flat >= lo[b], flat < hi[b]).astype(jnp.float32)
        else:
            m = None

        def body(i, acc, salt=salt, m=m, b=b):
            seed_b = splitmix32(seeds[i] ^ salt)
            v = gen_tile(fold_seed(seed_b, leaf_tag), row, col, distribution)
            if m is not None:
                v = v * m
            return acc + rs[i, b] * v

        acc = jax.lax.fori_loop(0, n, body, acc)
    y = x_local.astype(jnp.float32) + jnp.asarray(scale, jnp.float32) * acc
    return y.astype(x_local.dtype)


def _local_reconstruct_kernel(x_local, seeds, rs, scale, leaf_tag,
                              row_offset, col_offset, distribution,
                              lo, hi, orig_cols, masked):
    """Pallas-kernel local body (TPU fast path; interpret mode on CPU)."""
    from repro.kernels.ops import _pick_block
    from repro.kernels.seeded_reconstruct import reconstruct_kernel_call

    rl, cl = x_local.shape
    br, bc = _pick_block(rl, cl)
    pr, pc = (-rl) % br, (-cl) % bc
    xp = jnp.pad(x_local, ((0, pr), (0, pc))) if pr or pc else x_local
    y = reconstruct_kernel_call(
        xp, seeds, rs, leaf_tag, scale, distribution, (br, bc),
        row_offset=row_offset, col_offset=col_offset,
        lo=lo, hi=hi, orig_cols=orig_cols, masked=masked)
    return y[:rl, :cl]


def _local_project_kernel(x_local, seeds, leaf_tag, row_offset, col_offset,
                          distribution, lo, hi, orig_cols, masked):
    from repro.kernels.ops import _pick_block
    from repro.kernels.seeded_projection import projection_blocks_kernel_call

    rl, cl = x_local.shape
    br, bc = _pick_block(rl, cl)
    pr, pc = (-rl) % br, (-cl) % bc
    xp = jnp.pad(x_local, ((0, pr), (0, pc))) if pr or pc else x_local
    return projection_blocks_kernel_call(
        xp, seeds, leaf_tag, lo, hi, distribution, (br, bc),
        row_offset=row_offset, col_offset=col_offset,
        orig_cols=orig_cols, masked=masked)


# ---------------------------------------------------------------------------
# shard_map decode paths
# ---------------------------------------------------------------------------


def _dist_name(distribution) -> str:
    return distribution.value if isinstance(distribution, Distribution) \
        else str(distribution)


def _leaf_bounds(plan: FedShardPlan, k: int, mode: ProjectionMode):
    from repro.kernels.ops import leaf_block_bounds

    out = []
    for ls in plan.leaves:
        lo, hi = leaf_block_bounds(ls.layout.offset, ls.layout.size,
                                   plan.total, k, mode)
        out.append((jnp.asarray(lo, jnp.float32), jnp.asarray(hi, jnp.float32)))
    return out


def _offsets(ls: LeafShard, ordinal):
    off = ordinal * jnp.uint32(ls.per_shard)
    return (off, jnp.uint32(0)) if ls.axis == 0 else (jnp.uint32(0), off)


def sharded_apply_blocks(
    mesh: Mesh,
    plan: FedShardPlan,
    blocks,                        # padded 2-D views (to_sharded_2d/shard_tree)
    rs: jax.Array,                 # (N,), (N, 1) or (N, k) uploaded scalars
    seeds: jax.Array,              # (N,) uint32 round seeds
    server_lr: float = 1.0,
    distribution: Distribution = Distribution.RADEMACHER,
    weights: jax.Array | None = None,
    mode: ProjectionMode = ProjectionMode.FULL,
    block_weights: jax.Array | None = None,
    use_kernel: bool | None = None,
    use_fused: bool = False,
) -> list[jax.Array]:
    """The decode core on pre-sharded views → updated views, still sharded.

    Outputs carry the same PartitionSpecs as the inputs, so feeding
    them back in keeps the model device-resident across rounds (zero
    parameter bytes moved per round — the DESIGN §7 HBM bill).

    ``use_fused=True`` routes every local body through the fused
    reconstruct+apply megakernel spec instead of the fori/kernel pair
    (``use_kernel`` then picks Pallas vs the jnp mirror — same bits
    either way, DESIGN §11).
    """
    from repro.kernels.ops import fold_upload_weights
    from repro.kernels.reconstruct_apply import fused_reconstruct_apply

    rs, scale = fold_upload_weights(rs, server_lr, weights, mode, block_weights)
    k = rs.shape[1]
    masked = mode == ProjectionMode.BLOCK and k > 1
    bounds = _leaf_bounds(plan, k, mode)
    dist = _dist_name(distribution)
    if use_kernel is None:
        use_kernel = jax.default_backend() == "tpu"
    seeds = jnp.asarray(seeds, jnp.uint32)

    def apply_local(seeds, rs, *xs):
        s = shard_ordinal(mesh)
        out = []
        for ls, (lo, hi), xl in zip(plan.leaves, bounds, xs):
            ro, co = _offsets(ls, s)
            if use_fused:
                # The fused spec is a pure function of global (row, col),
                # so any shard layout concatenates bit-identically to the
                # single-device close (tests/test_kernel_differential.py);
                # the Pallas dispatch tiles the unpadded shard itself.
                out.append(fused_reconstruct_apply(
                    xl, seeds, rs, ls.layout.tag, scale, dist,
                    row_offset=ro, col_offset=co, lo=lo, hi=hi,
                    orig_cols=ls.layout.cols, masked=masked,
                    use_pallas=use_kernel))
                continue
            body = _local_reconstruct_kernel if use_kernel \
                else local_reconstruct_2d
            out.append(body(xl, seeds, rs, scale, ls.layout.tag, ro, co,
                            dist, lo, hi, ls.layout.cols, masked))
        return tuple(out)

    specs = fed_param_specs(plan, mesh)
    return list(jax.shard_map(
        apply_local, mesh=mesh,
        in_specs=(upload_spec(), upload_spec()) + specs,
        out_specs=specs, check_vma=False,
    )(seeds, rs, *blocks))


def sharded_server_update(
    mesh: Mesh,
    params: Any,
    rs: jax.Array,                 # (N,), (N, 1) or (N, k) uploaded scalars
    seeds: jax.Array,              # (N,) uint32 round seeds
    server_lr: float = 1.0,
    distribution: Distribution = Distribution.RADEMACHER,
    weights: jax.Array | None = None,
    mode: ProjectionMode = ProjectionMode.FULL,
    block_weights: jax.Array | None = None,
    use_kernel: bool | None = None,
    plan: FedShardPlan | None = None,
    use_fused: bool = False,
) -> Any:
    """Mesh-sharded Algorithm 1 lines 7–13: zero-collective decode.

    Semantically ≡ :func:`repro.kernels.ops.server_update_kernel` (and
    ≈ ``server_aggregate``): every mesh device reconstructs its own
    contiguous slice of the direction chain from the replicated
    ``(r, ξ)`` buffers and applies the update locally — no gather of v,
    no collective of any kind.  ``use_kernel`` routes the local body to
    the Pallas kernel (default on TPU) or the jnp mirror (default
    elsewhere).  Takes and returns a replicated pytree (the engine's
    client/eval stages consume the full model); callers holding the
    model sharded across rounds should use :func:`sharded_apply_blocks`
    directly and skip the per-round shard/unshard round-trip.
    """
    if plan is None:
        plan = plan_tree(params, num_mesh_shards(mesh))
    outs = sharded_apply_blocks(
        mesh, plan, to_sharded_2d(params, plan), rs, seeds,
        server_lr=server_lr, distribution=distribution, weights=weights,
        mode=mode, block_weights=block_weights, use_kernel=use_kernel,
        use_fused=use_fused)
    return from_sharded_2d(outs, plan, params)


def sharded_project_tree(
    mesh: Mesh,
    delta: Any,
    seed,
    distribution: Distribution = Distribution.RADEMACHER,
    num_blocks: int = 1,
    mode: ProjectionMode = ProjectionMode.FULL,
    use_kernel: bool | None = None,
    plan: FedShardPlan | None = None,
) -> jax.Array:
    """Mesh-sharded FedScalar encode → float32 ``(num_blocks,)``.

    ≡ :func:`repro.kernels.ops.project_tree_kernel` up to float32
    reassociation: each shard projects its slice locally, then the k
    partial block scalars cross the mesh in a single ``psum`` — the
    only collective of the whole decode/encode pair (DESIGN §7).
    """
    if plan is None:
        plan = plan_tree(delta, num_mesh_shards(mesh))
    masked = mode == ProjectionMode.BLOCK and num_blocks > 1
    bounds = _leaf_bounds(plan, num_blocks, mode)
    dist = _dist_name(distribution)
    if use_kernel is None:
        use_kernel = jax.default_backend() == "tpu"
    proj_seeds = jnp.stack([_proj_seed(seed, j) for j in range(num_blocks)])
    blocks = to_sharded_2d(delta, plan)

    def project_local(proj_seeds, *xs):
        s = shard_ordinal(mesh)
        acc = jnp.zeros((num_blocks,), jnp.float32)
        for ls, (lo, hi), xl in zip(plan.leaves, bounds, xs):
            ro, co = _offsets(ls, s)
            if use_kernel:
                acc = acc + _local_project_kernel(
                    xl, proj_seeds, ls.layout.tag, ro, co, dist,
                    lo, hi, ls.layout.cols, masked)
            else:
                folded = jax.vmap(
                    lambda sd: fold_seed(sd, ls.layout.tag))(proj_seeds)
                acc = acc + local_project_2d(
                    xl, folded, ro, co, dist, lo, hi, ls.layout.cols, masked)
        return jax.lax.psum(acc, _mesh_axes(mesh))

    specs = fed_param_specs(plan, mesh)
    return jax.shard_map(
        project_local, mesh=mesh,
        in_specs=(upload_spec(),) + specs,
        out_specs=P(), check_vma=False,
    )(proj_seeds, *blocks)
