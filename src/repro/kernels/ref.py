"""Pure-jnp oracles for every kernel (the correctness contracts).

The projection/reconstruction oracles are simply the core-library
functions (the kernels share their hash and addressing, so equality is
exact up to float reduction order).  The QSGD oracle is likewise the
core quantizer itself — :mod:`repro.core.qsgd` implements the same
hash-uniform stochastic rounding the kernel fuses, so there is one
source of the rounding stream and the oracle stays a pure re-export.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.core.fedscalar import FedScalarConfig, server_aggregate
from repro.core.prng import Distribution, block_seed, random_for_shape
from repro.core.projection import ProjectionMode, project_tree
from repro.core.qsgd import quantize_tree

__all__ = ["project_tree_ref", "server_update_ref",
           "server_update_fused_ref", "qsgd_roundtrip_ref"]


def project_tree_ref(delta: Any, seed,
                     distribution: Distribution = Distribution.RADEMACHER,
                     num_projections: int = 1,
                     mode: ProjectionMode = ProjectionMode.FULL):
    return project_tree(delta, seed, distribution,
                        num_projections=num_projections, mode=mode)


def server_update_ref(params: Any, rs, seeds, server_lr: float = 1.0,
                      distribution: Distribution = Distribution.RADEMACHER,
                      num_projections: int = 1,
                      mode: ProjectionMode = ProjectionMode.FULL,
                      block_weights=None):
    cfg = FedScalarConfig(server_lr=server_lr, distribution=distribution,
                          num_projections=num_projections, mode=mode)
    rs = jnp.asarray(rs, jnp.float32)
    if rs.ndim == 1:
        rs = rs.reshape(-1, 1)
    return server_aggregate(params, rs, seeds, cfg,
                            block_weights=block_weights)


def server_update_fused_ref(params: Any, rs, seeds, server_lr: float = 1.0,
                            distribution: Distribution =
                            Distribution.RADEMACHER,
                            num_projections: int = 1,
                            mode: ProjectionMode = ProjectionMode.FULL,
                            weights=None, block_weights=None):
    """Bitwise oracle for the fused reconstruct+apply numeric spec.

    Writes the chunked contract of ``reconstruct_apply`` longhand —
    scale folded into the scalars first, cohort zero-padded to a
    FUSED_CHUNK multiple, each chunk's ``(r·v)·mask`` contributions
    materialized via the **core library** generator (``block_seed`` +
    ``random_for_shape``, not the kernels' factored chain) and reduced
    along the client axis by the spec's left fold c₀ + c₁ + … + c₁₅,
    chunks and blocks accumulated sequentially
    in float32, final bare add into x.  O(chunk·d) memory — a test
    oracle, not a serving path.  ``tests/test_kernel_differential.py``
    asserts the Pallas megakernel, the jnp mirror and this function
    agree to the bit.
    """
    from repro.kernels import ops
    from repro.kernels.reconstruct_apply import FUSED_CHUNK

    rs, scale = ops.fold_upload_weights(rs, server_lr, weights, mode,
                                        block_weights)
    rs = rs * jnp.asarray(scale, jnp.float32)
    n, k = rs.shape
    seeds = jnp.asarray(seeds, jnp.uint32)
    pad = (-n) % FUSED_CHUNK
    if pad:
        seeds = jnp.concatenate([seeds, jnp.zeros((pad,), jnp.uint32)])
        rs = jnp.concatenate([rs, jnp.zeros((pad, k), jnp.float32)])
    num_chunks = (n + pad) // FUSED_CHUNK
    masked = mode == ProjectionMode.BLOCK and k > 1

    leaves, treedef = jax.tree_util.tree_flatten(params)
    from repro.core.projection import leaf_layout
    layout = leaf_layout(params)
    total = layout[-1].end if layout else 0
    out = []
    for ll, leaf in zip(layout, leaves):
        x2d = leaf.reshape(1, -1) if leaf.ndim < 2 \
            else leaf.reshape(-1, leaf.shape[-1])
        rows, cols = x2d.shape
        lo, hi = ops.leaf_block_bounds(ll.offset, ll.size, total, k, mode)
        if masked:
            flat = (jnp.arange(rows, dtype=jnp.float32)[:, None] * float(cols)
                    + jnp.arange(cols, dtype=jnp.float32)[None, :])
        acc = jnp.zeros((rows, cols), jnp.float32)
        for b in range(k):
            mask = None
            if masked:
                mask = jnp.logical_and(flat >= lo[b],
                                       flat < hi[b]).astype(jnp.float32)
            for c in range(num_chunks):
                # The spec's left fold, added as each product is made so
                # only one chunk partial is alive (a list of 16 float32
                # copies of the largest leaf would crowd a 16 GB chip).
                partial = None
                for i in range(c * FUSED_CHUNK, (c + 1) * FUSED_CHUNK):
                    sj = block_seed(seeds[i], b)
                    v = random_for_shape((rows, cols), sj, ll.tag,
                                         distribution)
                    contrib = rs[i, b] * v
                    if mask is not None:
                        contrib = contrib * mask
                    partial = contrib if partial is None else partial + contrib
                acc = acc + partial
        y = (x2d.astype(jnp.float32) + acc).astype(leaf.dtype)
        out.append(y.reshape(leaf.shape))
    return jax.tree_util.tree_unflatten(treedef, out)


def qsgd_roundtrip_ref(tree: Any, seed, bits: int = 8):
    """Oracle ≡ :func:`repro.core.qsgd.quantize_tree` (same hash chain)."""
    return quantize_tree(tree, seed, bits)
