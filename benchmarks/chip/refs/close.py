"""Plain round close: y = x + lr * sum_n w_n r_n v_n, per leaf.

The sum is taken in float32 over the clients in blocks of ``CHUNK``,
and rounded once to the parameter type (or, for the control, to the
type it is given).  Rounding of the sum differs from the system's own
association by float32 round-off, which moves a result by at most one
step of the parameter type where it lies next to a rounding boundary.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from refs.directions import leaf_seed, rademacher, view2d

__all__ = ["close_tree", "gap_steps"]

CHUNK = 8


@functools.partial(jax.jit, static_argnames=("ordinal", "out_dtype"))
def _close_leaf(x, seeds, coef, ordinal: int, out_dtype):
    rows, cols = view2d(x.shape)
    n = seeds.shape[0]
    pad = (-n) % CHUNK
    seeds = jnp.concatenate([seeds, jnp.zeros((pad,), jnp.uint32)])
    coef = jnp.concatenate([coef, jnp.zeros((pad,), jnp.float32)])
    ls = leaf_seed(seeds, ordinal)
    row = jnp.arange(rows, dtype=jnp.uint32)[:, None]
    col = jnp.arange(cols, dtype=jnp.uint32)[None, :]

    def body(c, acc):
        part = None
        for i in range(CHUNK):
            t = coef[c * CHUNK + i] * rademacher(ls[c * CHUNK + i], row, col)
            part = t if part is None else part + t
        return acc + part

    acc = jax.lax.fori_loop(0, (n + pad) // CHUNK, body,
                            jnp.zeros((rows, cols), jnp.float32))
    y = x.reshape(rows, cols).astype(jnp.float32) + acc
    return y.astype(out_dtype).reshape(x.shape)


def close_tree(params, seeds, rs, weights, server_lr: float, out_dtype=None):
    """→ the closed tree; ``rs`` (N,) scalars, ``weights`` (N,) float.

    ``out_dtype`` None keeps each leaf's type; the control passes a
    narrower one and gets it back widened to the leaf's type.
    """
    coef = (jnp.asarray(rs, jnp.float32) * jnp.asarray(weights, jnp.float32)
            * jnp.float32(server_lr))
    seeds = jnp.asarray(seeds, jnp.uint32)
    leaves, treedef = jax.tree_util.tree_flatten(params)
    out = []
    for ordinal, leaf in enumerate(leaves):
        dt = leaf.dtype if out_dtype is None else out_dtype
        y = _close_leaf(leaf, seeds, coef, ordinal, jnp.dtype(dt))
        out.append(y.astype(leaf.dtype))
    return jax.tree_util.tree_unflatten(treedef, out)


@jax.jit
def _gap_leaf(got, want, prev, floor):
    nmant = jnp.finfo(want.dtype).nmant
    g = got.astype(jnp.float32)
    w = want.astype(jnp.float32)
    m = jnp.maximum(jnp.abs(w), jnp.abs(prev.astype(jnp.float32)))
    m = jnp.maximum(m, jnp.maximum(floor, jnp.float32(2.0 ** -126)))
    _, e = jnp.frexp(m)
    step = jnp.ldexp(jnp.float32(1.0), e - 1 - nmant)
    gap = jnp.max(jnp.abs(g - w) / step)
    return jnp.where(jnp.all(jnp.isfinite(g)), gap, jnp.inf)


def gap_steps(got, want, prev, floor: float) -> float:
    """Widest gap between two closed trees, in steps of the parameter
    type at the largest of the element's magnitude before the close, its
    magnitude after it, and ``floor``, the root-sum-square of the
    round's coefficients: the typical size of the update, below which
    float32 round-off of the sum, not the parameter type, sets the gap.
    A non-finite element reads as infinity."""
    floor = jnp.float32(floor)
    gaps = [float(_gap_leaf(g, w, p, floor)) for g, w, p in zip(
        jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want),
        jax.tree_util.tree_leaves(prev))]
    return max(gaps) if gaps else 0.0
