"""Pallas TPU kernel: causal flash attention (forward).

The quadratic-attention working set is what made the naive prefill
lower at 527 GiB/device (§Perf pair 3); the pure-JAX blockwise path
fixed the memory, and this kernel is the TPU-native version of that
same online-softmax algorithm with explicit VMEM tiling:

* grid = (batch·kv_heads, q_blocks); the kv loop runs *inside* the
  kernel body (fori_loop) so the (q_block × kv_block) score tile and
  the (q_block × head_dim) accumulator never leave VMEM,
* block shapes are MXU-aligned (q_block × head_dim and
  kv_block × head_dim tiles, head_dim a multiple of 128 ideally),
* causal masking by absolute positions; a sliding ``window`` prunes
  nothing structurally (TPU grids are static) but masks correctly.

GQA is handled by folding the query-group axis into the q-block rows:
the kernel sees Q as (B·K, S·G, hd) against K/V of (B·K, T, hd).

Validated in interpret mode against ``ref.flash_attention_ref`` (the
einsum oracle) over shape/dtype/window sweeps in
``tests/test_flash_kernel.py``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_attention_call"]

DEFAULT_Q_BLOCK = 256
DEFAULT_KV_BLOCK = 256
_NEG = -1e30


def _flash_kernel(qpos_ref, kpos_ref, q_ref, k_ref, v_ref, o_ref, *,
                  kv_block: int, kv_len: int, causal: bool, window: int,
                  group: int):
    """One (batch·kv_head, q_block) program: loop kv blocks in VMEM.

    q_ref: (bq·G, hd) — query rows for this block, groups folded in.
    k_ref/v_ref: (T, hd) — this (batch, kv_head)'s full K/V stream
    (delivered block-row by the BlockSpec index map; the fori_loop
    walks it in kv_block chunks via pl.ds).
    """
    _, bq_g, hd = q_ref.shape
    bq = bq_g // group
    q = q_ref[0].astype(jnp.float32)                      # (bq·G, hd)
    qpos = qpos_ref[...]                                  # (bq,) int32
    # per-row absolute positions (group-folded rows share a position)
    rowpos = jnp.repeat(qpos, group)                      # (bq·G,)

    nkv = kv_len // kv_block

    def body(i, carry):
        acc, m, l = carry
        k_blk = k_ref[0, pl.ds(i * kv_block, kv_block), :]   # (kvb, hd)
        v_blk = v_ref[0, pl.ds(i * kv_block, kv_block), :]
        kp = kpos_ref[pl.ds(i * kv_block, kv_block)]         # (kvb,)
        sc = jax.lax.dot_general(
            q, k_blk.astype(jnp.float32),
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * (hd ** -0.5)
        ok = (kp >= 0)[None, :]
        if causal:
            ok = jnp.logical_and(ok, kp[None, :] <= rowpos[:, None])
        if window:
            ok = jnp.logical_and(ok, kp[None, :] > rowpos[:, None] - window)
        sc = jnp.where(ok, sc, _NEG)
        m_new = jnp.maximum(m, jnp.max(sc, axis=-1))
        p = jnp.exp(sc - m_new[:, None])  # fedlint: allow[FS004] online-softmax recurrence; flash conformance is allclose, not bitwise (DESIGN §8)
        corr = jnp.exp(m - m_new)  # fedlint: allow[FS004] online-softmax rescale, same allclose domain
        l_new = l * corr + jnp.sum(p, axis=-1)  # fedlint: allow[FS004] FMA here only moves ulps; tolerance-tested
        pv = jax.lax.dot_general(
            p, v_blk.astype(jnp.float32),
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        acc_new = acc * corr[:, None] + pv  # fedlint: allow[FS004] FMA here only moves ulps; tolerance-tested
        return acc_new, m_new, l_new

    acc0 = jnp.zeros((bq_g, hd), jnp.float32)
    m0 = jnp.full((bq_g,), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((bq_g,), jnp.float32)
    acc, m, l = jax.lax.fori_loop(0, nkv, body, (acc0, m0, l0))
    out = acc / jnp.maximum(l[:, None], 1e-30)
    o_ref[0] = out.astype(o_ref.dtype)


def flash_attention_call(
    q: jax.Array,            # (B, S, H, hd)
    k: jax.Array,            # (B, T, K, hd)
    v: jax.Array,            # (B, T, K, hd)
    qpos: jax.Array,         # (S,) int32 absolute positions
    kpos: jax.Array,         # (T,) int32 (−1 = empty slot)
    *,
    causal: bool = True,
    window: int = 0,
    q_block: int = DEFAULT_Q_BLOCK,
    kv_block: int = DEFAULT_KV_BLOCK,
    interpret: bool | None = None,
) -> jax.Array:
    """→ (B, S, H, hd).  S must be divisible by q_block, T by kv_block
    (ops-level callers pad; kpos −1 masks padded keys)."""
    b, s, h, hd = q.shape
    t, kh = k.shape[1], k.shape[2]
    g = h // kh
    assert s % q_block == 0 and t % kv_block == 0, (q.shape, k.shape)
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    if interpret:
        interpret = pltpu.InterpretParams()

    # fold: Q → (B·K, S, G·hd-rows): arrange as (B·K, S·G, hd)
    qf = (q.reshape(b, s, kh, g, hd).transpose(0, 2, 1, 3, 4)
          .reshape(b * kh, s * g, hd))
    kf = k.transpose(0, 2, 1, 3).reshape(b * kh, t, hd)
    vf = v.transpose(0, 2, 1, 3).reshape(b * kh, t, hd)

    kern = functools.partial(
        _flash_kernel, kv_block=kv_block, kv_len=t, causal=causal,
        window=window, group=g)
    out = pl.pallas_call(
        kern,
        grid=(b * kh, s // q_block),
        in_specs=[
            pl.BlockSpec((q_block,), lambda bh, i: (i,)),        # qpos
            pl.BlockSpec((t,), lambda bh, i: (0,)),              # kpos
            pl.BlockSpec((1, q_block * g, hd), lambda bh, i: (bh, i, 0)),
            pl.BlockSpec((1, t, hd), lambda bh, i: (bh, 0, 0)),
            pl.BlockSpec((1, t, hd), lambda bh, i: (bh, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, q_block * g, hd), lambda bh, i: (bh, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b * kh, s * g, hd), q.dtype),
        interpret=interpret,
    )(qpos.astype(jnp.int32), kpos.astype(jnp.int32), qf, kf, vf)

    return (out.reshape(b, kh, s, g, hd).transpose(0, 2, 1, 3, 4)
            .reshape(b, s, h, hd))
