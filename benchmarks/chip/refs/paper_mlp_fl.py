"""Plain FedScalar rounds of the paper's MLP, written from the protocol.

One round k of a job with cohort seed s (all randomness below is the
deployment's, reproduced from its definition):

* cohort: the C = round(p N) clients drawn without replacement by
  ``numpy.random.RandomState(f(s, k)).choice(N, C)``, in id order, with
  f the job's splitmix fold of (s, k); each carries the Horvitz-Thompson
  weight 1 / (N p), p = C / N;
* client n's 32-bit seed folds (k, n) the same splitmix way, and its S
  minibatches of B rows come from ``jax.random`` keyed by (run seed, k,
  n), over data shard n mod #shards (shards cycled to equal length);
* local SGD: S steps of plain SGD on softmax cross-entropy of the
  64-24-12-10 tanh MLP (inputs scaled by 1/16); the update is the
  difference of the end and start parameters;
* upload: r = <update, v(seed)> with the Rademacher direction of
  ``refs.directions`` over every leaf;
* channel: per upload a lognormal rate fluctuation (sigma, mean-one),
  then a loss draw with probability ``drop_prob``, both from one
  ``numpy.random.RandomState(run seed)`` stream that runs on across
  rounds and jobs; the round closes at the ceil(q C)-th arrival of the
  uploads not lost (latency is inversely proportional to the rate);
* server: x <- x + lr_s * sum over on-time uploads of w r v.

All arithmetic is float32 with matrix products at ``HIGHEST`` precision,
or, for the control, every array in the narrower type it is given.
Nothing here is imported from the system under test.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from refs.directions import leaf_seed, rademacher, view2d

__all__ = ["ReferenceFL", "test_loss"]

_MASK = 0xFFFFFFFF


def _fold(seed: int, k: int) -> int:
    x = ((seed * 0x9E3779B9) & _MASK) ^ (k & _MASK)
    x ^= x >> 16
    return (x * 0x21F0AAAD) & _MASK


def cohort(job_seed: int, k: int, population: int, size: int):
    rng = np.random.RandomState(_fold(job_seed, k))
    ids = np.sort(rng.choice(population, size=size, replace=False))
    weight = 1.0 / (population * (size / population))
    return ids.astype(np.int64), np.full(size, weight)


def client_seeds(k: int, ids: np.ndarray) -> np.ndarray:
    k32 = np.uint32(k)
    n = ids.astype(np.uint32)
    with np.errstate(over="ignore"):
        x = ((k32 * np.uint32(0x9E3779B9)) ^ (n * np.uint32(0x85EBCA6B))
             ^ np.uint32(0x5EED))
        x = x ^ (x >> np.uint32(16))
        x = x * np.uint32(0x21F0AAAD)
        x = x ^ (x >> np.uint32(15))
    return x.astype(np.uint32)


def _mlp_logits(params, x, prec):
    h = x / 16.0
    n = len(params) // 2
    for i in range(n):
        h = jnp.dot(h, params[f"w{i}"], precision=prec) + params[f"b{i}"]
        if i < n - 1:
            h = jnp.tanh(h)
    return h


def _loss(params, x, y, prec):
    logp = jax.nn.log_softmax(_mlp_logits(params, x, prec), axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=-1))


def test_loss(params, x, y, prec=jax.lax.Precision.HIGHEST):
    return float(jax.jit(_loss, static_argnums=3)(params, x, y, prec))


class ReferenceFL:
    """Replays a run's first rounds: ``round(params, job_seed, k)``."""

    def __init__(self, shards_x, shards_y, *, run_seed: int, population: int,
                 cohort_size: int, local_steps: int, batch_size: int,
                 local_lr: float, server_lr: float, quorum_frac: float,
                 lognormal_sigma: float, drop_prob: float, dtype=jnp.float32):
        self.sx = jnp.asarray(shards_x, dtype)
        self.sy = jnp.asarray(shards_y, jnp.int32)
        self.run_seed = run_seed
        self.population, self.size = population, cohort_size
        self.S, self.B = local_steps, batch_size
        self.lr, self.lr_s = local_lr, server_lr
        self.q, self.sigma, self.p_drop = quorum_frac, lognormal_sigma, drop_prob
        self.dtype = dtype
        self.channel = np.random.RandomState(run_seed)
        prec = jax.lax.Precision.HIGHEST
        S, B, lr = self.S, self.B, self.lr
        num_shards, n_per = self.sx.shape[0], self.sx.shape[1]

        def one_client(params, k, cid, seed, sx, sy, root):
            key = jax.random.fold_in(jax.random.fold_in(root, k), cid)
            idx = jax.random.randint(key, (S, B), 0, n_per)
            shard = (cid % num_shards).astype(jnp.int32)
            bx, by = sx[shard][idx], sy[shard][idx]
            p = params
            for s in range(S):
                g = jax.grad(_loss)(p, bx[s], by[s], prec)
                p = jax.tree_util.tree_map(
                    lambda w, gg: (w - lr * gg).astype(dtype), p, g)
            delta = jax.tree_util.tree_map(lambda a, b: a - b, p, params)
            r = jnp.float32(0.0)
            for ordinal, leaf in enumerate(jax.tree_util.tree_leaves(delta)):
                rows, cols = view2d(leaf.shape)
                v = rademacher(leaf_seed(seed, ordinal),
                               jnp.arange(rows, dtype=jnp.uint32)[:, None],
                               jnp.arange(cols, dtype=jnp.uint32)[None, :])
                r = r + jnp.sum(leaf.reshape(rows, cols).astype(jnp.float32)
                                * v)
            return r

        # The data and the run's key are arguments, not constants, so
        # that one compiled program serves every seed.
        self._root = jax.random.PRNGKey(run_seed)
        self._uploads = jax.jit(jax.vmap(
            one_client, in_axes=(None, None, 0, 0, None, None, None)))

        def apply(params, seeds, coef):
            leaves, treedef = jax.tree_util.tree_flatten(params)
            out = []
            for ordinal, leaf in enumerate(leaves):
                rows, cols = view2d(leaf.shape)
                v = rademacher(leaf_seed(seeds, ordinal)[:, None, None],
                               jnp.arange(rows, dtype=jnp.uint32)[None, :, None],
                               jnp.arange(cols, dtype=jnp.uint32)[None, None, :])
                g = jnp.sum(coef[:, None, None] * v, axis=0)
                y = leaf.reshape(rows, cols).astype(jnp.float32) + self.lr_s * g
                out.append(y.astype(dtype).reshape(leaf.shape))
            return jax.tree_util.tree_unflatten(treedef, out)

        self._apply = jax.jit(apply)

    def on_time(self, c: int) -> np.ndarray:
        fluct = self.channel.lognormal(mean=-0.5 * self.sigma ** 2,
                                       sigma=self.sigma, size=c)
        lost = (self.channel.random_sample(c) < self.p_drop
                if self.p_drop > 0 else np.zeros(c, bool))
        latency = 1.0 / fluct
        need = max(1, int(math.ceil(self.q * c)))
        landed = np.sort(latency[~lost])
        cut = landed[need - 1] if len(landed) >= need else (
            landed[-1] if len(landed) else 0.0)
        return (~lost) & (latency <= cut)

    def round(self, params, job_seed: int, k: int, *, fault: str | None = None):
        """→ (params after round k, uploads applied)."""
        ids, w = cohort(job_seed, k, self.population, self.size)
        seeds = client_seeds(k, ids)
        params = jax.tree_util.tree_map(lambda a: a.astype(self.dtype), params)
        rs = np.asarray(self._uploads(params, jnp.uint32(k),
                                      jnp.asarray(ids, jnp.uint32),
                                      jnp.asarray(seeds), self.sx, self.sy,
                                      self._root))
        ok = self.on_time(len(ids))
        coef = (rs * w.astype(np.float32)).astype(np.float32)[ok]
        s = seeds[ok]
        if fault == "half_batch":
            half = len(s) // 2
            s, coef = s[:half], coef[:half] * np.float32(len(coef) / half)
        elif fault == "altered_answer":
            coef = coef.copy()
            coef[0] = -coef[0]
        out = self._apply(params, jnp.asarray(s), jnp.asarray(coef))
        if fault == "unchanged":
            out = params
        return out, int(ok.sum())
