"""Centralized (non-federated) training baseline with Adam.

The upper bound FL methods are compared against: the same MLP/digits
task trained centrally with Adam + cosine schedule — exercises the
`repro.optim` substrate end-to-end and gives the accuracy ceiling for
the §III experiment (FL methods approach it as K grows).

    PYTHONPATH=src python examples/centralized_baseline.py [--steps 600]
"""
import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro.data import load_digits, train_test_split_arrays
from repro.models.mlp_classifier import init_mlp, mlp_accuracy, mlp_loss
from repro.optim import adam, warmup_cosine


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=600)
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-3)
    args = ap.parse_args()

    x, y = load_digits()
    xtr, ytr, xte, yte = train_test_split_arrays(x, y)
    xtr, ytr = jnp.asarray(xtr), jnp.asarray(ytr)
    xte, yte = jnp.asarray(xte), jnp.asarray(yte)

    params = init_mlp()
    sched = warmup_cosine(args.lr, warmup_steps=50, total_steps=args.steps)
    init_opt, _ = adam(args.lr)
    state = init_opt(params)

    @jax.jit
    def step(params, state, key, lr):
        idx = jax.random.randint(key, (args.batch,), 0, xtr.shape[0])
        batch = (xtr[idx], ytr[idx])
        loss, grads = jax.value_and_grad(mlp_loss)(params, batch)
        _, update = adam(lr)
        params, state = update(grads, state, params)
        return params, state, loss

    key = jax.random.PRNGKey(0)
    for k in range(args.steps):
        key, sub = jax.random.split(key)
        params, state, loss = step(params, state, sub, float(sched(k)))
        if k % 100 == 0 or k == args.steps - 1:
            acc = mlp_accuracy(params, xte, yte)
            print(f"step {k:4d}: loss={float(loss):.4f} "
                  f"test_acc={float(acc):.4f}")
    print(f"\ncentralized ceiling: {float(mlp_accuracy(params, xte, yte)):.4f} "
          f"(FL methods at K=1500 reach ≈0.91–0.93)")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
