"""End-to-end FedScalar training of a (reduced) assigned LLM on CPU.

Runs the SAME production `train_step` the multi-pod dry-run lowers —
sequential virtual clients, S local SGD steps, scalar projection,
seeded server reconstruction — on a reduced variant of any assigned
architecture, over a synthetic token stream, and logs round metrics.

    PYTHONPATH=src python examples/federated_llm.py --arch smollm-360m \
        --rounds 30 [--clients 4] [--steps 2]

The checkpointing substrate is exercised at the end (save + restore).
"""
import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import restore_checkpoint, save_checkpoint
from repro.configs.registry import ARCH_IDS, get_arch
from repro.launch.train import FLRunConfig, make_train_step


def synthetic_token_stream(vocab: int, batch: int, seq: int, round_idx: int):
    """Deterministic Zipf-ish token batches (a stand-in corpus)."""
    rng = np.random.RandomState(1000 + round_idx)
    probs = 1.0 / np.arange(1, vocab + 1) ** 1.1
    probs /= probs.sum()
    toks = rng.choice(vocab, size=(batch, seq + 1), p=probs).astype(np.int32)
    return {"tokens": jnp.asarray(toks[:, :-1]),
            "labels": jnp.asarray(toks[:, 1:])}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-360m", choices=list(ARCH_IDS))
    ap.add_argument("--rounds", type=int, default=30)
    ap.add_argument("--clients", type=int, default=4)
    ap.add_argument("--steps", type=int, default=2)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=0.05)
    args = ap.parse_args()

    arch = get_arch(args.arch, reduced=True)
    print(f"arch={arch.cfg.name} ({arch.cfg.arch_type}), vocab={arch.cfg.vocab_size}")
    params = arch.init(jax.random.PRNGKey(0))
    d = sum(p.size for p in jax.tree_util.tree_leaves(params))
    print(f"d = {d:,} params → FedScalar uplink: 64 bits/client/round "
          f"(FedAvg would be {32 * d:,})")

    fl = FLRunConfig(num_virtual_clients=args.clients, local_steps=args.steps,
                     local_lr=args.lr)
    step = jax.jit(make_train_step(arch, fl))

    for k in range(args.rounds):
        batch = synthetic_token_stream(arch.cfg.vocab_size, args.batch,
                                       args.seq, k)
        t0 = time.time()
        params, metrics = step(params, batch, jnp.int32(k))
        if k % 5 == 0 or k == args.rounds - 1:
            print(f"round {k:3d}: loss={float(metrics['loss']):.4f} "
                  f"r_rms={float(metrics['r_rms']):.3g} "
                  f"uplink={int(metrics['uploaded_scalars'])} scalars "
                  f"({time.time() - t0:.2f}s)")

    path = save_checkpoint("experiments/fedllm_ckpt", params,
                           step=args.rounds, metadata={"arch": args.arch})
    like = jax.tree_util.tree_map(
        lambda w: jax.ShapeDtypeStruct(w.shape, w.dtype), params)
    _, restored_step, meta = restore_checkpoint(path, like)
    print(f"checkpoint ok: {path} (step={restored_step}, meta={meta})")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
