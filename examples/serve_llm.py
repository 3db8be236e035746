"""Batched serving of a (reduced) assigned model: prefill + decode loop.

Exercises the exact prefill/decode steps the decode_32k / long_500k
dry-run shapes lower — ring KV caches (or SSM state), greedy sampling —
at CPU-friendly sizes.

    PYTHONPATH=src python examples/serve_llm.py --arch falcon-mamba-7b \
        --prompt-len 48 --gen 16
"""
import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.registry import ARCH_IDS, get_arch
from repro.launch.serve import make_decode_step, make_prefill_step


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-360m", choices=list(ARCH_IDS))
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=48)
    ap.add_argument("--gen", type=int, default=16)
    args = ap.parse_args()

    arch = get_arch(args.arch, reduced=True)
    cfg = arch.cfg
    params = arch.init(jax.random.PRNGKey(0))
    rng = np.random.RandomState(0)
    batch = {"tokens": jnp.asarray(
        rng.randint(0, cfg.vocab_size, size=(args.batch, args.prompt_len))
        .astype(np.int32))}
    if cfg.frontend == "vision":
        batch["embeds"] = jnp.asarray(
            rng.randn(args.batch, cfg.num_frontend_tokens, cfg.d_model)
            .astype(np.float32) * 0.02, cfg.jnp_dtype)
    if cfg.frontend == "audio":
        batch["embeds"] = jnp.asarray(
            rng.randn(args.batch, cfg.encoder_seq, cfg.d_model)
            .astype(np.float32) * 0.02, cfg.jnp_dtype)

    capacity = args.prompt_len + args.gen + 8
    prefill = jax.jit(make_prefill_step(arch, capacity=capacity))
    decode = jax.jit(make_decode_step(arch))

    t0 = time.time()
    token, caches = prefill(params, batch)
    print(f"prefill({args.batch}×{args.prompt_len}) → first tokens "
          f"{np.asarray(token).tolist()}  ({time.time() - t0:.2f}s)")

    toks = [token]
    pos = args.prompt_len
    t0 = time.time()
    for i in range(args.gen):
        token, caches = decode(params, token.reshape(args.batch, 1), caches,
                               jnp.int32(pos + i))
        toks.append(token.reshape(args.batch))
    dt = (time.time() - t0) / args.gen
    gen = np.stack([np.asarray(t).reshape(args.batch) for t in toks], axis=1)
    print(f"generated {args.gen} tokens/seq at {dt * 1e3:.1f} ms/token")
    for b in range(args.batch):
        print(f"  seq{b}: {gen[b].tolist()}")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
