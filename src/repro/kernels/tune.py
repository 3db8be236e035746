"""Autotuner for the fused reconstruct+apply megakernel (DESIGN §11).

The fused path has exactly two performance knobs, both proven
bits-invariant (``reconstruct_apply`` module docstring):

* Pallas ``(br, bc)`` tile budget — VMEM working set vs grid overhead;
  ``reconstruct_apply.fused_plan`` fits it to each leaf's orientation;
* the jnp mirror's ``row_slab`` height — L1/L2 residency of the
  (slab × cols) contribution tensor on CPU.

Everything that *could* move bits (FUSED_CHUNK, the chunk-axis reduce,
the scale fold) is pinned by the numeric spec and is deliberately not
sweepable here, so a tuned configuration is always safe to swap in.

Winners are cached in a JSON file keyed by
:func:`cache_key` — a **pure function** of the workload signature
``(backend, rows, cols, cohort bucket, k, distribution, dtype bits)``.
No wall-clock, hostname, or process state enters the key, so every
process that asks for the same workload reads the same entry; a cache
hit returns the stored winner without re-timing (asserted in
``tests/test_tune_cache.py``).  Writes are atomic (tmp file + rename)
so concurrent tuners never tear the file.

The cohort size is bucketed to the next power of two (min FUSED_CHUNK):
throughput is smooth in N, and bucketing keeps the cache from growing
one entry per cohort fluctuation under the admission-controlled
scheduler's variable round sizes.
"""
from __future__ import annotations

import json
import os
import statistics
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.reconstruct_apply import (
    DEFAULT_FUSED_BLOCK,
    FUSED_CHUNK,
    fused_plan,
    fused_reconstruct_apply,
)

__all__ = [
    "cache_key",
    "cohort_bucket",
    "autotune_fused",
    "cached_fused_params",
    "DEFAULT_CACHE_PATH",
    "MIRROR_ROW_SLABS",
    "PALLAS_BLOCKS",
]

# The serving cache, kept with the code and only ever read here, so the
# tile a run picks depends only on files in the checkout.  Sweeps write
# to the path their caller names; promoting a winner measured on a
# device into this file is a deliberate, committed step.  Absent means
# the default tiles.
DEFAULT_CACHE_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                  "fused_tune.json")

# Candidate spaces.  Mirror slabs: None = whole matrix in one span.
MIRROR_ROW_SLABS = (None, 16, 64, 256)
PALLAS_BLOCKS = ((128, 256), (256, 256), (128, 512), (256, 512))

# The mirror's chunk loop is a *static Python loop* (a bit-domain
# requirement — reconstruct_apply module docstring), so XLA compiles
# (rows/slab spans) × (cohort/16 chunks) distinct bodies.  Candidates
# past this budget pay minutes of compile for a sub-millisecond win
# (slab=16 at cohort 1024 is ~4 min on one CPU core) and are pruned
# from the sweep rather than timed.
_MAX_UNROLLED_BODIES = 1024


def cohort_bucket(cohort: int) -> int:
    """Next power of two ≥ cohort, floored at FUSED_CHUNK."""
    b = FUSED_CHUNK
    while b < cohort:
        b *= 2
    return b


def cache_key(backend: str, rows: int, cols: int, cohort: int, k: int,
              distribution: str, dtype_bits: int = 32) -> str:
    """Deterministic cache key — pure in its arguments, no ambient state."""
    return (f"{backend}|r{int(rows)}|c{int(cols)}|n{cohort_bucket(cohort)}"
            f"|k{int(k)}|{distribution}|b{int(dtype_bits)}")


def _load(path: str) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError):
        return {}


def _store(path: str, cache: dict) -> None:
    d = os.path.dirname(path)
    if d:
        os.makedirs(d, exist_ok=True)
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump(cache, f, indent=0, sort_keys=True)
    os.replace(tmp, path)


def _candidates(backend: str, rows: int, cols: int,
                cohort: int = FUSED_CHUNK) -> list[dict]:
    if backend == "tpu":
        # A block is a budget that ``fused_plan`` fits to the leaf, in
        # its orientation; blocks that give the same tiling are one
        # candidate.
        plans = {}
        for b in PALLAS_BLOCKS:
            plans.setdefault(fused_plan(rows, cols, b), b)
        return [{"impl": "pallas", "block": list(b), "row_slab": None}
                for b in plans.values()]
    # CPU (and any non-TPU backend): the mirror is the serving path —
    # interpret-mode Pallas is a conformance vehicle, not a candidate.
    chunks = max(1, cohort_bucket(cohort) // FUSED_CHUNK)
    cands = []
    for s in MIRROR_ROW_SLABS:
        if s is not None and s > rows:
            continue
        spans = 1 if s is None else -(-rows // s)
        if spans * chunks > _MAX_UNROLLED_BODIES:
            continue
        cands.append({"impl": "mirror", "block": None, "row_slab": s})
    if not cands:   # huge cohort: the single-span mirror is always legal
        cands = [{"impl": "mirror", "block": None, "row_slab": None}]
    return cands


def _default_measure(rows: int, cols: int, cohort: int, k: int,
                     distribution: str, dtype_bits: int):
    """Median-of-3 wall time of one fused round close under a candidate."""
    dtype = {16: jnp.bfloat16, 32: jnp.float32}.get(dtype_bits, jnp.float32)
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(rows, cols), dtype)
    seeds = jnp.asarray(rng.randint(0, 2**32, cohort, dtype=np.uint32))
    rs = jnp.asarray(rng.randn(cohort, k).astype(np.float32))

    def measure(cand: dict) -> float:
        use_pallas = cand["impl"] == "pallas"
        block = tuple(cand["block"]) if cand["block"] else DEFAULT_FUSED_BLOCK
        fn = jax.jit(lambda xx, ss, rr: fused_reconstruct_apply(
            xx, ss, rr, 0, 0.01, distribution, block=block,
            use_pallas=use_pallas, row_slab=cand["row_slab"]))
        fn(x, seeds, rs).block_until_ready()   # compile + warm
        ts = []
        for _ in range(3):
            t0 = time.perf_counter()
            fn(x, seeds, rs).block_until_ready()
            ts.append(time.perf_counter() - t0)
        return statistics.median(ts)

    return measure


def cached_fused_params(rows: int, cols: int, cohort: int, k: int,
                        distribution: str, dtype_bits: int = 32,
                        backend: str | None = None,
                        cache_path: str = DEFAULT_CACHE_PATH) -> dict | None:
    """Cache-only lookup: the stored winner, or None.  Never times."""
    if backend is None:
        backend = jax.default_backend()
    key = cache_key(backend, rows, cols, cohort, k, distribution, dtype_bits)
    return _load(cache_path).get(key)


def autotune_fused(rows: int, cols: int, cohort: int, k: int,
                   distribution: str = "rademacher", dtype_bits: int = 32,
                   backend: str | None = None, *, cache_path: str,
                   measure=None) -> dict:
    """Winner params for a fused workload, sweeping once and caching.

    Returns ``{"impl": "pallas"|"mirror", "block": [br, bc]|None,
    "row_slab": int|None}``.  A cache hit short-circuits the sweep
    entirely — the stored winner is returned as-is, making repeat calls
    (and calls from other processes) deterministic and cheap.
    ``cache_path`` is where the sweep reads and writes its winners; it
    has no default, so no sweep writes the serving cache
    (``DEFAULT_CACHE_PATH``).  ``measure`` is injectable for tests; the
    default times the real fused call (median of 3 after warmup).
    """
    if backend is None:
        backend = jax.default_backend()
    key = cache_key(backend, rows, cols, cohort, k, distribution, dtype_bits)
    cache = _load(cache_path)
    hit = cache.get(key)
    if hit is not None:
        return hit
    cands = _candidates(backend, rows, cols, cohort)
    if measure is None:
        measure = _default_measure(rows, cols, cohort_bucket(cohort), k,
                                   distribution, dtype_bits)
    timed = [(measure(c), i) for i, c in enumerate(cands)]
    best = cands[min(timed)[1]]
    # Re-read before writing: another process may have added keys while
    # we were timing; last writer wins per key, which is fine — any
    # measured winner is valid, and the *first* cached one is what every
    # later reader deterministically sees.
    cache = _load(cache_path)
    cache.setdefault(key, best)
    _store(cache_path, cache)
    return cache[key]
