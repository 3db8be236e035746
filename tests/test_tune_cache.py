"""Autotune cache determinism (DESIGN §11, kernels/tune.py).

The tuning cache is the only piece of the fused path that touches
wall-clock at all, so these tests pin the properties that keep it out
of the numerics and out of flaky-CI territory:

* the cache key is a pure function of the workload signature — no
  wall-clock, pid, or hostname components — and cohort sizes bucket to
  powers of two so scheduler-driven cohort jitter reuses one entry;
* a cache miss sweeps every candidate exactly once; a hit returns the
  stored winner **without re-timing** (the injected measure would
  raise);
* the first cached winner is sticky: later sweeps (even ones whose
  measurements would prefer a different candidate) keep the stored
  entry, so every process that ever asks sees the same params;
* a second *process* reading the same cache file resolves the same
  winner byte-for-byte — the cross-process determinism regression.
"""
import json
import os
import subprocess
import sys

import pytest

from repro.kernels import tune


def _fake_measure(prefer_slab):
    """Deterministic fake timer: the preferred slab 'wins'."""
    calls = []

    def measure(cand):
        calls.append(dict(cand))
        return 0.001 if cand["row_slab"] == prefer_slab else 0.5

    measure.calls = calls
    return measure


def _raising_measure(cand):
    raise AssertionError(f"cache hit must not re-time, measured {cand}")


def test_cache_key_is_pure_and_bucketed():
    k1 = tune.cache_key("cpu", 512, 2048, 100, 3, "rademacher")
    # exact format: nothing ambient (time, pid, host) can hide in here
    assert k1 == "cpu|r512|c2048|n128|k3|rademacher|b32"
    # pure: same args → same key, every call
    assert k1 == tune.cache_key("cpu", 512, 2048, 100, 3, "rademacher")
    # cohort bucketing: 100 and 128 share an entry, 129 does not
    assert k1 == tune.cache_key("cpu", 512, 2048, 128, 3, "rademacher")
    assert k1 != tune.cache_key("cpu", 512, 2048, 129, 3, "rademacher")
    # every other signature component is significant
    assert k1 != tune.cache_key("tpu", 512, 2048, 100, 3, "rademacher")
    assert k1 != tune.cache_key("cpu", 512, 2048, 100, 1, "rademacher")
    assert k1 != tune.cache_key("cpu", 512, 2048, 100, 3, "gaussian")
    assert k1 != tune.cache_key("cpu", 512, 2048, 100, 3, "rademacher",
                                dtype_bits=16)


def test_cohort_bucket_floors_at_chunk():
    assert tune.cohort_bucket(1) == tune.cohort_bucket(16) == 16
    assert tune.cohort_bucket(17) == 32
    assert tune.cohort_bucket(1024) == 1024
    assert tune.cohort_bucket(1025) == 2048


def test_miss_sweeps_once_then_hit_never_retimes(tmp_path):
    path = str(tmp_path / "tune.json")
    m = _fake_measure(prefer_slab=64)
    won = tune.autotune_fused(512, 256, 100, 3, "rademacher",
                              backend="cpu", cache_path=path, measure=m)
    assert won == {"impl": "mirror", "block": None, "row_slab": 64}
    # the miss measured every CPU candidate exactly once
    assert len(m.calls) == len(tune._candidates("cpu", 512, 256, 100))
    # hit path: same winner, measure never called
    again = tune.autotune_fused(512, 256, 100, 3, "rademacher",
                                backend="cpu", cache_path=path,
                                measure=_raising_measure)
    assert again == won
    # bucketed cohort variation is also a hit
    assert tune.autotune_fused(512, 256, 128, 3, "rademacher",
                               backend="cpu", cache_path=path,
                               measure=_raising_measure) == won
    # cache-only lookup agrees
    assert tune.cached_fused_params(512, 256, 100, 3, "rademacher",
                                    backend="cpu", cache_path=path) == won


def test_first_cached_winner_is_sticky(tmp_path):
    path = str(tmp_path / "tune.json")
    first = tune.autotune_fused(512, 256, 100, 3, "rademacher",
                                backend="cpu", cache_path=path,
                                measure=_fake_measure(prefer_slab=16))
    assert first["row_slab"] == 16
    # a later sweep preferring a different candidate must NOT displace
    # the stored entry (hit short-circuits before measuring)
    later = tune.autotune_fused(512, 256, 100, 3, "rademacher",
                                backend="cpu", cache_path=path,
                                measure=_fake_measure(prefer_slab=256))
    assert later == first
    raw = json.load(open(path))
    assert raw[tune.cache_key("cpu", 512, 256, 100, 3, "rademacher")] == first


def test_candidates_prune_by_compile_budget():
    """Mirror candidates whose static chunk loop would unroll past the
    body budget are pruned, not timed: slab=16 at rows=512 survives a
    cohort-256 sweep (512 bodies) but not cohort-1024 (2048 bodies).
    The single-span mirror always remains legal."""
    slabs = lambda n: [c["row_slab"]
                       for c in tune._candidates("cpu", 512, 2048, n)]
    assert 16 in slabs(256)
    assert 16 not in slabs(1024)
    assert 64 in slabs(1024)          # 8 spans × 64 chunks = 512 bodies
    assert None in slabs(1 << 20)     # degenerate: fallback candidate


def test_tpu_candidates_are_distinct_tilings():
    """On the TPU a block is a budget that ``fused_plan`` fits to the
    leaf: every budget is a candidate for a 960-column leaf closed
    rows-along-lanes (240- and 480-column tiles, 128 or 256 rows), and
    budgets that give the same tiling of a short leaf are timed once."""
    blocks = lambda r, c: [tuple(x["block"])
                           for x in tune._candidates("tpu", r, c)]
    assert blocks(30720, 960) == list(tune.PALLAS_BLOCKS)
    assert blocks(32, 960) == [(128, 256), (128, 512)]


def test_cached_lookup_without_entry_is_none(tmp_path):
    assert tune.cached_fused_params(
        512, 256, 100, 3, "rademacher", backend="cpu",
        cache_path=str(tmp_path / "missing.json")) is None


def test_store_is_atomic_rename(tmp_path):
    path = str(tmp_path / "tune.json")
    tune._store(path, {"a": 1})
    # no tmp droppings survive the rename
    assert os.listdir(tmp_path) == ["tune.json"]
    assert tune._load(path) == {"a": 1}


_SUBPROC = """
import json, sys
sys.path.insert(0, {src!r})
from repro.kernels import tune

def raising(cand):
    raise AssertionError("subprocess must hit the cache, not re-time")

won = tune.autotune_fused(512, 256, 100, 3, "rademacher",
                          backend="cpu", cache_path={path!r},
                          measure=raising)
key = tune.cache_key("cpu", 512, 256, 100, 3, "rademacher")
print(json.dumps({{"won": won, "key": key}}))
"""


def test_cache_hit_deterministic_across_processes(tmp_path):
    """Seed the cache here; a fresh process resolves the identical winner
    from disk without re-timing — and derives the identical pure key."""
    path = str(tmp_path / "tune.json")
    won = tune.autotune_fused(512, 256, 100, 3, "rademacher",
                              backend="cpu", cache_path=path,
                              measure=_fake_measure(prefer_slab=64))
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run(
        [sys.executable, "-c", _SUBPROC.format(src=src, path=path)],
        capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr
    got = json.loads(out.stdout.strip().splitlines()[-1])
    assert got["won"] == won
    assert got["key"] == tune.cache_key("cpu", 512, 256, 100, 3, "rademacher")
