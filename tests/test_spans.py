"""The FL round's host spans (``fed.*``) in a profiler trace of a tiny
synchronous scheduler job: the documented names and stats, each stage
nested in its round, and no span per upload."""
import collections
import glob
import os

import jax
import pytest

from repro.fed.runtime import RuntimeConfig, SchedulerConfig, run_federation
from repro.models.mlp_classifier import init_mlp

SPANS = {"fed.round", "fed.sample_cohort", "fed.catch_up",
         "fed.compute_cohort", "fed.device_wait", "fed.transmit",
         "fed.offer_uploads", "fed.close_round", "fed.apply_round",
         "fed.close_digest", "fed.evaluate"}
STATS = {"fed.round": {"round"}, "fed.apply_round": {"rows"}}
ROUNDS = 3
CHUNK = 8


def _trace_job(log_dir, clients, xte, yte, cohort: int,
               projection_mode: str = "full"):
    """One traced job of ``cohort`` clients per round → its ``fed.*``
    events as (name, start, end, stats), by start."""
    cfg = RuntimeConfig(rounds=ROUNDS, population=64,
                        participation=cohort / 64, client_chunk=CHUNK,
                        projection_mode=projection_mode,
                        downlink_mode="digest", eval_every=2,
                        scheduler=SchedulerConfig(mode="sync",
                                                  quorum_frac=0.8))
    jax.profiler.start_trace(str(log_dir))
    try:
        run_federation(cfg, init_mlp(), clients, xte, yte)
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(log_dir), "plugins", "profile", "*",
                                   "*.xplane.pb"))
    data = jax.profiler.ProfileData.from_file(path)
    return sorted(((ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
                    dict(ev.stats))
                   for plane in data.planes if plane.name.startswith("/host:")
                   for line in plane.lines for ev in line.events
                   if ev.name.startswith("fed.")), key=lambda e: e[1])


def _by_round(events):
    """→ [(round event, [events nested in it])]."""
    rounds = [e for e in events if e[0] == "fed.round"]
    return [(r, [e for e in events if e[0] != "fed.round"
                 and r[1] <= e[1] and e[2] <= r[2]]) for r in rounds]


@pytest.fixture(scope="module")
def digits8():
    from repro.data import (
        load_digits,
        make_client_datasets,
        train_test_split_arrays,
    )
    x, y = load_digits(n_samples=400)
    xtr, ytr, xte, yte = train_test_split_arrays(x, y)
    return make_client_datasets(xtr, ytr, 8), xte, yte


@pytest.fixture(scope="module")
def traced(tmp_path_factory, digits8):
    """The job traced at cohorts of one and of four compute chunks."""
    clients, xte, yte = digits8
    return {c: _trace_job(tmp_path_factory.mktemp(f"trace{c}"), clients,
                          xte, yte, c)
            for c in (CHUNK, 4 * CHUNK)}


def test_span_names_and_stats_are_the_documented_ones(traced):
    events = traced[4 * CHUNK]
    assert {e[0] for e in events} == SPANS
    for name, _, _, stats in events:
        assert set(stats) == STATS.get(name, set()), name
    assert [r[3]["round"] for r in events if r[0] == "fed.round"] \
        == list(range(ROUNDS))


def test_every_stage_span_nests_in_its_round(traced):
    events = traced[4 * CHUNK]
    rounds = _by_round(events)
    assert len(rounds) == ROUNDS
    nested = sum(len(inner) for _, inner in rounds)
    assert nested == len(events) - ROUNDS
    for _, inner in rounds:
        names = {e[0] for e in inner}
        assert {"fed.sample_cohort", "fed.catch_up", "fed.compute_cohort",
                "fed.transmit", "fed.offer_uploads", "fed.close_round",
                "fed.apply_round", "fed.close_digest"} <= names


def test_spans_per_round_grow_only_by_one_wait_per_chunk(traced):
    """Four times the cohort adds three compute chunks, so three
    ``fed.device_wait`` spans per round, and nothing per upload."""
    small, large = (_by_round(traced[c]) for c in (CHUNK, 4 * CHUNK))
    for (_, a), (_, b) in zip(small, large):
        ca = collections.Counter(e[0] for e in a)
        cb = collections.Counter(e[0] for e in b)
        assert cb["fed.device_wait"] - ca["fed.device_wait"] == 3
        del ca["fed.device_wait"], cb["fed.device_wait"]
        assert ca == cb
        assert max(cb.values()) == 1


def test_fused_close_span_carries_its_tiling(tmp_path, digits8):
    """A job closed by the fused kernel adds the tiling of its tree to
    every ``fed.apply_round``: the leaves closed rows-along-lanes and
    the elements computed and thrown away per client, both as
    ``ops.fused_tiling`` gives them for this tree and backend."""
    from repro.kernels.ops import fused_tiling

    clients, xte, yte = digits8
    events = _trace_job(tmp_path, clients, xte, yte, CHUNK,
                        projection_mode="fused_kernel")
    applies = [e[3] for e in events if e[0] == "fed.apply_round"]
    assert len(applies) == ROUNDS
    want = fused_tiling(init_mlp())
    for stats in applies:
        assert set(stats) == {"rows", "lane_rows_leaves", "pad_elements"}
        assert {k: stats[k] for k in want} == want
