"""The program's ``fed.*`` spans read from a trace: whole rounds of the
window, stage time nested in them per round, the p95 of the rounds."""
import collections
import types

import numpy as np
import pytest

import program_spans as ps

E = collections.namedtuple("E", "name start_ns duration_ns")
L = collections.namedtuple("L", "name events")
P = collections.namedtuple("P", "name lines")


def _planes():
    """A window [1000, 2000) over four rounds: the first starts before
    it (the job's round 0, held until the window opens), two lie inside
    it, and the last ends after it."""
    job = L("run_scheduled", [
        E("fed.round", 0, 1100),             # starts before the window
        E("fed.evaluate", 900, 150),         # round 0's, partly inside
        E("fed.round", 1100, 300),           # [1100, 1400)
        E("fed.sample_cohort", 1100, 50),
        E("fed.compute_cohort", 1150, 100),
        E("fed.device_wait", 1200, 40),      # nested in compute
        E("fed.apply_round", 1250, 100),
        E("fed.device_wait", 1300, 20),      # nested in apply
        E("fed.round", 1400, 500),           # [1400, 1900)
        E("fed.sample_cohort", 1400, 70),
        E("fed.compute_cohort", 1470, 200),
        E("fed.device_wait", 1500, 60),
        E("fed.round", 1900, 200),           # ends after the window
        E("fed.sample_cohort", 1900, 80),
    ])
    main = L("python", [E("window", 1000, 1000), E("sample_cohort", 1100, 60)])
    dev = P("/device:TPU:0", [L("XLA Ops", [E("fusion.1", 1200, 40)])])
    return [P("/host:CPU", [main, job]), dev]


def test_whole_rounds_of_the_window():
    red = ps.reduce_spans(_planes())
    assert red["rounds"] == 2
    assert red["window_ms"] == pytest.approx(1000e-6)
    np.testing.assert_allclose(red["round_ms"], [300e-6, 500e-6])


def test_stage_time_per_round_nested_in_the_rounds():
    st = ps.reduce_spans(_planes())["stage_ms"]
    # Round 0's and the last round's spans fall outside; nested waits
    # count once each; the benchmark's own spans are not read.
    assert st["fed.sample_cohort"] == pytest.approx((50 + 70) / 2 * 1e-6)
    assert st["fed.compute_cohort"] == pytest.approx((100 + 200) / 2 * 1e-6)
    assert st["fed.device_wait"] == pytest.approx((40 + 20 + 60) / 2 * 1e-6)
    assert st["fed.apply_round"] == pytest.approx(100 / 2 * 1e-6)
    assert "fed.evaluate" not in st and "sample_cohort" not in st


def test_round_p95():
    job = [E("fed.round", 100 * i, 10 * (i + 1)) for i in range(20)]
    planes = [P("/host:CPU", [L("python", [E("window", 0, 10_000)] + job)])]
    red = ps.reduce_spans(planes)
    assert red["rounds"] == 20
    want = np.percentile(np.arange(1, 21) * 10e-6, 95)
    assert float(np.percentile(red["round_ms"], 95)) == pytest.approx(want)


def test_nothing_to_read_gives_none(monkeypatch):
    # A program without the spans: a window but no fed.round.
    no_rounds = [P("/host:CPU", [L("python", [E("window", 0, 100),
                                             E("sample_cohort", 10, 5)])])]
    assert ps.reduce_spans(no_rounds) is None
    assert ps.reduce_spans([p for p in _planes()
                            if not p.name.startswith("/host")]) is None
    # No TPU plane: the benchmark's reduction gave no trace, and the
    # readers look at no file.
    monkeypatch.setattr(ps, "find_xplane", lambda d: pytest.fail(d))
    ctx = types.SimpleNamespace(trace=None)
    assert ps.read(ctx) is None
    assert ps.stage_ms(ctx, "fed.sample_cohort") is None
    assert ps.round_p95_ms(ctx) is None


def test_metric_readers_read_the_spans(monkeypatch):
    import run_cell

    red = ps.reduce_spans(_planes())
    monkeypatch.setattr(ps, "read", lambda ctx: red)
    ctx = types.SimpleNamespace(trace={})
    got = {name: run_cell.load_module("metrics", name).read(ctx)
           for name in ("round_p95_ms.uploads", "sample_ms.uploads",
                        "compute_ms.uploads", "offer_ms.uploads",
                        "device_wait_ms.uploads")}
    assert got["sample_ms.uploads"] == red["stage_ms"]["fed.sample_cohort"]
    assert got["compute_ms.uploads"] == red["stage_ms"]["fed.compute_cohort"]
    assert got["device_wait_ms.uploads"] == red["stage_ms"]["fed.device_wait"]
    assert got["offer_ms.uploads"] is None      # no such span in a round
    assert got["round_p95_ms.uploads"] == pytest.approx(
        np.percentile([300e-6, 500e-6], 95))
