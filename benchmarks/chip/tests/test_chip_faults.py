"""The check must fail a run whose timed path is broken underneath, and
the control (the reference in the precision below the configuration's)
put in the program's place."""
import numpy as np
import pytest

from chip_cpu import TINY, restore_jax_cache, run_tiny  # noqa: F401

from repro.fed.runtime.engine import EngineCore
from repro.fed.runtime.server import StreamingAggregator


def _unchanged(monkeypatch):
    orig = EngineCore.apply_round

    def apply_round(self, params, *a, **k):
        _, method, apply_s = orig(self, params, *a, **k)
        return params, method, apply_s

    monkeypatch.setattr(EngineCore, "apply_round", apply_round)


def _half_batch(monkeypatch):
    orig = StreamingAggregator.close_round

    def close_round(self, k):
        seeds, coeffs, rs, st = orig(self, k)
        h = len(seeds) // 2
        return seeds[:h], coeffs[:h] * 2.0, rs[:h], st

    monkeypatch.setattr(StreamingAggregator, "close_round", close_round)


def _altered_close(monkeypatch):
    import jax

    orig = EngineCore.apply_round
    calls = iter(range(10**9))

    def apply_round(self, *a, **k):
        # One element of each round's answer is off by 0.01, a new
        # element each round, so no element drifts far.
        params, method, apply_s = orig(self, *a, **k)
        leaves, treedef = jax.tree_util.tree_flatten(params)
        at = np.unravel_index(next(calls), leaves[0].shape)
        leaves[0] = leaves[0].at[at].add(0.01)
        return jax.tree_util.tree_unflatten(treedef, leaves), method, apply_s

    monkeypatch.setattr(EngineCore, "apply_round", apply_round)


def _altered_upload(monkeypatch):
    orig = EngineCore.compute_cohort

    def compute_cohort(self, *a, **k):
        rs, seeds = orig(self, *a, **k)
        rs = np.array(rs)
        rs[0] = -rs[0]
        return rs, seeds

    monkeypatch.setattr(EngineCore, "compute_cohort", compute_cohort)


@pytest.mark.parametrize("workload,fault", [
    ("smollm360m.close.c256", _unchanged),
    ("smollm360m.close.c256", _half_batch),
    ("smollm360m.close.c256", _altered_close),
    ("paper-mlp.engine.p100k", _unchanged),
    ("paper-mlp.engine.p100k", _half_batch),
    ("paper-mlp.engine.p100k", _altered_upload),
])
def test_broken_timed_path_is_not_correct(capsys, monkeypatch,
                                          restore_jax_cache, workload, fault):
    fault(monkeypatch)
    rc, res, _ = run_tiny(capsys, workload, seconds=0.5)
    assert rc == 0
    assert res["correct"] is False
    assert any(c["value"] > c["limit"] for c in res["checks"].values())


@pytest.mark.parametrize("workload", sorted(TINY))
def test_control_is_not_correct(capsys, restore_jax_cache, workload):
    import json

    import control

    rc = control.main(["--workload", workload, "--seeds", "7",
                       "--control-seeds", "1", "--seconds", "0.5"],
                      allow_cpu=True, overrides=TINY[workload])
    assert rc == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    import run_cell as run

    driver = run.load_module(
        "drivers", run.load_cell(workload)["traffic"]["driver"])
    limits = (driver.LIMITS if hasattr(driver, "LIMITS")
              else {"close_gap_steps": driver.GAP_LIMIT})
    assert all(line["program"][k] <= lim for k, lim in limits.items())
    assert any(line["control"][k] > lim for k, lim in limits.items())


def test_a_non_finite_reading_fails_the_engine_check():
    """A NaN loss in any compared round must read as a gap, not vanish
    under ``max``."""
    import run_cell as run

    driver = run.load_module("drivers", "engine")
    x0 = {"w": np.zeros((3, 2), np.float32), "b": np.zeros(2, np.float32)}
    x1 = {"w": np.ones((3, 2), np.float32), "b": np.ones(2, np.float32)}
    nums = driver._numbers(x0, x1, x1, [1.0, float("nan")], x1, x1, [1.0, 0.5])
    assert nums["loss_gap"] == float("inf") > driver.LIMITS["loss_gap"]
    assert nums["first_update_gap"] == 0.0
