"""Each cell end to end at a tiny size on the CPU, through the same file
lookup the command uses (BENCHMARK.json, configs/, traffic/, drivers/,
metrics/)."""
import json

import pytest

from chip_cpu import restore_jax_cache, run_tiny  # noqa: F401

import run_cell as run

RESULT_KEYS = ["correct", "attempted", "failed", "metrics", "device"]


def test_command_refuses_a_machine_without_a_tpu(capsys):
    rc = run.main(["--workload", "paper-mlp.engine.p100k", "--seed", "1",
                   "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert rc == 2
    assert out.out == ""
    assert "no TPU" in out.err


@pytest.mark.parametrize("workload,trace,want", [
    ("paper-mlp.engine.p100k", 0, {"setup_s", "uploads_per_s"}),
    ("paper-mlp.engine.p100k", 1, {"apply_ms.uploads"}),
    ("smollm360m.close.c256", 0, {"setup_s", "round_s"}),
    ("smollm360m.close.c256", 1, set()),
])
def test_cell_runs_end_to_end(capsys, restore_jax_cache, workload, trace,
                              want):
    rc, res, earlier = run_tiny(capsys, workload, trace=trace)
    assert rc == 0
    assert list(res)[:5] == RESULT_KEYS and list(res)[-1] == "checks"
    assert res["correct"] is True, res["checks"]
    assert res["failed"] == 0 and res["attempted"] >= 1
    # The CPU has no device trace: device metrics are left out, not 0.
    assert set(res["metrics"]) == want
    assert res["device"]["platform"] == "cpu"
    counts = json.loads(earlier[-1])
    assert counts["compiles_in_window"] == 0
    assert all("limit" in c for c in res["checks"].values())


def test_every_metric_has_a_reader_and_every_cell_its_files():
    with open(run.os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert callable(run.load_module("metrics", m["name"]).read)
    for w in bench["workloads"]:
        spec = run.load_cell(w["name"])
        driver = run.load_module("drivers", spec["traffic"]["driver"])
        for fn in ("setup", "window", "check", "control", "failed_rounds"):
            assert callable(getattr(driver, fn)), (w["name"], fn)


def test_engine_window_is_one_job(capsys, monkeypatch, restore_jax_cache):
    """Set-up times warm rounds on one core; the window is one call of
    the scheduler on a second, sized to the window, with one finalize."""
    from repro.fed.runtime import scheduler

    calls = []
    orig = scheduler.run_scheduled

    def run_scheduled(core, params):
        calls.append(core.cfg.rounds)
        return orig(core, params)

    monkeypatch.setattr(scheduler, "run_scheduled", run_scheduled)
    rc, res, earlier = run_tiny(capsys, "paper-mlp.engine.p100k", seconds=1.0)
    assert rc == 0 and res["correct"] is True, res["checks"]
    driver = run.load_module("drivers", "engine")
    assert calls[0] == driver.TIMING_ROUNDS and len(calls) == 2
    assert calls[1] == res["attempted"] + 1 >= 12
    notes = json.loads(earlier[-1])["driver"]
    assert notes["set_up_round_s"] > 0 and notes["after_last_apply_s"] > 0
