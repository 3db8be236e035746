"""Helpers for driving the benchmark at tiny sizes on the CPU."""
import json
import os

import pytest

# Stand-ins for each cell at sizes a test run holds: the reduced model
# with the cell's cohort of 256 (the close check's scale follows the
# cohort), and the MLP engine with a cohort of 16.
TINY = {
    "smollm360m.close.c256": {
        "config": {"program_reduced": True},
        "traffic": {"population": 2000, "participation": 0.128, "cohort": 256}},
    "paper-mlp.engine.p100k": {
        "traffic": {"population": 2000, "participation": 0.008}},
}


@pytest.fixture
def restore_jax_cache():
    """Leave JAX's cache settings as the test found them."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    saved = {k: getattr(jax.config, k) for k in keys}
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    yield
    for k, v in saved.items():
        jax.config.update(k, v)
    if env is None:
        os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
    else:
        os.environ["JAX_COMPILATION_CACHE_DIR"] = env
    compilation_cache.reset_cache()


def run_tiny(capsys, workload, seconds=1.0, trace=0, seed=2147483901):
    """run.main at the tiny size on the CPU → (rc, result, earlier lines)."""
    import run_cell as run

    rc = run.main(["--workload", workload, "--seed", str(seed),
                   "--seconds", str(seconds), "--trace", str(trace)],
                  allow_cpu=True, overrides=TINY[workload])
    lines = capsys.readouterr().out.strip().splitlines()
    return rc, json.loads(lines[-1]), lines[:-1]
