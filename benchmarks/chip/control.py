"""Readings behind the limits of a cell's check, on the chip.

    python3 benchmarks/chip/control.py --workload <name> \
        --seeds 1 2 3 ... [--control-seeds 3] [--seconds 3]

For each seed, in one process: the cell's set-up, a short window where
the cell's check needs one (``--seconds``), then the numbers the check
compares for the program, and for the first ``--control-seeds`` seeds
the same numbers with the control in the program's place: the plain
reference in the precision below the configuration's.  Where the
driver has faults of its own (a training cell), each is read too.  One
JSON line per seed; the benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import run_cell as run  # noqa: E402


def main(argv=None, *, allow_cpu: bool = False,
         overrides: dict | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args(argv)

    os.environ["JAX_COMPILATION_CACHE_DIR"] = run.CACHE_DIR
    sys.path.insert(0, os.path.join(run.ROOT, "src"))
    spec = run.load_cell(args.workload)
    config = run._merge(spec["config"], (overrides or {}).get("config"))
    traffic = run._merge(spec["traffic"], (overrides or {}).get("traffic"))

    import jax

    devices, why = run._device_check(jax, spec["cell"]["chips"], allow_cpu)
    if devices is None:
        print(why, file=sys.stderr)
        return 2
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    driver = run.load_module("drivers", traffic["driver"])
    ctx = run.Context(jax)
    ctx.seconds = args.seconds
    for i, seed in enumerate(args.seeds):
        st = driver.setup(config, traffic, seed, ctx)
        if driver.CHECK_READS_WINDOW:
            driver.window(st, args.seconds, ctx.span)
        saved = dict(st)
        kept = dict(st.get("kept", {}))
        line = {"seed": seed, "program": _plain(driver.check(st))}
        if i < args.control_seeds:
            st = dict(saved, kept=dict(kept))
            line["control"] = _plain(driver.control(st))
            if hasattr(driver, "faults"):
                st = dict(saved, kept=dict(kept))
                line["faults"] = {f: _plain(v) for f, v in
                                  driver.faults(st).items()}
        print(json.dumps(line), flush=True)
        del st, saved, kept
    return 0


def _plain(checks: dict) -> dict:
    return {k: v for k, (v, _) in checks.items()}


if __name__ == "__main__":
    sys.exit(main())
