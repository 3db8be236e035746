"""CI gate on the fused reconstruct+apply megakernel's throughput.

Reads ``experiments/kernels/fused_throughput.csv`` (written by
``benchmarks.run --only-kernels``) and fails the build unless the
fused kernel aggregates at least as many clients/s as the jitted
fori-loop baseline at every cohort ≥ ``CROSSOVER_COHORT`` — the
crossover the fusion PR exists to deliver.  Both paths are timed in
the same process on the same runner, so the ratio is
hardware-independent even though the absolute clients/s are not.

The ratio floor is **ratchet-up only**: when a change legitimately
widens the fused margin, raise the floor to just under the new figure
in the same PR; never lower it to make a regression pass (that is the
regression the gate exists to catch).  ``RATIO_FLOOR = 1.0`` is the
acceptance criterion itself — fused ≥ fori — and is the one floor
that must never move down.

    PYTHONPATH=src python -m benchmarks.check_kernels
"""
from __future__ import annotations

import csv
import sys

CSV_PATH = "experiments/kernels/fused_throughput.csv"

# Ratchet-up only (see module docstring).  Current figures: the gate
# fails.  With JAX 0.9 and the chunk fold as a loop of single adds (the
# order the Pallas kernel can reproduce), the fused/fori clients/s ratio
# at cohorts 256/1024 is ~0.14-0.16 on an 8-core CPU and ~0.08-0.12 on
# one core; it was ~1.6 with JAX 0.4.37 and a batched reduce.
RATIO_FLOOR = 1.0
CROSSOVER_COHORT = 256           # fused must win from here up
REQUIRED_COHORTS = (256, 1024)   # rows the CSV must contain


def main() -> int:
    try:
        with open(CSV_PATH) as f:
            rows = {int(r["cohort"]): r for r in csv.DictReader(f)}
    except FileNotFoundError:
        print(f"kernel gate FAILED: {CSV_PATH} missing — run "
              "`PYTHONPATH=src python -m benchmarks.run --only-kernels`",
              file=sys.stderr)
        return 1

    failures = []
    for n in REQUIRED_COHORTS:
        if n not in rows:
            failures.append(f"CSV has no cohort={n} row")
    if not failures:
        for n, r in sorted(rows.items()):
            if n < CROSSOVER_COHORT:
                continue   # small cohorts are launch-overhead bound
            ratio = float(r["ratio"])
            if ratio < RATIO_FLOOR:
                failures.append(
                    f"cohort {n}: fused/fori clients/s ratio {ratio:.3f} "
                    f"< {RATIO_FLOOR} (fused "
                    f"{float(r['fused_clients_per_s']):.0f} vs fori "
                    f"{float(r['fori_clients_per_s']):.0f})")
    if not failures:
        figs = ", ".join(
            f"n={n}: {float(r['ratio']):.2f}×"
            for n, r in sorted(rows.items()) if n >= CROSSOVER_COHORT)
        print(f"kernel gate OK: fused ≥ {RATIO_FLOOR}× fori at every "
              f"cohort ≥ {CROSSOVER_COHORT} ({figs})")
        return 0
    for msg in failures:
        print(f"kernel gate FAILED: {msg}", file=sys.stderr)
    return 1


if __name__ == "__main__":
    sys.exit(main())
