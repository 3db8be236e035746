"""End-to-end reproduction of the paper's §III experiment (Figs 2–6).

Trains the d≈2000 MLP on synthetic 8×8 digits across N=20 clients for
K rounds with S=5 local steps, comparing FedScalar (Rademacher and
Gaussian) against FedAvg and 8-bit QSGD, under the 0.1 Mbps
bandwidth-constrained channel with the eq. (12)/(13) cost model.

Usage::

    PYTHONPATH=src python examples/fedscalar_digits.py [--rounds 1500] [--runs 3]

Writes per-method CSV curves to ``experiments/digits/`` and prints the
paper's headline comparisons.
"""
from __future__ import annotations

import argparse
import os

import numpy as np

from repro.data import load_digits, make_client_datasets, train_test_split_arrays
from repro.fed import SimulationConfig, run_simulation
from repro.models.mlp_classifier import init_mlp
from repro.core.projection import tree_size


def acc_at_budget(h, budget, key):
    """Test accuracy of the last round whose cumulative cost ≤ budget."""
    idx = np.searchsorted(h[key], budget, side="right") - 1
    return float(h["accuracy"][idx]) if idx >= 0 else 0.0


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rounds", type=int, default=1500)
    ap.add_argument("--runs", type=int, default=3)
    ap.add_argument("--methods", nargs="*", default=[
        "fedscalar_rademacher", "fedscalar_gaussian", "fedavg", "qsgd"])
    ap.add_argument("--outdir", default="experiments/digits")
    ap.add_argument("--partition", default="iid", choices=["iid", "dirichlet"],
                    help="beyond-paper: label-skewed non-iid clients")
    ap.add_argument("--alpha", type=float, default=0.5,
                    help="Dirichlet concentration for --partition dirichlet")
    ap.add_argument("--access", default="concurrent",
                    choices=["concurrent", "tdma"],
                    help="uplink medium access (Table I scenarios)")
    args = ap.parse_args()

    import dataclasses

    from repro.fed.costmodel import ChannelConfig

    x, y = load_digits()
    xtr, ytr, xte, yte = train_test_split_arrays(x, y)
    clients = make_client_datasets(xtr, ytr, 20, scheme=args.partition,
                                   alpha=args.alpha)
    os.makedirs(args.outdir, exist_ok=True)
    channel = ChannelConfig(access=args.access)
    suffix = ""
    if args.partition != "iid":
        suffix += f"_{args.partition}{args.alpha}"
    if args.access != "concurrent":
        suffix += f"_{args.access}"

    results = {}
    for method in args.methods:
        runs = []
        for r in range(args.runs):
            p0 = init_mlp(seed=r)
            cfg = SimulationConfig(method=method, rounds=args.rounds, seed=r,
                                   channel=channel)
            runs.append(run_simulation(cfg, p0, clients, xte, yte))
        h = {
            "round": runs[0]["round"],
            "loss": np.mean([h["loss"] for h in runs], axis=0),
            "accuracy": np.mean([h["accuracy"] for h in runs], axis=0),
            "cum_bits": np.mean([h["cum_bits"] for h in runs], axis=0),
            "cum_wall_s": np.mean([h["cum_wall_s"] for h in runs], axis=0),
            "cum_energy_j": np.mean([h["cum_energy_j"] for h in runs], axis=0),
        }
        results[method] = h
        path = os.path.join(args.outdir, f"{method}{suffix}.csv")
        np.savetxt(
            path,
            np.column_stack([h["round"], h["loss"], h["accuracy"],
                             h["cum_bits"], h["cum_wall_s"], h["cum_energy_j"]]),
            delimiter=",",
            header="round,loss,accuracy,cum_bits,cum_wall_s,cum_energy_j",
            comments="",
        )
        print(f"{method:24s} final acc={h['accuracy'][-1]:.4f} "
              f"loss={h['loss'][-1]:.4f} total bits={h['cum_bits'][-1]:.3g} "
              f"wall={h['cum_wall_s'][-1]:.3g}s energy={h['cum_energy_j'][-1]:.3g}J "
              f"-> {path}")

    d = tree_size(init_mlp())
    print(f"\nmodel d = {d}")
    print("\n== Fig 4 headline: accuracy at 1e6 uploaded bits ==")
    for m, h in results.items():
        print(f"  {m:24s} {100*acc_at_budget(h, 1e6, 'cum_bits'):6.2f} %")
    print("\n== Fig 5 headline: accuracy at t = 1250 s ==")
    for m, h in results.items():
        print(f"  {m:24s} {100*acc_at_budget(h, 1250.0, 'cum_wall_s'):6.2f} %")
    print("\n== Fig 6 headline: accuracy at 50 J ==")
    for m, h in results.items():
        print(f"  {m:24s} {100*acc_at_budget(h, 50.0, 'cum_energy_j'):6.2f} %")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
