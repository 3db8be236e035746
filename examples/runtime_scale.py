"""Massive-cohort federation runtime demo (DESIGN.md §5).

Runs K rounds of FedScalar over a registered population of (by
default) 100,000 virtual clients at 1 % participation on the digits
task — something the fixed-N fully-synchronous simulation cannot
express — and reports unbiased-estimate diagnostics plus bandwidth /
wall-clock / energy totals from the cost model.

Usage::

    PYTHONPATH=src python examples/runtime_scale.py \
        [--population 100000] [--participation 0.01] [--rounds 50] \
        [--serve sync|async|legacy] [--quorum 1.0] [--period-s 0.001] \
        [--depth 32] [--window 4] \
        [--sampler uniform|weighted|poisson] [--scalar fp32|fp16|bf16] \
        [--deadline-s inf] [--max-staleness 0] [--staleness-beta 0.0] \
        [--drop-prob 0.0] [--downlink dense|digest] [--log-window 64] \
        [--check-fused]

``--serve`` picks the driver (DESIGN §10): ``sync`` is the
continuous-round scheduler in its bit-identical-to-legacy mode (with
``--quorum`` < 1 rounds close at the ⌈q·C⌉-th arrival instead of the
deadline), ``async`` pipelines up to ``--depth`` rounds opened every
``--period-s`` seconds with post-close stragglers re-admitted within
``--window`` rounds, and ``legacy`` keeps the pre-scheduler
one-cohort-at-a-time loop.  Scheduler runs report modeled serving
throughput (rounds/s and clients/s).

``--check-fused`` additionally verifies that a sampled cohort at
participation = 1.0 with deadline = ∞ reproduces the paper-scale
``run_simulation`` trajectory bit-for-bit.

``--downlink digest`` switches the downlink to the scalar round-digest
discipline (DESIGN §9): clients become stateful, sampled members catch
up through the bounded round log (dense fallback past ``--log-window``
rounds), and the cost totals show a dimension-free downlink.
"""
from __future__ import annotations

import argparse
import dataclasses
import math

import numpy as np

from repro.data import load_digits, make_client_datasets, train_test_split_arrays
from repro.fed.costmodel import ChannelConfig
from repro.fed.runtime import (
    RuntimeConfig,
    SchedulerConfig,
    ServerConfig,
    run_federation,
)
from repro.fed.runtime.engine import cohort_sampler
from repro.fed.runtime.sampling import sampling_diagnostic
from repro.models.mlp_classifier import init_mlp


def check_fused_equivalence(clients, xte, yte) -> None:
    """participation=1.0, deadline=∞ → bit-for-bit run_simulation."""
    from repro.fed import SimulationConfig, run_simulation

    p0 = init_mlp()
    rt = run_federation(
        RuntimeConfig(rounds=30, population=len(clients), participation=1.0),
        p0, clients, xte, yte)
    sim = run_simulation(
        SimulationConfig(method="fedscalar_rademacher", rounds=30,
                         num_clients=len(clients)),
        p0, clients, xte, yte)
    assert rt["fused_path"], "full sync cohort should take the fused scan path"
    assert np.array_equal(rt["loss"], sim["loss"]), "loss trajectory diverged"
    assert np.array_equal(rt["accuracy"], sim["accuracy"]), "accuracy diverged"
    for a, b in zip(np.asarray(rt["final_params"]["w0"]),
                    np.asarray(sim["final_params"]["w0"])):
        np.testing.assert_array_equal(a, b)
    print("fused-path check: runtime @ participation=1.0 ≡ run_simulation "
          "(loss/accuracy/params bit-for-bit over 30 rounds)")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--population", type=int, default=100_000)
    ap.add_argument("--participation", type=float, default=0.01)
    ap.add_argument("--rounds", type=int, default=50)
    ap.add_argument("--sampler", default="uniform",
                    choices=["uniform", "weighted", "poisson"])
    ap.add_argument("--scalar", default="fp32", choices=["fp32", "fp16", "bf16"])
    ap.add_argument("--deadline-s", type=float, default=math.inf)
    ap.add_argument("--max-staleness", type=int, default=0)
    ap.add_argument("--staleness-beta", type=float, default=0.0)
    ap.add_argument("--round-period-s", type=float, default=math.inf)
    ap.add_argument("--drop-prob", type=float, default=0.0)
    ap.add_argument("--serve", default="sync",
                    choices=["sync", "async", "legacy"],
                    help="driver: continuous scheduler (sync/async, DESIGN "
                         "§10) or the pre-scheduler legacy loop")
    ap.add_argument("--quorum", type=float, default=1.0,
                    help="close a round once this fraction of the cohort "
                         "arrived (1.0 = wait for the deadline)")
    ap.add_argument("--period-s", type=float, default=0.001,
                    help="async: open a new round every this many seconds")
    ap.add_argument("--depth", type=int, default=32,
                    help="async: max rounds in flight")
    ap.add_argument("--window", type=int, default=4,
                    help="async: staleness window for re-admitted stragglers")
    ap.add_argument("--downlink", default="dense", choices=["dense", "digest"])
    ap.add_argument("--log-window", type=int, default=64)
    ap.add_argument("--shards", type=int, default=20)
    ap.add_argument("--eval-every", type=int, default=5)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--check-fused", action="store_true")
    args = ap.parse_args()

    x, y = load_digits()
    xtr, ytr, xte, yte = train_test_split_arrays(x, y)
    clients = make_client_datasets(xtr, ytr, args.shards)

    if args.check_fused:
        check_fused_equivalence(clients, xte, yte)

    if args.serve == "legacy":
        scheduler = None
    elif args.serve == "sync":
        scheduler = SchedulerConfig(mode="sync", quorum_frac=args.quorum)
    else:
        scheduler = SchedulerConfig(
            mode="async", quorum_frac=args.quorum, period_s=args.period_s,
            max_rounds_in_flight=args.depth, staleness_window=args.window)

    cfg = RuntimeConfig(
        rounds=args.rounds,
        scheduler=scheduler,
        population=args.population,
        participation=args.participation,
        sampler=args.sampler,
        scalar_format=args.scalar,
        downlink_mode=args.downlink,
        downlink_log_window=args.log_window,
        eval_every=args.eval_every,
        seed=args.seed,
        server=ServerConfig(
            deadline_s=args.deadline_s,
            round_period_s=args.round_period_s,
            max_staleness=args.max_staleness,
            staleness_exponent=args.staleness_beta,
        ),
        channel=ChannelConfig(drop_prob=args.drop_prob),
    )
    print(f"population={cfg.population}  participation={cfg.participation} "
          f"(cohort ≈ {cfg.cohort_size()})  sampler={cfg.sampler}  "
          f"wire={cfg.scalar_format} ({cfg.wire().bits_per_upload} bits/upload)")

    h = run_federation(cfg, init_mlp(seed=args.seed), clients, xte, yte)

    evals = ~np.isnan(h["loss"])
    path = ("fused scan" if h["fused_path"]
            else f"scheduler/{args.serve}" if args.serve != "legacy"
            else "event-driven legacy")
    print(f"\nran {args.rounds} rounds in {h['sim_compute_seconds']:.1f}s "
          f"({path} path)")
    print(f"loss  {h['loss'][evals][0]:.4f} → {h['loss'][evals][-1]:.4f}   "
          f"accuracy {h['accuracy'][evals][0]:.4f} → {h['accuracy'][evals][-1]:.4f}")

    if "scheduler" in h:
        s = h["scheduler"]
        print("\n== continuous-round serving (modeled timeline, DESIGN §10) ==")
        print(f"  makespan           : {s['makespan_s']:.3f} s "
              f"({s['mode']}, quorum {s['quorum_frac']}, "
              f"{s['max_rounds_in_flight']} round(s) in flight)")
        print(f"  serving throughput : {s['rounds_per_s']:.1f} rounds/s, "
              f"{s['clients_per_s']:,.0f} clients/s "
              f"({s['offered_uploads']} uploads offered)")
        print(f"  closures           : {s['closed_by_quorum']} by quorum, "
              f"{len(s['starts']) - s['closed_by_quorum']} by deadline/drain; "
              f"params lag ≤ {s['params_lag_max']}")
        print(f"  stragglers         : {s['stale_admitted']} re-admitted ≤ "
              f"{s['staleness_window']} rounds late, "
              f"{s['stale_dropped']} dropped, {s['queue_leftover']} left "
              f"queued at shutdown")
        print(f"  server state       : {s['client_state_bytes']:,} B "
              f"per-client map + {s['agg_state_bytes_peak']:,} B aggregator "
              f"peak + {s['queue_peak_bytes']:,} B queue peak "
              f"({s['queue_entry_bytes']} B/entry)")

    print("\n== unbiased-estimate diagnostics ==")
    diag = sampling_diagnostic(cohort_sampler(cfg, clients),
                               rounds=min(200, 4 * cfg.rounds))
    print(f"  Horvitz–Thompson probe estimate rel. err : "
          f"{diag['estimate_rel_err']:.4f}")
    print(f"  empirical inclusion-marginal abs. err    : "
          f"{diag['empirical_marginal_abs_err']:.4f}")
    print(f"  mean per-round Σwᵢ (target 1.0)          : "
          f"{np.mean(h['weight_sum']):.4f}")

    print("\n== arrivals ==")
    print(f"  uploads applied    : {int(h['applied'].sum())} "
          f"(stale: {int(h['applied_stale'].sum())})")
    print(f"  lost in channel    : {int(h['lost_channel'].sum())}")
    print(f"  dropped @ deadline : {int(h['dropped_deadline'].sum())}")
    print(f"  dropped too-stale  : {int(h['dropped_stale'].sum())}")

    print("\n== two-sided cost-model totals (eqs. 12′–13′, DESIGN §9) ==")
    print(f"  uplink   : {h['cum_bits'][-1]:.3g} bits "
          f"({h['bits_per_client_per_round']} bits/client/round)")
    ds = h["downlink_stats"]
    print(f"  downlink : {h['cum_downlink_bits'][-1]:.3g} bits "
          f"[{h['downlink_mode']}] (broadcast {ds['broadcast_bits']:.3g} + "
          f"catch-up {ds['catchup_bits']:.3g}; "
          f"{ds['dense_resyncs']} dense resyncs)")
    print(f"  wall     : {h['cum_wall_s'][-1] + h['cum_downlink_wall_s'][-1]:.3g} s "
          f"(uplink {h['cum_wall_s'][-1]:.3g} + "
          f"downlink {h['cum_downlink_wall_s'][-1]:.3g})")
    print(f"  energy   : {h['cum_energy_j'][-1] + h['cum_downlink_energy_j'][-1]:.3g} J "
          f"(uplink {h['cum_energy_j'][-1]:.3g} + "
          f"downlink {h['cum_downlink_energy_j'][-1]:.3g})")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
