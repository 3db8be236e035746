"""Benchmark harness — one entry per paper table/figure (+ framework extras).

Prints ``name,us_per_call,derived`` CSV rows:

  table1_row_*        — Table I upload-time model (derived = total seconds)
  fig2_loss_*         — §III training-loss curves (derived = final loss)
  fig3_acc_*          — §III test-accuracy curves (derived = final accuracy)
  fig4_bits_*         — accuracy at a 10⁶-bit communication budget
  fig5_wall_*         — accuracy at t = 1250 s wall-clock
  fig6_energy_*       — accuracy at 50 J transmit energy
  baseline_*          — Table I / §V trade-off: the three protocols
                        through the engine at 0.1 Mbps, concurrent +
                        TDMA, d swept (derived = bits/round + final acc;
                        CSV → experiments/baselines/tradeoff.csv)
  downlink_*          — two-sided round traffic: digest vs dense
                        downlink per protocol × d (DESIGN §9; derived =
                        round traffic + total wall/energy; CSV →
                        experiments/downlink/tradeoff.csv)
  prop21_variance     — Rademacher-vs-Gaussian aggregation-variance gap
                        (derived = measured/theory; theory = 2Σ‖δₙ‖²/N²)
  direction_*         — variance-vs-bandwidth sweep of the pluggable
                        direction families × k block scalars (DESIGN §6;
                        derived = measured/predicted variance + bytes)
  kernel_*            — Pallas kernel per-call latency (interpret mode on
                        CPU — structural check, not TPU timing)
  fused_throughput_*  — fused reconstruct+apply megakernel vs the jitted
                        fori baseline, clients/s vs cohort, autotuned
                        block/slab (DESIGN §11; CSV →
                        experiments/kernels/fused_throughput.csv, gated
                        by benchmarks.check_kernels)
  sharded_recon_*     — mesh-sharded server reconstruction throughput vs
                        device count (DESIGN §7; derived = elements/s)
  scheduler_*         — continuous-round serving throughput on a
                        10⁵-client population: legacy vs sync vs async
                        pipelined scheduler (DESIGN §10; derived =
                        modeled clients/s; CSV →
                        experiments/scheduler/throughput.csv, gated by
                        benchmarks.check_scheduler)
  roofline_*          — dry-run sweep summary

Usage: ``PYTHONPATH=src python -m benchmarks.run [--rounds 300]``
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

ROWS = []


def emit(name: str, us_per_call: float, derived):
    ROWS.append((name, us_per_call, derived))
    print(f"{name},{us_per_call:.1f},{derived}", flush=True)


def timed(fn, repeat: int = 3):
    fn()  # warmup/compile
    t0 = time.perf_counter()
    for _ in range(repeat):
        out = fn()
    if hasattr(out, "block_until_ready"):
        out.block_until_ready()
    return (time.perf_counter() - t0) / repeat * 1e6, out


# ---------------------------------------------------------------------------
# Table I
# ---------------------------------------------------------------------------

def bench_table1():
    from repro.fed.costmodel import table1_upload_times
    t0 = time.perf_counter()
    rows = table1_upload_times()
    us = (time.perf_counter() - t0) * 1e6
    for r in rows:
        bw = int(r["bandwidth_bps"])
        emit(f"table1_{bw}bps_concurrent", us,
             f"{r['concurrent_total_s']:.0f}s"
             + ("_VIOLATES" if r["concurrent_violates"] else ""))
        emit(f"table1_{bw}bps_tdma", us,
             f"{r['tdma_total_s']:.0f}s"
             + ("_VIOLATES" if r["tdma_violates"] else ""))


# ---------------------------------------------------------------------------
# Figs 2–6: digits experiment
# ---------------------------------------------------------------------------

def bench_digits(rounds: int):
    from repro.data import load_digits, make_client_datasets, train_test_split_arrays
    from repro.fed import SimulationConfig, run_simulation
    from repro.models.mlp_classifier import init_mlp

    x, y = load_digits()
    xtr, ytr, xte, yte = train_test_split_arrays(x, y)
    clients = make_client_datasets(xtr, ytr, 20)
    p0 = init_mlp()

    def at_budget(h, budget, key):
        idx = np.searchsorted(h[key], budget, side="right") - 1
        return float(h["accuracy"][idx]) if idx >= 0 else 0.0

    for method in ("fedscalar_rademacher", "fedscalar_gaussian", "fedavg", "qsgd"):
        t0 = time.perf_counter()
        h = run_simulation(SimulationConfig(method=method, rounds=rounds),
                           p0, clients, xte, yte)
        us = (time.perf_counter() - t0) / rounds * 1e6
        emit(f"fig2_loss_{method}", us, f"{h['loss'][-1]:.4f}")
        emit(f"fig3_acc_{method}", us, f"{h['accuracy'][-1]:.4f}")
        emit(f"fig4_bits_{method}", us,
             f"acc@1e6bits={at_budget(h, 1e6, 'cum_bits'):.4f}")
        emit(f"fig5_wall_{method}", us,
             f"acc@1250s={at_budget(h, 1250.0, 'cum_wall_s'):.4f}")
        emit(f"fig6_energy_{method}", us,
             f"acc@50J={at_budget(h, 50.0, 'cum_energy_j'):.4f}")


# ---------------------------------------------------------------------------
# Table I / §V: protocol trade-off through the engine (DESIGN §8)
# ---------------------------------------------------------------------------

def bench_baseline_tradeoff(rounds: int):
    """FedAvg/QSGD/FedScalar through one engine at the paper regime.

    The acceptance shape: FedScalar's bits/upload column constant in d,
    the baselines Θ(d), and wall/energy ordered fedscalar ≪ qsgd <
    fedavg at 0.1 Mbps.  Rows land in
    ``experiments/baselines/tradeoff.csv`` for report §Baselines.
    """
    from repro.fed.baselines import baseline_tradeoff, write_tradeoff_csv

    t0 = time.perf_counter()
    rows = baseline_tradeoff(rounds=rounds)
    us = (time.perf_counter() - t0) / max(len(rows), 1) * 1e6
    for r in rows:
        emit(f"baseline_{r['protocol']}_d{r['d']}_{r['access']}", us,
             f"{r['bits_per_client_per_round']}bits/up_"
             f"acc={r['final_accuracy']:.4f}_wall={r['total_wall_s']:.0f}s_"
             f"energy={r['total_energy_j']:.1f}J")
    write_tradeoff_csv(rows)


def bench_downlink_tradeoff(rounds: int):
    """Two-sided round traffic: digest vs dense downlink (DESIGN §9).

    The acceptance shape: fedscalar×digest's round_traffic_bits is the
    same at every d (dimension-free round), while every dense-downlink
    row — fedscalar×dense included — scales Θ(d).  Rows land in
    ``experiments/downlink/tradeoff.csv`` for report §Downlink.
    """
    from repro.fed.baselines import downlink_tradeoff, write_downlink_csv

    t0 = time.perf_counter()
    rows = downlink_tradeoff(rounds=rounds)
    us = (time.perf_counter() - t0) / max(len(rows), 1) * 1e6
    for r in rows:
        emit(f"downlink_{r['protocol']}_{r['downlink']}_d{r['d']}", us,
             f"{r['round_traffic_bits']:.0f}bits/round_"
             f"wall={r['total_wall_s']:.0f}s_"
             f"energy={r['total_energy_j']:.1f}J_"
             f"acc={r['final_accuracy']:.4f}")
    write_downlink_csv(rows)


# ---------------------------------------------------------------------------
# Prop 2.1: aggregation variance gap
# ---------------------------------------------------------------------------

def bench_prop21():
    from repro.core.prng import Distribution
    from repro.core.projection import project_tree, reconstruct_tree

    rng = np.random.RandomState(0)
    n_clients, trials, d = 5, 60_000, 40
    deltas = [{"w": jnp.asarray(rng.randn(d), jnp.float32)}
              for _ in range(n_clients)]

    def agg_samples(dist):
        def one(t):
            acc = jnp.zeros(d)
            for n, dl in enumerate(deltas):
                seed = t * jnp.uint32(131) + jnp.uint32(n)
                r = project_tree(dl, seed, dist)
                acc = acc + reconstruct_tree(dl, seed, r, dist)["w"]
            return acc / n_clients
        return jax.jit(jax.vmap(one))(jnp.arange(trials, dtype=jnp.uint32))

    t0 = time.perf_counter()
    var_g = float(jnp.var(agg_samples(Distribution.GAUSSIAN), axis=0).sum())
    var_r = float(jnp.var(agg_samples(Distribution.RADEMACHER), axis=0).sum())
    us = (time.perf_counter() - t0) * 1e6
    # Corrected Prop 2.1 (Isserlis): Var_g − Var_r = (2/N²)Σₙ diag(δₙ²),
    # trace = (2/N²)Σₙ‖δₙ‖².  The paper states (2/N²)Σ‖δₙ‖²·I_d — a
    # ×d overcount from the i=j=m=p overlap in its Case 1/4 expansion;
    # verified per-coordinate in tests/test_projection.py.
    theory = 2.0 / n_clients**2 * sum(
        float(jnp.sum(dl["w"] ** 2)) for dl in deltas)
    emit("prop21_variance_corrected", us,
         f"measured/theory={(var_g - var_r) / theory:.3f}")
    emit("prop21_variance_paper_constant", us,
         f"measured/paper_theory={(var_g - var_r) / (theory * d):.3f}_(x d overcount)")


# ---------------------------------------------------------------------------
# kernels (interpret mode)
# ---------------------------------------------------------------------------

def bench_kernels():
    from repro.kernels import ops

    tree = {"w": jnp.asarray(np.random.RandomState(1).randn(512, 2048),
                             jnp.float32)}
    us, r = timed(lambda: ops.project_tree_kernel(tree, 42))
    emit("kernel_seeded_projection_1M", us, f"r={float(r[0]):.3f}")
    seeds = jnp.arange(4, dtype=jnp.uint32)
    rs = jnp.ones((4,), jnp.float32)
    us, out = timed(lambda: ops.server_update_kernel(tree, rs, seeds)["w"])
    emit("kernel_seeded_reconstruct_1M_n4", us,
         f"norm={float(jnp.linalg.norm(out)):.1f}")
    us, q = timed(lambda: ops.qsgd_roundtrip_kernel(tree, 7, 8)["w"])
    err = float(jnp.abs(q - tree["w"]).mean())
    emit("kernel_qsgd_quant_1M", us, f"mean_abs_err={err:.4f}")


# ---------------------------------------------------------------------------
# direction families: variance vs bandwidth (DESIGN §6)
# ---------------------------------------------------------------------------

def bench_direction_sweep():
    """Measured & predicted estimator variance per (family, k) vs bytes.

    The k-block-scalar dial: upload k scalars (4k + 4 bytes fp32) and
    cut estimator variance ~k×; the family picks the constant.  Rows
    land in ``experiments/directions/variance_sweep.csv`` for
    benchmarks.report §Directions.
    """
    import os

    from repro.core.directions import FAMILIES, tree_block_sqnorms
    from repro.core.projection import (
        ProjectionMode,
        project_tree,
        reconstruct_tree,
    )
    from repro.fed.runtime.transport import WireFormat

    d, trials = 256, 8192
    delta = {"w": jnp.asarray(np.random.RandomState(0).randn(d), jnp.float32)}
    rows = []
    for name, fam in FAMILIES.items():
        for k in (1, 4, 16):
            mode = ProjectionMode.BLOCK if k > 1 else ProjectionMode.FULL

            def one(seed, k=k, mode=mode, dist=fam.distribution):
                r = project_tree(delta, seed, dist, k, mode)
                return reconstruct_tree(delta, seed, r, dist, k, mode)["w"]

            f = jax.jit(jax.vmap(one))
            ts = jnp.arange(trials, dtype=jnp.uint32)
            f(ts).block_until_ready()           # warmup: exclude compile
            t0 = time.perf_counter()
            recs = jax.block_until_ready(f(ts))
            us = (time.perf_counter() - t0) / trials * 1e6
            meas = float(jnp.sum(jnp.var(recs, axis=0)))
            pred = fam.predicted_variance(
                d, k, block_sqnorms=tree_block_sqnorms(delta, k))
            by32 = WireFormat("fp32", k).bytes_per_upload
            by16 = WireFormat("fp16", k).bytes_per_upload
            emit(f"direction_{name}_k{k}", us,
                 f"var={meas:.1f}_pred={pred:.1f}_bytes={by32}")
            rows.append((name, k, by32, by16, pred, meas, meas / pred))

    os.makedirs("experiments/directions", exist_ok=True)
    with open("experiments/directions/variance_sweep.csv", "w") as f:
        f.write("family,k,bytes_fp32,bytes_fp16,predicted_var,measured_var,"
                "measured_over_predicted\n")
        for r in rows:
            f.write(f"{r[0]},{r[1]},{r[2]},{r[3]},"
                    f"{r[4]:.4f},{r[5]:.4f},{r[6]:.4f}\n")


# ---------------------------------------------------------------------------
# federation runtime: server-side aggregation throughput
# ---------------------------------------------------------------------------

def bench_runtime_throughput():
    """Server clients/second aggregated vs cohort size, fori vs Pallas.

    The naive path is the jitted fori-loop ``server_aggregate``; the
    fused path is the chunked-grid Pallas kernel (interpret mode on
    CPU — structural comparison, not TPU timing).  Rows also land in
    ``experiments/runtime/throughput.csv`` for benchmarks.report.
    """
    import os

    from repro.core import fedscalar as fs
    from repro.kernels import ops

    params = {"w": jnp.asarray(np.random.RandomState(0).randn(512, 2048),
                               jnp.float32)}
    cfg = fs.FedScalarConfig()
    rows = []
    for n in (8, 64, 256, 1024):
        seeds = fs.round_seeds(0, n)
        rs = jnp.asarray(np.random.RandomState(1).randn(n, 1), jnp.float32)
        w = jnp.full((n,), 1.0 / n, jnp.float32)

        agg = jax.jit(lambda p, r, s, wt: fs.server_aggregate(p, r, s, cfg, wt))
        us_f, _ = timed(lambda: agg(params, rs, seeds, w)["w"])
        cps_f = n / (us_f / 1e6)
        emit(f"runtime_throughput_n{n}_fori", us_f, f"{cps_f:.0f}_clients/s")

        us_k, _ = timed(lambda: ops.server_update_kernel(
            params, rs[:, 0], seeds, weights=w)["w"], repeat=1)
        cps_k = n / (us_k / 1e6)
        emit(f"runtime_throughput_n{n}_pallas", us_k, f"{cps_k:.0f}_clients/s")
        rows.append((n, us_f, cps_f, us_k, cps_k))

    os.makedirs("experiments/runtime", exist_ok=True)
    with open("experiments/runtime/throughput.csv", "w") as f:
        f.write("cohort,fori_us,fori_clients_per_s,pallas_us,pallas_clients_per_s\n")
        for r in rows:
            f.write(",".join(f"{v:.1f}" for v in r) + "\n")


# ---------------------------------------------------------------------------
# fused megakernel: reconstruct+apply throughput vs the fori baseline
# ---------------------------------------------------------------------------

KERNELS_CSV = "experiments/kernels/fused_throughput.csv"


def bench_fused_kernel_throughput():
    """Fused reconstruct+apply vs the jitted fori baseline (DESIGN §11).

    Same 1M-param leaf and weighted-aggregation workload as
    ``bench_runtime_throughput``, but the contender is the **fused**
    megakernel serving path (``ops.server_update_fused``) under its
    autotuned parameters — on CPU the jnp mirror with a tuned
    ``row_slab``, on TPU the Pallas tile — instead of the
    interpret-mode Pallas structural check.  Both sides are timed
    post-compile in the same process, so the fused/fori ratio is a
    hardware-independent crossover figure; ``benchmarks.check_kernels``
    gates CI on ratio ≥ 1 at every cohort ≥ 256.  The autotune sweep
    itself is excluded from the timings, and its winners go to a
    scratch cache that lives for this run only (``kernels/tune.py``).
    """
    import os
    import tempfile

    from repro.core import fedscalar as fs
    from repro.kernels import ops, tune

    params = {"w": jnp.asarray(np.random.RandomState(0).randn(512, 2048),
                               jnp.float32)}
    cfg = fs.FedScalarConfig()
    rows = []
    tune_dir = tempfile.TemporaryDirectory()
    tune_cache = os.path.join(tune_dir.name, "fused_tune.json")
    for n in (8, 64, 256, 1024):
        seeds = fs.round_seeds(0, n)
        rs = jnp.asarray(np.random.RandomState(1).randn(n, 1), jnp.float32)
        w = jnp.full((n,), 1.0 / n, jnp.float32)

        agg = jax.jit(lambda p, r, s, wt: fs.server_aggregate(p, r, s, cfg, wt))
        us_f, _ = timed(lambda: agg(params, rs, seeds, w)["w"])
        cps_f = n / (us_f / 1e6)
        emit(f"fused_throughput_n{n}_fori", us_f, f"{cps_f:.0f}_clients/s")

        best = tune.autotune_fused(512, 2048, n, 1, cfg.distribution.value,
                                   cache_path=tune_cache)
        fused = jax.jit(lambda p, r, s, wt, b=best: ops.server_update_fused(
            p, r, s, weights=wt, distribution=cfg.distribution,
            use_pallas=b["impl"] == "pallas",
            block=tuple(b["block"]) if b["block"] else None,
            row_slab=b["row_slab"]))
        us_u, _ = timed(lambda: fused(params, rs, seeds, w)["w"])
        cps_u = n / (us_u / 1e6)
        emit(f"fused_throughput_n{n}_fused", us_u,
             f"{cps_u:.0f}_clients/s_{best['impl']}_slab{best['row_slab']}")
        rows.append((n, us_f, cps_f, us_u, cps_u, cps_u / cps_f,
                     best["impl"], best["row_slab"]))

    os.makedirs(os.path.dirname(KERNELS_CSV), exist_ok=True)
    with open(KERNELS_CSV, "w") as f:
        f.write("cohort,fori_us,fori_clients_per_s,fused_us,"
                "fused_clients_per_s,ratio,impl,row_slab\n")
        for r in rows:
            f.write(f"{r[0]},{r[1]:.1f},{r[2]:.1f},{r[3]:.1f},{r[4]:.1f},"
                    f"{r[5]:.4f},{r[6]},{r[7]}\n")
    tune_dir.cleanup()


# ---------------------------------------------------------------------------
# mesh-sharded server: reconstruction throughput vs device count
# ---------------------------------------------------------------------------

def bench_sharded_throughput():
    """Sharded server apply: elements/s reconstructed vs mesh devices.

    Sweeps mesh size (1/2/4/8 devices, capped at what the backend
    exposes — run under ``XLA_FLAGS=--xla_force_host_platform_
    device_count=8`` to see the full curve on CPU) × model dimension d
    × cohort size N, timing the **resident** shard_map decode of
    ``repro.sharding.fed_rules`` — the model stays sharded across
    rounds (``shard_tree`` + ``sharded_apply_blocks``), so the loop
    measures reconstruction, not host↔mesh parameter transfer (jnp
    local body — on CPU the numbers are a scaling-shape check, not TPU
    timing).  Rows land in ``experiments/sharding/throughput.csv`` for
    report §Sharding.
    """
    import os

    from repro.core import fedscalar as fs
    from repro.core.compat import make_mesh
    from repro.sharding import fed_rules as fr

    n_dev = len(jax.devices())
    shard_counts = [s for s in (1, 2, 4, 8) if s <= n_dev]
    rows = []
    for d in (1 << 18, 1 << 20):
        rows_2d = 512
        params = {"w": jnp.asarray(
            np.random.RandomState(0).randn(rows_2d, d // rows_2d), jnp.float32)}
        for cohort in (64, 256):
            seeds = fs.round_seeds(0, cohort)
            rs = jnp.asarray(np.random.RandomState(1).randn(cohort, 1),
                             jnp.float32)
            for s in shard_counts:
                mesh = make_mesh((1, s), ("data", "model"))
                plan = fr.plan_tree(params, s)
                blocks = fr.shard_tree(params, plan, mesh)

                @jax.jit
                def apply(b, r, sd, mesh=mesh, plan=plan):
                    return fr.sharded_apply_blocks(
                        mesh, plan, b, r, sd, use_kernel=False)

                us, _ = timed(lambda: apply(blocks, rs, seeds)[0], repeat=1)
                eps = d * cohort / (us / 1e6)    # regenerated elements/s
                emit(f"sharded_recon_d{d}_n{cohort}_dev{s}", us,
                     f"{eps:.3g}_elems/s")
                rows.append((d, cohort, s, us, eps))

    os.makedirs("experiments/sharding", exist_ok=True)
    with open("experiments/sharding/throughput.csv", "w") as f:
        f.write("d,cohort,devices,us_per_apply,elements_per_s\n")
        for r in rows:
            f.write(f"{r[0]},{r[1]},{r[2]},{r[3]:.1f},{r[4]:.4g}\n")


# ---------------------------------------------------------------------------
# continuous-round scheduler: serving throughput at 10⁵ clients (DESIGN §10)
# ---------------------------------------------------------------------------

SCHEDULER_CSV = "experiments/scheduler/throughput.csv"


def bench_scheduler_throughput(population: int = 100_000, rounds: int = 20):
    """Sync vs async pipelined serving over a 10⁵-client population.

    One fedscalar × digest-downlink configuration (cohort 1000 at 1%
    participation, 0.1 Mbps, 20 ms access latency), driven twice: the
    sync scheduler (bit-identical to the legacy loop) and the async
    scheduler with rounds opened every 1 ms up to 32 in flight.  The
    reported clients/s is the **modeled serving timeline** (eq. 12″) —
    deterministic given the seed, so ``benchmarks.check_scheduler``
    can gate CI on a pinned floor and on async ≥ 10× sync.  Rows land
    in ``experiments/scheduler/throughput.csv`` for report §Scheduler.
    """
    import os

    from repro.data import load_digits, make_client_datasets, train_test_split_arrays
    from repro.fed.costmodel import ChannelConfig
    from repro.fed.runtime import RuntimeConfig, SchedulerConfig, run_federation
    from repro.models.mlp_classifier import init_mlp

    x, y = load_digits()
    xtr, ytr, xte, yte = train_test_split_arrays(x, y)
    clients = make_client_datasets(xtr, ytr, 20)
    p0 = init_mlp()

    base = dict(rounds=rounds, population=population, participation=0.01,
                seed=0, eval_every=10**6, downlink_mode="digest",
                channel=ChannelConfig(base_latency_s=0.02,
                                      lognormal_sigma=0.5))
    schedulers = dict(
        sync=SchedulerConfig(mode="sync"),
        async_pipelined=SchedulerConfig(mode="async", period_s=0.001,
                                        max_rounds_in_flight=32,
                                        staleness_window=4),
    )
    rows = []
    for mode, sched in schedulers.items():
        t0 = time.perf_counter()
        h = run_federation(RuntimeConfig(scheduler=sched, **base),
                           p0, clients, xte, yte)
        us = (time.perf_counter() - t0) / rounds * 1e6
        s = h["scheduler"]
        emit(f"scheduler_{mode}_n{population}", us,
             f"{s['clients_per_s']:.0f}_clients/s_"
             f"{s['rounds_per_s']:.1f}_rounds/s_"
             f"lag{s['params_lag_max']}")
        rows.append(dict(
            mode=mode, protocol="fedscalar", population=population,
            cohort=int(h["cohort_size"][0]), rounds=rounds,
            quorum_frac=s["quorum_frac"],
            period_s=s["period_s"] if s["period_s"] is not None else "",
            max_rounds_in_flight=s["max_rounds_in_flight"],
            makespan_s=f"{s['makespan_s']:.6f}",
            rounds_per_s=f"{s['rounds_per_s']:.3f}",
            clients_per_s=f"{s['clients_per_s']:.1f}",
            stale_admitted=s["stale_admitted"],
            stale_dropped=s["stale_dropped"],
            params_lag_max=s["params_lag_max"],
            queue_peak_bytes=s["queue_peak_bytes"],
            agg_state_bytes_peak=s["agg_state_bytes_peak"],
            client_state_bytes=s["client_state_bytes"]))

    os.makedirs(os.path.dirname(SCHEDULER_CSV), exist_ok=True)
    cols = list(rows[0])
    with open(SCHEDULER_CSV, "w") as f:
        f.write(",".join(cols) + "\n")
        for r in rows:
            f.write(",".join(str(r[c]) for c in cols) + "\n")


# ---------------------------------------------------------------------------
# roofline / dry-run summary
# ---------------------------------------------------------------------------

def bench_roofline():
    import glob
    import json
    recs = [json.load(open(p)) for p in glob.glob("experiments/dryrun/*.json")]
    baseline = [r for r in recs if r.get("variant", "baseline") == "baseline"]
    ok = [r for r in baseline if r.get("ok")]
    emit("dryrun_combos_compiled", 0.0, f"{len(ok)}/{len(baseline)}")
    try:
        from repro.launch.roofline import full_table
        rows = full_table()
        from collections import Counter
        c = Counter(r["dominant"] for r in rows)
        for k, v in sorted(c.items()):
            emit(f"roofline_dominant_{k}", 0.0, f"{v}_combos")
    except Exception as e:  # dry-run artifacts may be absent
        emit("roofline_table", 0.0, f"skipped({type(e).__name__})")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rounds", type=int, default=300)
    ap.add_argument("--skip-digits", action="store_true")
    ap.add_argument("--only-scheduler", action="store_true",
                    help="just regenerate experiments/scheduler/throughput.csv")
    ap.add_argument("--only-kernels", action="store_true",
                    help="just regenerate experiments/kernels/"
                         "fused_throughput.csv")
    args = ap.parse_args()
    print("name,us_per_call,derived")
    if args.only_scheduler:
        bench_scheduler_throughput()
        print(f"# {len(ROWS)} benchmark rows", flush=True)
        return
    if args.only_kernels:
        bench_fused_kernel_throughput()
        print(f"# {len(ROWS)} benchmark rows", flush=True)
        return
    bench_table1()
    if not args.skip_digits:
        bench_digits(args.rounds)
        bench_baseline_tradeoff(args.rounds)
        bench_downlink_tradeoff(args.rounds)
    bench_prop21()
    bench_direction_sweep()
    bench_kernels()
    bench_runtime_throughput()
    bench_fused_kernel_throughput()
    bench_sharded_throughput()
    bench_scheduler_throughput()
    bench_roofline()
    print(f"# {len(ROWS)} benchmark rows", flush=True)


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
