"""Federation runtime: sampling unbiasedness, wire codec, server state
machine, engine paths (fused ≡ simulation bit-for-bit; event-driven
statistics)."""
import dataclasses
import math

import jax.numpy as jnp
import numpy as np
import pytest

from repro.fed.costmodel import ChannelConfig
from repro.fed.runtime import (
    ClientPopulation,
    CohortSampler,
    RuntimeConfig,
    ServerConfig,
    StreamingAggregator,
    Upload,
    WireFormat,
    decode_upload,
    encode_upload,
    run_federation,
)


# ---------------------------------------------------------------------------
# cohort sampling — Horvitz–Thompson unbiasedness
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kind", ["uniform", "weighted", "poisson"])
def test_sampler_unbiased_estimator(kind):
    """E[Σ_{n∈S} wₙ·xₙ] = (1/N)·Σₙ xₙ over many sampled rounds."""
    n = 400
    rng = np.random.RandomState(0)
    values = rng.randn(n) + 2.0
    weights = rng.uniform(0.5, 4.0, size=n) if kind == "weighted" else None
    pop = ClientPopulation(n, weights=weights)
    sampler = CohortSampler(pop, participation=0.1, kind=kind, seed=3)
    rounds = 3000
    est = np.zeros(rounds)
    for k in range(rounds):
        c = sampler.sample(k)
        est[k] = np.sum(values[c.client_ids] * c.agg_weights)
    true_mean = values.mean()
    err = abs(est.mean() - true_mean) / abs(true_mean)
    # MC std of the mean over 3000 rounds ≲ 1%; allow 3 sigma
    assert err < 0.03, (kind, est.mean(), true_mean)


def test_sampler_marginals_match_declared_pi():
    n, rounds = 200, 4000
    pop = ClientPopulation(n, weights=np.arange(1, n + 1, dtype=float))
    sampler = CohortSampler(pop, participation=0.05, kind="weighted", seed=7)
    counts = np.zeros(n)
    pi = np.zeros(n)
    for k in range(rounds):
        c = sampler.sample(k)
        counts[c.client_ids] += 1
        pi[c.client_ids] = c.inclusion_probs
    seen = pi > 0
    # binomial std ≈ sqrt(π/rounds) ≤ 0.007 at π≤0.1; allow 5σ + never-sampled tail
    assert np.max(np.abs(counts[seen] / rounds - pi[seen])) < 0.035


def test_sampler_deterministic_and_sorted():
    pop = ClientPopulation(1000)
    s = CohortSampler(pop, 0.02, "uniform", seed=1)
    a, b = s.sample(5), s.sample(5)
    assert np.array_equal(a.client_ids, b.client_ids)
    assert np.all(np.diff(a.client_ids) > 0)
    assert not np.array_equal(a.client_ids, s.sample(6).client_ids)


def test_weight_sum_expectation_is_one():
    pop = ClientPopulation(300)
    s = CohortSampler(pop, 0.1, "poisson", seed=2)
    sums = [s.sample(k).agg_weights.sum() for k in range(2000)]
    assert abs(np.mean(sums) - 1.0) < 0.02


# ---------------------------------------------------------------------------
# wire codec — byte-exact round trips at every scalar width
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("scalar,bits", [("fp32", 64), ("fp16", 48), ("bf16", 48)])
def test_codec_byte_exact_roundtrip(scalar, bits):
    fmt = WireFormat(scalar=scalar)
    assert fmt.bits_per_upload == bits
    rng = np.random.RandomState(0)
    for _ in range(50):
        r = rng.randn(1).astype(np.float32) * 10 ** rng.randint(-3, 4)
        seed = int(rng.randint(0, 2**32, dtype=np.uint64))
        packet = encode_upload(r, seed, fmt)
        assert len(packet) == fmt.bytes_per_upload
        r_hat, seed_hat = decode_upload(packet, fmt)
        assert seed_hat == seed
        # decode∘encode is idempotent at the byte level
        assert encode_upload(r_hat, seed_hat, fmt) == packet
        if scalar == "fp32":
            np.testing.assert_array_equal(r_hat, r)


def test_codec_multi_projection():
    fmt = WireFormat(scalar="fp16", num_projections=4)
    assert fmt.bits_per_upload == 4 * 16 + 32
    r = np.asarray([1.5, -2.25, 0.125, 3.0], np.float32)  # fp16-exact values
    r_hat, seed = decode_upload(encode_upload(r, 0xDEADBEEF, fmt), fmt)
    np.testing.assert_array_equal(r_hat, r)
    assert seed == 0xDEADBEEF


# ---------------------------------------------------------------------------
# server state machine
# ---------------------------------------------------------------------------

def _up(**kw):
    d = dict(client_id=0, encoded_round=0, seed=1, r=np.ones(1, np.float32),
             agg_weight=0.1, latency_s=0.0, lost=False)
    d.update(kw)
    return Upload(**d)


def test_aggregator_deadline_drops_stragglers():
    agg = StreamingAggregator(ServerConfig(deadline_s=1.0))
    assert agg.offer(_up(latency_s=0.5)) == "applied"
    assert agg.offer(_up(latency_s=2.0)) == "dropped"
    assert agg.offer(_up(lost=True)) == "lost"
    seeds, coeffs, rs, st = agg.close_round(0)
    assert len(seeds) == 1 and st.applied == 1
    assert st.dropped_deadline == 1 and st.lost_channel == 1


def test_aggregator_async_staleness_weighting():
    cfg = ServerConfig(max_staleness=2, staleness_exponent=1.0, round_period_s=1.0)
    agg = StreamingAggregator(cfg)
    assert agg.offer(_up(latency_s=0.5)) == "applied"       # τ=0
    assert agg.offer(_up(latency_s=1.5)) == "deferred"      # τ=1
    assert agg.offer(_up(latency_s=5.0)) == "dropped"       # τ=5 > τ_max
    _, c0, _, st0 = agg.close_round(0)
    np.testing.assert_allclose(c0, [0.1])                   # w·(1+0)⁻¹ = w
    _, c1, _, st1 = agg.close_round(1)
    np.testing.assert_allclose(c1, [0.05])                  # w·(1+1)⁻¹
    assert st1.applied_stale == 1 and st1.max_tau == 1
    assert st0.dropped_stale == 1


def test_aggregator_tau_zero_reduces_to_sync():
    """With round_period=∞ every upload has τ=0: async ≡ sync coefficients."""
    ups = [_up(seed=i, agg_weight=0.1 * (i + 1), latency_s=float(i))
           for i in range(5)]
    sync = StreamingAggregator(ServerConfig())
    asyn = StreamingAggregator(ServerConfig(max_staleness=4, staleness_exponent=2.0))
    for u in ups:
        sync.offer(u)
        asyn.offer(u)
    s_seeds, s_coeffs, s_rs, _ = sync.close_round(0)
    a_seeds, a_coeffs, a_rs, _ = asyn.close_round(0)
    np.testing.assert_array_equal(s_seeds, a_seeds)
    np.testing.assert_array_equal(s_coeffs, a_coeffs)
    np.testing.assert_array_equal(s_rs, a_rs)


# ---------------------------------------------------------------------------
# engine — fused equivalence + event-driven statistics
# ---------------------------------------------------------------------------

def _digits(num_shards=8):
    from repro.data import load_digits, make_client_datasets, train_test_split_arrays
    x, y = load_digits(n_samples=400)
    xtr, ytr, xte, yte = train_test_split_arrays(x, y)
    return make_client_datasets(xtr, ytr, num_shards), xte, yte


def test_full_participation_reproduces_simulation_bitforbit():
    """participation=1.0, deadline=∞ → run_simulation trajectory exactly."""
    from repro.fed import SimulationConfig, run_simulation
    from repro.models.mlp_classifier import init_mlp

    clients, xte, yte = _digits(8)
    p0 = init_mlp()
    rt = run_federation(
        RuntimeConfig(rounds=25, population=8, participation=1.0),
        p0, clients, xte, yte)
    sim = run_simulation(
        SimulationConfig(method="fedscalar_rademacher", rounds=25, num_clients=8),
        p0, clients, xte, yte)
    assert rt["fused_path"]
    np.testing.assert_array_equal(rt["loss"], sim["loss"])
    np.testing.assert_array_equal(rt["accuracy"], sim["accuracy"])
    for a, b in zip(np.asarray(rt["final_params"]["w1"]),
                    np.asarray(sim["final_params"]["w1"])):
        np.testing.assert_array_equal(a, b)


@pytest.mark.slow
def test_event_driven_partial_participation_descends():
    from repro.models.mlp_classifier import init_mlp

    from repro.fed.runtime.engine import cohort_sampler
    from repro.fed.runtime.sampling import sampling_diagnostic

    clients, xte, yte = _digits(8)
    cfg = RuntimeConfig(rounds=40, population=500, participation=0.08,
                        eval_every=39)
    h = run_federation(cfg, init_mlp(), clients, xte, yte)
    assert not h["fused_path"]
    evals = ~np.isnan(h["loss"])
    assert h["loss"][evals][-1] < h["loss"][evals][0]
    assert np.all(h["cohort_size"] == 40)
    diag = sampling_diagnostic(cohort_sampler(cfg, clients), rounds=160)
    assert diag["estimate_rel_err"] < 0.1
    # Σwᵢ per round should hover around 1 (IPW correctness)
    assert abs(np.mean(h["weight_sum"]) - 1.0) < 0.05


@pytest.mark.slow
def test_event_driven_async_matches_sync_at_tau_zero():
    """round_period=∞ keeps every upload at τ=0: same trajectory as sync."""
    from repro.models.mlp_classifier import init_mlp

    clients, xte, yte = _digits(8)
    p0 = init_mlp()
    base = RuntimeConfig(rounds=10, population=200, participation=0.1)
    h_sync = run_federation(base, p0, clients, xte, yte)
    h_async = run_federation(
        dataclasses.replace(base, server=ServerConfig(
            max_staleness=3, staleness_exponent=0.5)),
        p0, clients, xte, yte)
    np.testing.assert_array_equal(h_sync["loss"], h_async["loss"])
    for a, b in zip(np.asarray(h_sync["final_params"]["w0"]),
                    np.asarray(h_async["final_params"]["w0"])):
        np.testing.assert_array_equal(a, b)


def test_event_driven_deadline_and_loss_account():
    from repro.models.mlp_classifier import init_mlp

    clients, xte, yte = _digits(8)
    p0 = init_mlp()
    h = run_federation(
        RuntimeConfig(rounds=6, population=200, participation=0.2,
                      server=ServerConfig(deadline_s=0.0005),
                      channel=ChannelConfig(drop_prob=0.2)),
        p0, clients, xte, yte)
    offered = h["cohort_size"].sum()
    accounted = (h["applied"].sum() + h["lost_channel"].sum()
                 + h["dropped_deadline"].sum())
    assert offered == accounted
    assert h["dropped_deadline"].sum() > 0 and h["lost_channel"].sum() > 0
    # wall-clock per round is capped by the deadline (+t_other)
    per_round_wall = np.diff(np.concatenate([[0.0], h["cum_wall_s"]]))
    assert np.all(per_round_wall <= 0.0005 + 1.0)   # t_other ≪ 1 s


def test_weighted_server_aggregate_matches_uniform():
    """weights=1/N reproduces the unweighted paper aggregation."""
    import jax
    from repro.core import fedscalar as fs
    from repro.models.mlp_classifier import init_mlp

    params = init_mlp(seed=5)
    n = 6
    rs = jnp.asarray(np.random.RandomState(0).randn(n, 1), jnp.float32)
    seeds = fs.round_seeds(3, n)
    cfg = fs.FedScalarConfig()
    uni = fs.server_aggregate(params, rs, seeds, cfg)
    wei = fs.server_aggregate(params, rs, seeds, cfg,
                              weights=jnp.full((n,), 1.0 / n))
    for a, b in zip(jax.tree_util.tree_leaves(uni),
                    jax.tree_util.tree_leaves(wei)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-6)


def test_kernel_weighted_update_matches_fori():
    """Chunked Pallas path ≡ weighted fori aggregation for a big cohort."""
    from repro.core import fedscalar as fs
    from repro.kernels import ops

    params = {"w": jnp.asarray(np.random.RandomState(1).randn(64, 256),
                               jnp.float32)}
    n = 80   # > one client chunk → exercises the grid dimension
    rng = np.random.RandomState(2)
    rs = jnp.asarray(rng.randn(n, 1), jnp.float32)
    seeds = fs.round_seeds(0, n)
    w = jnp.asarray(rng.uniform(0.0, 0.02, n), jnp.float32)
    cfg = fs.FedScalarConfig(server_lr=0.7)
    ref = fs.server_aggregate(params, rs, seeds, cfg, weights=w)
    ker = ops.server_update_kernel(params, rs[:, 0], seeds, server_lr=0.7,
                                   weights=w)
    np.testing.assert_allclose(np.asarray(ker["w"]), np.asarray(ref["w"]),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.slow
def test_wire_width_fp16_still_trains():
    from repro.models.mlp_classifier import init_mlp

    clients, xte, yte = _digits(8)
    h = run_federation(
        RuntimeConfig(rounds=30, population=100, participation=0.2,
                      scalar_format="fp16", eval_every=29),
        init_mlp(), clients, xte, yte)
    assert h["bits_per_client_per_round"] == 48
    evals = ~np.isnan(h["loss"])
    assert h["loss"][evals][-1] < h["loss"][evals][0]
