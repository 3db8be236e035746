"""Mesh-sharded federation server: equivalence + statistical contracts.

DESIGN §7 invariants, asserted on real device meshes (8 forced host
devices — pinned by conftest so these never silently skip on
single-device CI runners):

* a (1, 1) mesh is **bit-identical** to the existing single-device
  kernel path, and the jnp local mirror agrees within one float32 ulp
  of reassociation;
* an N-shard mesh reconstructs bit-identically to the (1, 1) layout
  (reconstruction is elementwise in d — nothing reassociates), and the
  sharded projection matches the full-width call within fp32
  reassociation of its single k-scalar psum;
* the estimator stays **unbiased** through shard_map, and its measured
  variance matches the family's closed-form (d − 2 + κ) model from
  ``core/directions.py``.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.directions import FAMILIES
from repro.core.prng import Distribution
from repro.core.projection import ProjectionMode, project_tree
from repro.kernels import ops
from repro.sharding import fed_rules as fr


def _tree(seed=0):
    rng = np.random.RandomState(seed)
    return {
        "w": jnp.asarray(rng.randn(16, 120), jnp.float32),
        "b": jnp.asarray(rng.randn(300), jnp.float32),
    }


def _leaves(t):
    return [np.asarray(x) for x in jax.tree_util.tree_leaves(t)]


def _uploads(n, k, seed=3):
    seeds = jnp.arange(n, dtype=jnp.uint32) + 3
    rs = jnp.asarray(np.random.RandomState(seed).randn(n, k), jnp.float32)
    return seeds, rs


def test_mesh11_matches_single_device_path(fed_mesh_single):
    """(1, 1) mesh ≡ ops.server_update_kernel: the kernel local body bit
    for bit, the jnp mirror to fp32 fusion noise only."""
    tree = _tree()
    seeds, rs = _uploads(5, 2)
    want = ops.server_update_kernel(tree, rs, seeds, 0.5,
                                    mode=ProjectionMode.BLOCK)
    got_k = fr.sharded_server_update(
        fed_mesh_single, tree, rs, seeds, 0.5, mode=ProjectionMode.BLOCK,
        use_kernel=True)
    for a, b in zip(_leaves(got_k), _leaves(want)):
        assert np.array_equal(a, b)
    got_j = fr.sharded_server_update(
        fed_mesh_single, tree, rs, seeds, 0.5, mode=ProjectionMode.BLOCK,
        use_kernel=False)
    for a, b in zip(_leaves(got_j), _leaves(want)):
        np.testing.assert_allclose(a, b, rtol=0, atol=1e-6)


def test_multi_shard_reconstruction_matches_single(fed_mesh, fed_mesh_single):
    """8-shard reconstruction ≡ (1, 1): elementwise, so bit-identical —
    the jnp mirror across layouts, and the kernel body vs the unsharded
    kernel path."""
    tree = _tree(1)
    seeds, rs = _uploads(6, 2, seed=5)
    one = fr.sharded_server_update(
        fed_mesh_single, tree, rs, seeds, 0.5, mode=ProjectionMode.BLOCK,
        use_kernel=False)
    many = fr.sharded_server_update(
        fed_mesh, tree, rs, seeds, 0.5, mode=ProjectionMode.BLOCK,
        use_kernel=False)
    for a, b in zip(_leaves(one), _leaves(many)):
        assert np.array_equal(a, b)

    want = ops.server_update_kernel(tree, rs, seeds, 0.5,
                                    mode=ProjectionMode.BLOCK)
    many_k = fr.sharded_server_update(
        fed_mesh, tree, rs, seeds, 0.5, mode=ProjectionMode.BLOCK,
        use_kernel=True)
    for a, b in zip(_leaves(many_k), _leaves(want)):
        assert np.array_equal(a, b)


@pytest.mark.parametrize("dist,k,mode", [
    (Distribution.RADEMACHER, 1, ProjectionMode.FULL),
    (Distribution.HADAMARD, 3, ProjectionMode.BLOCK),
])
def test_sharded_fused_apply_matches_single_device(fed_mesh, dist, k, mode):
    """Mesh-sharded fused apply ≡ the single-device fused path, bitwise.

    ``use_fused=True`` routes every shard's local body through the
    megakernel mirror with its global SMEM offsets; reconstruction is
    elementwise in d, so the shard layout must not move a bit (the same
    DESIGN §7 contract the two-kernel path pins, now for the fused
    spec).  An awkward cohort (n=37, padded in-kernel to 48) and a
    non-tile-aligned multi-leaf tree keep the padding paths honest.
    """
    rng = np.random.RandomState(0)
    params = {"w": jnp.asarray(rng.randn(40, 180), jnp.float32),
              "b": jnp.asarray(rng.randn(100), jnp.float32)}
    n = 37
    seeds = jnp.asarray(rng.randint(0, 2**32, n, dtype=np.uint32))
    rs = jnp.asarray(rng.randn(n, k).astype(np.float32))
    many = fr.sharded_server_update(
        fed_mesh, params, rs, seeds, 0.5, dist, mode=mode,
        use_kernel=False, use_fused=True)
    one = ops.server_update_fused(params, rs, seeds, 0.5, dist, mode=mode,
                                  use_pallas=False)
    for a, b in zip(_leaves(many), _leaves(one)):
        assert np.array_equal(a, b)


@pytest.mark.parametrize("k", [1, 4])
def test_sharded_fused_pallas_rows_along_lanes(fed_mesh_single, k):
    """The mesh's fused local body on the Pallas path (interpret mode)
    hands the kernel its unpadded shard: a 320-column leaf closes
    rows-along-lanes, bit for bit with the single-device mirror.  (The
    offsets' composition across shards is pinned on the kernel itself,
    ``test_fused_rows_along_lanes_offsets_bit_identical``.)"""
    from repro.kernels.reconstruct_apply import fused_plan

    rng = np.random.RandomState(4)
    params = {"w": jnp.asarray(rng.randn(256, 320), jnp.float32)}
    assert fused_plan(256, 320).lanes_rows
    n = 20
    seeds = jnp.asarray(rng.randint(0, 2**32, n, dtype=np.uint32))
    rs = jnp.asarray(rng.randn(n, k).astype(np.float32))
    mode = ProjectionMode.BLOCK if k > 1 else ProjectionMode.FULL
    mesh = fr.sharded_server_update(
        fed_mesh_single, params, rs, seeds, 0.5, Distribution.RADEMACHER,
        mode=mode, use_kernel=True, use_fused=True)
    one = ops.server_update_fused(params, rs, seeds, 0.5,
                                  Distribution.RADEMACHER, mode=mode,
                                  use_pallas=False)
    assert np.array_equal(np.asarray(mesh["w"]), np.asarray(one["w"]))


def test_sharded_projection_single_psum(fed_mesh):
    """Sharded encode ≡ full-width projection within the k-scalar psum's
    fp32 reassociation — the round's only collective.  Single 1-D leaf
    (col-sharded) keeps the 8-way SPMD compile inside the fast tier;
    the multi-leaf masked case rides the slow weight-folding test."""
    tree = {"w": jnp.asarray(np.random.RandomState(2).randn(480), jnp.float32)}
    k = 2
    want = np.asarray(ops.project_tree_kernel(
        tree, 21, Distribution.RADEMACHER, num_blocks=k,
        mode=ProjectionMode.BLOCK))
    got = np.asarray(fr.sharded_project_tree(
        fed_mesh, tree, 21, Distribution.RADEMACHER, k, ProjectionMode.BLOCK,
        use_kernel=False))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-3)


@pytest.mark.slow
def test_sharded_multi_leaf_projection(fed_mesh):
    """Multi-leaf masked projection through the mesh matches the kernel."""
    tree = _tree(2)
    k = 3
    want = np.asarray(ops.project_tree_kernel(
        tree, 23, Distribution.GAUSSIAN, num_blocks=k,
        mode=ProjectionMode.BLOCK))
    got = np.asarray(fr.sharded_project_tree(
        fed_mesh, tree, 23, Distribution.GAUSSIAN, k, ProjectionMode.BLOCK,
        use_kernel=False))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-3)


@pytest.mark.slow
def test_sharded_weight_folding_matches_fori(fed_mesh):
    """HT weights + block shrinkage fold identically to server_aggregate."""
    from repro.core import fedscalar as fs

    tree = _tree(4)
    n, k = 7, 2
    seeds, rs = _uploads(n, k, seed=8)
    w = jnp.asarray(np.random.RandomState(9).rand(n) / n, jnp.float32)
    bw = jnp.asarray(np.linspace(0.6, 1.0, k), jnp.float32)
    cfg = fs.FedScalarConfig(server_lr=0.7, num_projections=k,
                             mode=ProjectionMode.BLOCK)
    want = fs.server_aggregate(tree, rs, seeds, cfg, weights=w,
                               block_weights=bw)
    got = fs.server_aggregate_mesh(tree, rs, seeds, cfg, fed_mesh, weights=w,
                                   block_weights=bw, use_kernel=False)
    for a, b in zip(_leaves(got), _leaves(want)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)


@pytest.mark.slow
def test_engine_mesh_run_matches_single_device(fed_mesh):
    """run_federation with mesh_shape reproduces the unsharded run and
    reports per-device accounting."""
    from repro.data import load_digits, make_client_datasets, \
        train_test_split_arrays
    from repro.fed.runtime.engine import RuntimeConfig, run_federation
    from repro.models.mlp_classifier import init_mlp

    x, y = load_digits()
    xtr, ytr, xte, yte = train_test_split_arrays(x, y)
    clients = make_client_datasets(xtr, ytr, 8)
    p0 = init_mlp()
    base = dict(rounds=2, population=16, participation=0.5, seed=1)
    h1 = run_federation(RuntimeConfig(**base), p0, clients, xte, yte)
    h8 = run_federation(RuntimeConfig(**base, mesh_shape=(2, 4)),
                        p0, clients, xte, yte)
    assert h1["sharding"] is None
    assert h8["sharding"]["devices"] == 8
    assert h8["sharding"]["per_device_elements"] > 0
    assert h8["recon_clients_per_s"] > 0
    for a, b in zip(_leaves(h1["final_params"]), _leaves(h8["final_params"])):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# Statistical contracts through shard_map
# ---------------------------------------------------------------------------

_D = 48


def _delta(seed=0):
    v = np.random.RandomState(seed).randn(_D).astype(np.float32)
    v /= np.linalg.norm(v)
    return {"w": jnp.asarray(v)}


def _estimates(mesh, family: str, trials: int) -> np.ndarray:
    """δ̂ for `trials` independent seeds, each through the sharded decode."""
    fam = FAMILIES[family]
    delta = _delta()
    seeds = jnp.arange(trials, dtype=jnp.uint32) * 977 + 13
    # Encode with the (independently tested) jnp reference; decode sharded.
    rs = jax.vmap(lambda s: project_tree(delta, s, fam.distribution))(seeds)
    zeros = jax.tree_util.tree_map(jnp.zeros_like, delta)

    @jax.jit
    def decode_one(seed, r):
        out = fr.sharded_server_update(
            mesh, zeros, r.reshape(1, 1), seed.reshape(1), 1.0,
            distribution=fam.distribution, use_kernel=False)
        return out["w"]

    return np.stack([np.asarray(decode_one(seeds[t], rs[t]))
                     for t in range(trials)])


@pytest.mark.parametrize("family", ["rademacher", "gaussian"])
def test_sharded_estimator_unbiased(fed_mesh, family):
    """E[δ̂] = δ within CI bounds when decoding runs through shard_map."""
    trials = 512
    est = _estimates(fed_mesh, family, trials)
    delta = np.asarray(_delta()["w"])
    err2 = float(np.sum((est.mean(axis=0) - delta) ** 2))
    kappa = FAMILIES[family].kurtosis
    expected = (_D - 2 + kappa) * 1.0 / trials   # E‖mean−δ‖² = Var/T, ‖δ‖²=1
    assert err2 < 4.0 * expected, (err2, expected)


@pytest.mark.parametrize("family", ["rademacher", "gaussian"])
def test_sharded_variance_matches_family_model(fed_mesh, family):
    """Measured E‖δ̂ − δ‖² tracks the (d − 2 + κ) closed form through
    shard_map (tolerance sized to the χ²-tailed trial noise)."""
    trials = 512
    est = _estimates(fed_mesh, family, trials)
    delta = np.asarray(_delta()["w"])
    measured = float(np.mean(np.sum((est - delta) ** 2, axis=1)))
    predicted = FAMILIES[family].predicted_variance(_D, 1, total_sqnorm=1.0)
    assert abs(measured / predicted - 1.0) < 0.25, (measured, predicted)
