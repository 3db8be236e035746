#!/usr/bin/env python3
"""Smoke run of FedScalar on a TPU through its user entry points.

    python chip_smoke.py            # one chip: device, engine, llm_round,
                                    #           server_close
    python chip_smoke.py --chips 4  # four chips: the mesh-sharded server
                                    #             and what it is compared with

Phases (one process, run in order; any failure raises and exits non-zero):

* ``device``       — the first JAX device must be a TPU; there is no CPU
  fallback.
* ``engine``       — ``run_federation`` on the synthetic digits with the
  paper MLP (20 clients, full participation, sync scheduler, 5 rounds),
  once with the fused Pallas round close plus the digest downlink and its
  in-run shadow replay, once with the default fori close.  The two final
  models must agree, the shadow replay must stay bit-identical (the
  engine raises otherwise), and the test loss must fall.
* ``llm_round``    — 3 FedScalar rounds of ``launch.train.make_train_step``
  on ``smollm-360m`` at its published widths (bf16, random weights from
  a seed): 4 virtual clients × 2 local steps × batch 2 × seq 512.
* ``server_close`` — cohort-256 round closes over that parameter tree
  through the Pallas fused megakernel and the two-kernel path, checked
  leaf by leaf on the chip against ``kernels.ref.server_update_fused_ref``
  and ``core.fedscalar.server_aggregate``; every route must lower to a
  Mosaic kernel (no interpreter, no jnp fallback).
* ``mesh`` (``--chips 4`` only) — ``fed_rules.sharded_server_update`` over
  a (1, 4) mesh on the smollm tree against the single-device kernel
  apply, per-device bytes of the sharded views, and
  ``run_federation(mesh_shape=(1, 4))`` against the unsharded run.

Each phase prints one JSON line with its backend-compile seconds, the
rest of its wall time as run seconds, and the device's
``peak_bytes_in_use``: smoke timings of one cold run, not
benchmark numbers.  The last line is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))

COHORT = 256
SERVER_LR = 1.0
# Engine runs: the fused close is a different float association than
# the fori close (DESIGN §11), so five rounds apart they agree closely,
# not bitwise.
ENGINE_RTOL, ENGINE_ATOL = 1e-3, 1e-4


class Clock:
    """Seconds of XLA/Mosaic backend compilation, from JAX's own events.

    Only the backend-compile event is summed: JAX's trace and lowering
    events nest (an inner jit's trace runs inside the outer one), so
    adding them counts the same seconds twice.
    """

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self, jax):
        self.compile_s = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event == self.EVENT:
            self.compile_s += duration


def run_phase(name, fn, clock, jax):
    c0, t0 = clock.compile_s, time.perf_counter()
    detail = fn() or {}
    wall = time.perf_counter() - t0
    compile_s = clock.compile_s - c0
    stats = jax.devices()[0].memory_stats() or {}
    print(json.dumps({
        "phase": name, "passed": True,
        "compile_s": compile_s, "run_s": wall - compile_s,
        "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
        "timing": "smoke (one cold run), not a benchmark",
        **detail}), flush=True)


def digits_setup(num_clients: int):
    from repro.data import (load_digits, make_client_datasets,
                            train_test_split_arrays)
    from repro.models.mlp_classifier import init_mlp

    x, y = load_digits()
    xtr, ytr, xte, yte = train_test_split_arrays(x, y)
    return init_mlp(), make_client_datasets(xtr, ytr, num_clients), xte, yte


def digits_run(mesh_shape=None, **overrides):
    from repro.fed.runtime import RuntimeConfig, SchedulerConfig, run_federation

    p0, clients, xte, yte = digits_setup(20)
    cfg = RuntimeConfig(rounds=5, population=20, participation=1.0,
                        scheduler=SchedulerConfig(mode="sync"),
                        mesh_shape=mesh_shape, **overrides)
    return p0, run_federation(cfg, p0, clients, xte, yte), (xte, yte)


def assert_trees_close(a, b, what: str):
    import jax

    for x, y in zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   rtol=ENGINE_RTOL, atol=ENGINE_ATOL,
                                   err_msg=what)


# ---------------------------------------------------------------------------
# one-chip phases
# ---------------------------------------------------------------------------


def phase_engine():
    from repro.models.mlp_classifier import mlp_loss

    p0, fused, (xte, yte) = digits_run(projection_mode="fused_kernel",
                                       downlink_mode="digest",
                                       verify_replay=True)
    _, fori, _ = digits_run()
    assert_trees_close(fused["final_params"], fori["final_params"],
                       "fused vs fori engine close")
    loss0 = float(mlp_loss(p0, (xte, yte)))
    for name, h in (("fused", fused), ("fori", fori)):
        if not (np.isfinite(h["loss"][-1]) and h["loss"][-1] < loss0):
            raise AssertionError(f"{name} run: test loss {h['loss']} did not "
                                 f"fall from {loss0}")
    return {"loss0": loss0, "loss_fused": float(fused["loss"][-1]),
            "loss_fori": float(fori["loss"][-1]),
            "shadow_replay": "bit-identical every round"}


def smollm_params():
    import jax

    from repro.configs.registry import get_arch

    arch = get_arch("smollm-360m")
    return arch, jax.jit(arch.init)(jax.random.PRNGKey(0))


def phase_llm_round(state: dict):
    import jax
    import jax.numpy as jnp

    from repro.launch.train import FLRunConfig, make_train_step

    arch, params = smollm_params()
    fl = FLRunConfig(num_virtual_clients=4, local_steps=2)
    step = jax.jit(make_train_step(arch, fl))
    rng = np.random.RandomState(0)
    batch_rows, seq = fl.num_virtual_clients * fl.local_steps * 2, 512
    p, losses = params, []
    for k in range(3):
        toks = rng.randint(0, arch.cfg.vocab_size, (batch_rows, seq + 1))
        batch = {"tokens": jnp.asarray(toks[:, :-1], jnp.int32),
                 "labels": jnp.asarray(toks[:, 1:], jnp.int32)}
        p, metrics = step(p, batch, jnp.int32(k))
        losses.append(float(metrics["loss"]))
    if not all(np.isfinite(losses)):
        raise AssertionError(f"non-finite round loss: {losses}")
    changed = any(bool(jnp.any(a != b)) for a, b in zip(
        jax.tree_util.tree_leaves(p), jax.tree_util.tree_leaves(params)))
    if not changed:
        raise AssertionError("three FedScalar rounds left the params unchanged")
    d = sum(x.size for x in jax.tree_util.tree_leaves(params))
    state["params"] = p
    return {"arch": arch.cfg.name, "d": int(d), "round_losses": losses}


def one_layer(params, with_embedding: bool):
    """Layer 0 of every stacked leaf (plus the embedding): real widths."""
    import jax

    period = jax.tree_util.tree_map(lambda w: w[0], params["period"][0])
    tree = {"layer0": period, "final_norm": params["final_norm"]}
    if with_embedding:
        tree["embed"] = params["embed"]
    return tree


def compile_on_chip(fn, nleaves: int, *args):
    """Compile ``fn`` → executable; every leaf must run a Mosaic kernel
    and no host callback (the Pallas interpreter's signature) may be in
    the program."""
    import jax

    compiled = jax.jit(fn).lower(*args).compile()
    text = compiled.as_text()
    if "callback" in text:
        raise AssertionError("compiled round close holds a host callback")
    if text.count("tpu_custom_call") < nleaves:
        raise AssertionError(f"{text.count('tpu_custom_call')} Mosaic "
                             f"kernels for {nleaves} leaves")
    return compiled


def leaf_report(got, want, what: str, rtol: float = 0.0,
                atol_frac: float = 0.0) -> float:
    """On-chip leaf-by-leaf comparison → max |got − want| over the tree.

    Raises on any non-finite value (NaN never passes a comparison here)
    and on any leaf outside ``|Δ| ≤ rtol·|want| + atol_frac·max|want|``.
    With both tolerances 0 the check is on the bit patterns.
    """
    import jax
    import jax.numpy as jnp

    max_abs = 0.0
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        g32, w32 = g.astype(jnp.float32), w.astype(jnp.float32)
        if not bool(jnp.all(jnp.isfinite(g32)) & jnp.all(jnp.isfinite(w32))):
            raise AssertionError(f"{what}: non-finite values in a leaf")
        diff = jnp.abs(g32 - w32)
        if rtol == 0 and atol_frac == 0:
            bits = {2: jnp.uint16, 4: jnp.uint32}[g.dtype.itemsize]
            ok = g.dtype == w.dtype and bool(jnp.all(
                jax.lax.bitcast_convert_type(g, bits)
                == jax.lax.bitcast_convert_type(w, bits)))
        else:
            tol = atol_frac * jnp.max(jnp.abs(w32)) + rtol * jnp.abs(w32)
            ok = bool(jnp.all(diff <= tol))
        max_abs = max(max_abs, float(jnp.max(diff)))
        if not ok:
            raise AssertionError(f"{what}: max |Δ| {max_abs} over "
                                 f"rtol={rtol}, atol_frac={atol_frac}")
    return max_abs


def phase_server_close(state: dict):
    import jax
    import jax.numpy as jnp

    from repro.core import fedscalar as fs
    from repro.core.prng import Distribution
    from repro.core.projection import ProjectionMode
    from repro.kernels import ops, ref

    params = state.pop("params")
    rng = np.random.RandomState(1)
    seeds = jnp.asarray(rng.randint(0, 2**32, COHORT, dtype=np.uint32))
    cases = [
        ("rademacher", 1, params),
        ("hadamard", 1, one_layer(params, with_embedding=True)),
        ("gaussian", 1, one_layer(params, with_embedding=True)),
        # Masked BLOCK mode needs leaves inside the float32 mask domain
        # (2**24 elements): one layer without the embedding.
        ("rademacher", 4, one_layer(params, with_embedding=False)),
    ]
    out = []
    for dist_name, k, tree in cases:
        dist = Distribution(dist_name)
        mode = ProjectionMode.BLOCK if k > 1 else ProjectionMode.FULL
        rs = jnp.asarray(0.05 * rng.randn(COHORT, k), jnp.float32)
        nleaves = len(jax.tree_util.tree_leaves(tree))
        fused = functools.partial(ops.server_update_fused, server_lr=SERVER_LR,
                                  distribution=dist, mode=mode)
        kernel = functools.partial(ops.server_update_kernel,
                                   server_lr=SERVER_LR, distribution=dist,
                                   mode=mode)
        got_fused = compile_on_chip(fused, nleaves, tree, rs, seeds)(
            tree, rs, seeds)
        got_kernel = compile_on_chip(kernel, nleaves, tree, rs, seeds)(
            tree, rs, seeds)
        want_fused = ref.server_update_fused_ref(
            tree, rs, seeds, SERVER_LR, dist, num_projections=k, mode=mode)
        cfg = fs.FedScalarConfig(server_lr=SERVER_LR, distribution=dist,
                                 num_projections=k, mode=mode)
        want_fori = jax.jit(functools.partial(fs.server_aggregate, cfg=cfg))(
            tree, rs, seeds)

        # The fused close is its own numeric spec, bitwise against the
        # oracle for every family (DESIGN §11).
        leaf_report(got_fused, want_fused, f"fused {dist_name} k={k} vs oracle")
        # Two-kernel path vs the fori oracle: different association in
        # float32, one rounding to the parameter dtype — within one ulp.
        eps = float(jnp.finfo(jax.tree_util.tree_leaves(tree)[0].dtype).eps)
        max_abs_k = leaf_report(got_kernel, want_fori,
                                f"kernel {dist_name} k={k} vs fori", eps, 1e-3)
        out.append({"family": dist_name, "k": k, "leaves": nleaves,
                    "d": int(sum(x.size for x in jax.tree_util.tree_leaves(tree))),
                    "fused_vs_ref": "bitwise",
                    "kernel_vs_fori_max_abs": max_abs_k})
        del got_fused, got_kernel, want_fused, want_fori
    return {"cohort": COHORT, "closes": out}


# ---------------------------------------------------------------------------
# four-chip phase
# ---------------------------------------------------------------------------


def phase_mesh():
    import jax
    import jax.numpy as jnp

    from repro.core.prng import Distribution
    from repro.kernels import ops
    from repro.launch.mesh import make_fed_mesh
    from repro.sharding import fed_rules as fr

    mesh = make_fed_mesh((1, 4))
    _, params = smollm_params()
    rng = np.random.RandomState(2)
    seeds = jnp.asarray(rng.randint(0, 2**32, COHORT, dtype=np.uint32))
    rs = jnp.asarray(0.05 * rng.randn(COHORT, 1), jnp.float32)
    dist = Distribution.RADEMACHER

    sharded = jax.jit(functools.partial(
        fr.sharded_server_update, mesh, server_lr=SERVER_LR,
        distribution=dist))(params, rs, seeds)
    single = jax.jit(functools.partial(
        ops.server_update_kernel, server_lr=SERVER_LR,
        distribution=dist))(params, rs, seeds)
    for a, b in zip(jax.tree_util.tree_leaves(sharded),
                    jax.tree_util.tree_leaves(single)):
        if not bool(jnp.all(jax.device_put(a, b.sharding) == b)):
            raise AssertionError("sharded apply differs from the "
                                 "single-device kernel apply")

    plan = fr.plan_tree(params, fr.num_mesh_shards(mesh))
    blocks = fr.shard_tree(params, plan, mesh)
    per_dev: dict = {}
    for arr in blocks:
        for sh in arr.addressable_shards:
            per_dev[sh.device.id] = per_dev.get(sh.device.id, 0) + sh.data.nbytes
    total = sum(per_dev.values())
    if len(per_dev) != 4 or any(abs(b / total - 0.25) > 0.01
                                for b in per_dev.values()):
        raise AssertionError(f"uneven sharded bytes per device: {per_dev}")

    _, sharded_run, _ = digits_run(mesh_shape=(1, 4))
    _, plain_run, _ = digits_run()
    assert_trees_close(sharded_run["final_params"], plain_run["final_params"],
                       "mesh (1, 4) vs unsharded engine run")
    return {"cohort": COHORT, "sharded_vs_single": "bitwise",
            "bytes_per_device": {str(k): v for k, v in sorted(per_dev.items())},
            "engine_sharding": {k: v for k, v in sharded_run["sharding"].items()
                                if k != "mesh_shape"}}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="1: the one-chip phases; 4: the mesh-sharded "
                         "server on a (1, 4) mesh and nothing else")
    args = ap.parse_args()

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU — JAX found {devices[0].platform} "
              f"devices only; this smoke runs on the chip", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"devices, found {len(devices)}", file=sys.stderr)
        return 1

    sys.path.insert(0, os.path.join(HERE, "src"))
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    clock = Clock(jax)
    run_phase("device", lambda: {"kind": devices[0].device_kind,
                                 "count": len(devices)}, clock, jax)
    if args.chips == 4:
        run_phase("mesh", phase_mesh, clock, jax)
    else:
        state: dict = {}
        run_phase("engine", phase_engine, clock, jax)
        run_phase("llm_round", lambda: phase_llm_round(state), clock, jax)
        run_phase("server_close", lambda: phase_server_close(state), clock, jax)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
