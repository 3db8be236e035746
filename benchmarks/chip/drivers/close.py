"""Round close of a resident model: the server side of cross-device FL.

Set-up builds one ``EngineCore`` around the model, whose weights the
benchmark makes on the device from the seed, and closes one warm round.
The window then closes whole rounds, each through the calls the
synchronous scheduler makes per round: the cohort draw, ``transmit`` of
the uploads through the wire codec and channel, ``offer_uploads`` to
the streaming aggregator, ``close_round`` and ``apply_round`` (the
fused reconstruct+apply kernel).  The uploads, one (seed, scalar) pair
per client, come from the benchmark's seed in place of client compute.

The check: two rounds of the window, one drawn from the seed among the
first three and the last, are closed again by the plain reference
(``refs.close``) from the parameters before the round and the uploads
the benchmark offered; the widest gap to what the program returned is
compared in steps of the parameter type.
"""
from __future__ import annotations

import time

import numpy as np

__all__ = ["setup", "window", "check", "control"]

# Widest gap between the program's closed tree and the reference's, in
# steps of bfloat16 (PERF.md gives the readings behind it).
GAP_LIMIT = 40.0
# The check reads rounds of the measured window.
CHECK_READS_WINDOW = True


def _make_params(shapes, seed_words, dtype):
    """Random weights in their served type, made in one jitted call:
    norm scales 1 + 0.02 N(0, 1), every other leaf 0.02 N(0, 1)."""
    import jax
    import jax.numpy as jnp

    dtype = jnp.dtype(dtype)
    paths, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    @jax.jit
    def make(key):
        keys = jax.random.split(key, len(paths))
        out = []
        for k, (path, s) in zip(keys, paths):
            z = 0.02 * jax.random.normal(k, s.shape, jnp.float32)
            if "norm" in jax.tree_util.keystr(path):
                z = z + 1.0
            out.append(z.astype(dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    return make(jax.random.PRNGKey(int(seed_words[0]) & 0x7FFFFFFF))


def _program_arch(config):
    from repro.configs.registry import get_arch

    arch = get_arch(config["program_arch"],
                    reduced=bool(config.get("program_reduced", False)))
    if not config.get("program_reduced"):
        c = arch.cfg
        stated = {"num_hidden_layers": c.num_layers, "hidden_size": c.d_model,
                  "num_attention_heads": c.num_heads,
                  "num_key_value_heads": c.num_kv_heads,
                  "intermediate_size": c.d_ff, "vocab_size": c.vocab_size,
                  "tie_word_embeddings": c.tie_embeddings}
        wrong = {k: (v, config[k]) for k, v in stated.items() if config[k] != v}
        if wrong:
            raise ValueError(f"program's {config['program_arch']} differs "
                             f"from the configuration: {wrong}")
    return arch


def setup(config, traffic, seed, ctx):
    import jax

    from repro.core.projection import tree_size
    from repro.fed.runtime import RuntimeConfig
    from repro.fed.runtime.engine import EngineCore

    words = np.random.SeedSequence(seed).generate_state(4)
    arch = _program_arch(config)
    shapes = jax.eval_shape(arch.init, jax.random.PRNGKey(0))
    params = _make_params(shapes, words, config["torch_dtype"])
    d = tree_size(params)
    if not config.get("program_reduced") and d != config["parameters"]:
        raise ValueError(f"model has {d} parameters, configuration states "
                         f"{config['parameters']}")
    rcfg = RuntimeConfig(
        rounds=1, population=traffic["population"],
        participation=traffic["participation"], family=traffic["family"],
        num_projections=traffic["num_projections"],
        projection_mode=traffic["projection_mode"],
        server_lr=traffic["server_lr"], seed=int(words[1]) & 0x7FFFFFFF)
    if rcfg.cohort_size() != traffic["cohort"]:
        raise ValueError(f"cohort {rcfg.cohort_size()} != {traffic['cohort']}")
    proto = rcfg.build_protocol(params)
    # No client computes in this cell: the engine's compute and eval
    # stages get one-row placeholders and are never called.
    one = (np.zeros((1, 1), np.float32), np.zeros(1, np.int32))
    core = EngineCore(rcfg, params, [one], one[0], one[1],
                      grad_fn=lambda p, b: p,
                      eval_fns=(lambda p, b: 0.0, lambda p, x, y: 0.0),
                      client_weights=None, proto=proto, d=d)
    st = {"core": core, "params": params, "k": 0, "traffic": traffic,
          "rng": np.random.Generator(np.random.PCG64(int(words[2]))),
          "pick": int(np.random.RandomState(int(words[3]) & 0x7FFFFFFF)
                      .randint(0, 3)),
          "off_path": 0, "shortfall": 0, "d": d,
          "param_bytes": max(x.dtype.itemsize
                             for x in jax.tree_util.tree_leaves(params))}
    st["params"], _ = _round(st, ctx.span)       # warm: compiles the close
    jax.block_until_ready(st["params"])
    return st


def _round(st, span):
    core, tr = st["core"], st["traffic"]
    k = st["k"]
    with span("sample_cohort"):
        cohort = core.sampler.sample(k)
    ids = cohort.client_ids
    c = len(ids)
    rs = (tr["scalar_std"] * st["rng"].standard_normal(
        (c, tr["num_projections"]))).astype(np.float32)
    seeds = st["rng"].integers(0, 2**32, c, dtype=np.uint32)
    with span("transmit"):
        tx = core.uplink.transmit(rs, seeds)
    with span("offer_uploads"):
        core.offer_uploads(ids, cohort.agg_weights, k, tx)
    with span("close_round"):
        aseeds, acoeffs, ars, rst = core.agg.close_round(k)
    with span("apply_round"):
        params, method, _ = core.apply_round(st["params"], aseeds, acoeffs,
                                             ars, c, rst)
    st["k"] = k + 1
    st["off_path"] += method != "fused"
    st["shortfall"] += c - rst.applied
    st["applied"] = st.get("applied", 0) + rst.applied
    st["attempted"] = st.get("attempted", 0) + 1
    return params, {"seeds": seeds, "rs": rs[:, 0], "applied": rst.applied,
                    "population": core.cfg.population}


def window(st, seconds, span):
    st["applied"] = st["attempted"] = 0
    st["off_path"] = st["shortfall"] = 0
    kept = {}
    rounds = 0
    t0 = time.perf_counter()
    while True:
        pre = st["params"]
        st["params"], info = _round(st, span)
        last = (pre, st["params"], info)
        if rounds == st["pick"]:
            kept[rounds] = last
        rounds += 1
        if time.perf_counter() - t0 >= seconds:
            break
    elapsed = time.perf_counter() - t0
    kept[rounds - 1] = last
    st["kept"] = kept
    return {"elapsed_s": elapsed, "rounds": rounds,
            "uploads": st["applied"], "attempted": st["attempted"]}


def failed_rounds(st) -> int:
    import jax
    import jax.numpy as jnp

    finite = all(bool(jnp.all(jnp.isfinite(x)))
                 for x in jax.tree_util.tree_leaves(st["params"]))
    return 0 if finite else st["attempted"]


def _weights(info, c):
    # Horvitz-Thompson weight of a uniform cohort of c out of N.
    n = info["population"]
    return np.full(c, 1.0 / (n * (c / n)))


def check(st, out_dtype=None) -> dict:
    """→ {name: (value, limit)}; frees the engine first.

    ``out_dtype`` puts the reference, in that narrower type, in the
    program's place (the control).
    """
    from refs.close import close_tree, gap_steps

    traffic = st["traffic"]
    st.pop("core", None)
    st.pop("params", None)
    worst = 0.0
    for i in sorted(st["kept"]):
        pre, got, info = st["kept"].pop(i)
        c = len(info["seeds"])
        want = close_tree(pre, info["seeds"], info["rs"], _weights(info, c),
                          traffic["server_lr"])
        if out_dtype is not None:
            got = close_tree(pre, info["seeds"], info["rs"], _weights(info, c),
                             traffic["server_lr"], out_dtype=out_dtype)
        coef = info["rs"] * _weights(info, c) * traffic["server_lr"]
        floor = float(np.sqrt(np.sum(np.square(coef))))
        worst = max(worst, gap_steps(got, want, pre, floor))
        del pre, got, want
    return {"close_gap_steps": (worst, GAP_LIMIT),
            "uploads_not_applied": (float(st["shortfall"]), 0.0),
            "rounds_off_fused_path": (float(st["off_path"]), 0.0)}


def control(st) -> dict:
    """The reference rounded to float8 (e4m3) in the program's place."""
    import jax.numpy as jnp

    return check(st, out_dtype=jnp.float8_e4m3fn)


def counts(st) -> dict:
    from counts import close_bytes, close_flops

    c, d, k = st["traffic"]["cohort"], st["d"], st["traffic"]["num_projections"]
    return {"flops_per_round": close_flops(d, c, k),
            "close_flops": close_flops(d, c, k),
            "close_bytes": close_bytes(d, c, st["param_bytes"], k),
            "close_kernel": st["traffic"]["close_kernel"]}
