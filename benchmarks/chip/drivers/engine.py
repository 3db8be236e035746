"""The FL runtime's synchronous scheduler over one compiled engine.

A run is one job of the deployment: one ``EngineCore`` and one call of
``run_scheduled`` from the weights the seed makes, for as many rounds
as the window holds.  Set-up sizes the job: a first core of
``TIMING_ROUNDS`` rounds on the same seed and data compiles every stage
(or loads it from the cache) and times its warm rounds, and the job's
core gets one round more than ``seconds`` over that time per round.
The job starts in set-up, on a thread of its own, and waits at the end
of its first round, once its stages are loaded; the window lets it go
on and ends when the call returns, its ``finalize`` included.

The benchmark watches each core through the entry points the scheduler
calls on it and its parts: a host span around each (the cohort draw,
``compute_cohort``, ``transmit``, ``offer_uploads``, ``close_round``,
``apply_round``, ``close_digest``, ``evaluate``), the time each round's
apply ends, the parameters after the first rounds, and the first
evaluation (the end of round 0), where the job waits for the window.

The check replays the job's first ``eval_every + 1`` rounds with the
plain reference (``refs.paper_mlp_fl``) from the same weights and data,
and compares the test loss the program evaluated after the first round
and after round ``eval_every + 1`` (inside the window), each leaf's
first update and its norm, and each leaf's change over three rounds.
"""
from __future__ import annotations

import dataclasses
import math
import threading
import time

import numpy as np

__all__ = ["setup", "window", "check", "control", "faults"]

# Limits, each between the readings of sound runs and of the control or
# a fault (PERF.md gives the readings behind each).
LIMITS = {
    "loss_gap": 2e-3,
    "first_update_gap": 3e-2,
    "first_update_norm_gap": 6e-3,
    "change_norm_gap": 1e-2,
}
# The check reads rounds of the measured window.
CHECK_READS_WINDOW = True
# Rounds of the core that times a warm round in set-up.
TIMING_ROUNDS = 6
# Rounds whose parameters the check keeps: the first update, and the
# change over three rounds.
KEPT_ROUNDS = 3


def _weights(sizes, seed_word):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def make(key):
        keys = jax.random.split(key, len(sizes) - 1)
        out = {}
        for i, (fi, fo) in enumerate(zip(sizes[:-1], sizes[1:])):
            lim = (6.0 / (fi + fo)) ** 0.5
            out[f"w{i}"] = jax.random.uniform(keys[i], (fi, fo), jnp.float32,
                                              -lim, lim)
            out[f"b{i}"] = jnp.zeros((fo,), jnp.float32)
        return out

    return make(jax.random.PRNGKey(int(seed_word) & 0x7FFFFFFF))


def _spanned(obj, name: str, span, label: str | None = None) -> None:
    """Wrap ``obj.name`` in a host span named ``label`` (or ``name``)."""
    fn = getattr(obj, name)

    def call(*a, **kw):
        with span(label or name):
            return fn(*a, **kw)

    setattr(obj, name, call)


class _Watch:
    """Spans, round ends and kept parameters of one core's run, taken at
    the entry points the scheduler calls on the core and its parts.
    With ``hold`` the run waits after its first evaluation (the end of
    round 0) until ``go`` is set."""

    def __init__(self, core, span, keep: int = 0, hold: bool = False):
        import jax

        self.ends: list[float] = []
        self.kept: list = []
        self.ready, self.go = threading.Event(), threading.Event()
        self.t_open = None
        apply_round, evaluate = core.apply_round, core.evaluate

        def watched_apply(params, *a, **kw):
            with span("apply_round"):
                out = apply_round(params, *a, **kw)
            if len(self.kept) < keep:
                self.kept.append(out[0])
            self.ends.append(time.perf_counter())
            return out

        def watched_eval(params):
            with span("evaluate"):
                out = jax.block_until_ready(evaluate(params))
            if hold and self.t_open is None:
                self.ready.set()
                self.go.wait()
                self.t_open = time.perf_counter()
            return out

        core.apply_round = watched_apply
        core.evaluate = watched_eval
        for obj, name, label in ((core.sampler, "sample", "sample_cohort"),
                                 (core, "compute_cohort", None),
                                 (core.uplink, "transmit", None),
                                 (core, "offer_uploads", None),
                                 (core.agg, "close_round", None),
                                 (core, "close_digest", None)):
            _spanned(obj, name, span, label)


def setup(config, traffic, seed, ctx):
    from repro.fed.costmodel import ChannelConfig
    from repro.fed.runtime import RuntimeConfig, SchedulerConfig
    from repro.fed.runtime.engine import EngineCore
    from repro.fed.runtime.scheduler import run_scheduled
    from repro.models.mlp_classifier import mlp_accuracy, mlp_grad, mlp_loss

    from datagen import digits_task

    words = np.random.SeedSequence(seed).generate_state(3)
    data = traffic["data"]
    clients, xte, yte, sx, sy = digits_task(
        data["samples"], data["test_frac"], data["shards"], int(words[0]))
    sizes = config["layer_sizes"]
    x0 = _weights(sizes, words[1])
    run_seed = int(words[2]) & 0x7FFFFFFF
    cfg = RuntimeConfig(
        rounds=TIMING_ROUNDS, population=traffic["population"],
        participation=traffic["participation"],
        local_steps=traffic["local_steps"], batch_size=traffic["batch_size"],
        local_lr=traffic["local_lr"], server_lr=traffic["server_lr"],
        family=traffic["family"], num_projections=traffic["num_projections"],
        downlink_mode=traffic["downlink_mode"],
        eval_every=traffic["eval_every"], seed=run_seed,
        channel=ChannelConfig(drop_prob=traffic["drop_prob"]),
        scheduler=SchedulerConfig(mode="sync",
                                  quorum_frac=traffic["quorum_frac"]))
    cfg.scheduler.validate(cfg)
    d = sum(int(np.prod(x.shape)) for x in x0.values())
    if d != config["parameters"]:
        raise ValueError(f"MLP has {d} parameters, configuration states "
                         f"{config['parameters']}")

    def core(rounds):
        c = dataclasses.replace(cfg, rounds=rounds)
        return EngineCore(c, x0, clients, xte, yte, mlp_grad,
                          (mlp_loss, mlp_accuracy), None,
                          c.build_protocol(x0), d)

    timing = core(TIMING_ROUNDS)
    watch = _Watch(timing, ctx.span)
    run_scheduled(timing, x0)
    round_s = float(np.median(np.diff(watch.ends[1:])))
    check_rounds = cfg.eval_every + 1
    rounds = 1 + max(check_rounds, math.ceil(ctx.seconds / round_s))

    job = core(rounds)
    watch = _Watch(job, ctx.span, keep=KEPT_ROUNDS, hold=True)
    box: dict = {}

    def run():
        try:
            box["hist"] = run_scheduled(job, x0)
        except BaseException as e:  # handed to the main thread
            box["error"] = e
        finally:
            box["t_end"] = time.perf_counter()
            watch.ready.set()

    thread = threading.Thread(target=run, name="run_scheduled", daemon=True)
    thread.start()
    watch.ready.wait()
    if "error" in box:
        raise box["error"]
    return {"thread": thread, "box": box, "watch": watch, "cfg": cfg,
            "traffic": traffic, "config": config, "run_seed": run_seed,
            "data": (sx, sy, xte, yte), "x0": x0, "rounds": rounds,
            "check_rounds": check_rounds, "timing_round_s": round_s}


def window(st, seconds, span):
    watch, box, thread = st.pop("watch"), st.pop("box"), st.pop("thread")
    watch.go.set()
    thread.join()
    if "error" in box:
        raise box["error"]
    h = box["hist"]
    st["params"] = h["final_params"]
    st["losses"] = [float(v) for v in h["loss"]]
    st["first_params"] = watch.kept
    rounds = st["rounds"] - 1
    st["attempted"] = rounds
    return {"elapsed_s": box["t_end"] - watch.t_open, "rounds": rounds,
            "uploads": int(h["applied"][1:].sum()), "attempted": rounds,
            "lost_in_channel": int(h["lost_channel"][1:].sum()),
            "left_out_by_quorum": int(h["dropped_deadline"][1:].sum()),
            "apply_s": [float(a) for a in h["apply_s"][1:] if a > 0],
            "notes": {
                "catch_up_dense_resyncs": int(h["dense_resyncs"][1:].sum()),
                "catch_up_bits": int(h["catchup_bits"][1:].sum()),
                "after_last_apply_s": box["t_end"] - watch.ends[-1],
                "set_up_round_s": st["timing_round_s"]}}


def failed_rounds(st) -> int:
    finite = all(np.all(np.isfinite(np.asarray(x)))
                 for x in st["params"].values())
    return 0 if finite else st["attempted"]


def _reference(st, dtype=None, fault=None):
    """The job's first rounds by the reference → (x after round 1, x
    after round 3, [test loss after round 1, after round eval_every+1])."""
    import jax.numpy as jnp

    from refs.paper_mlp_fl import ReferenceFL, test_loss

    tr = st["traffic"]
    sx, sy, xte, yte = st["data"]
    dt = jnp.float32 if dtype is None else dtype
    ref = ReferenceFL(
        sx, sy, run_seed=st["run_seed"], population=tr["population"],
        cohort_size=st["cfg"].cohort_size(), local_steps=tr["local_steps"],
        batch_size=tr["batch_size"], local_lr=tr["local_lr"],
        server_lr=tr["server_lr"], quorum_frac=tr["quorum_frac"],
        lognormal_sigma=st["cfg"].channel.lognormal_sigma,
        drop_prob=tr["drop_prob"], dtype=dt)
    x = st["x0"]
    xs, losses = [], []
    xte_d, yte_d = jnp.asarray(xte, dt), jnp.asarray(yte)
    last = st["check_rounds"] - 1
    for k in range(st["check_rounds"]):
        x, _ = ref.round(x, st["run_seed"], k, fault=fault)
        xs.append(x)
        if k in (0, last):
            losses.append(test_loss(x, xte_d, yte_d))
    return xs[0], xs[KEPT_ROUNDS - 1], losses


def _norms(tree, base):
    return {k: float(np.linalg.norm(np.asarray(tree[k], np.float64)
                                    - np.asarray(base[k], np.float64)))
            for k in sorted(tree)}


def _leaf_gap(a: dict, b: dict) -> float:
    """Worst leaf's |a - b| over b's norm of that leaf or the median
    leaf's, whichever is larger (``a``, ``b``: leaf → norm)."""
    med = float(np.median(list(b.values())))
    return float(np.max([abs(a[k] - b[k]) / max(b[k], med) for k in b]))


def _numbers(x0, got1, got3, got_losses, want1, want3, want_losses):
    n1_got, n1_want = _norms(got1, x0), _norms(want1, x0)
    diff = {k: float(np.linalg.norm(np.asarray(got1[k], np.float64)
                                    - np.asarray(want1[k], np.float64)))
            for k in sorted(want1)}
    med = float(np.median(list(n1_want.values())))
    first_update = float(np.max([diff[k] / max(n1_want[k], med)
                                 for k in n1_want]))
    loss = float(np.max([abs(g - w) / abs(w)
                         for g, w in zip(got_losses, want_losses)]))
    out = {"loss_gap": loss, "first_update_gap": first_update,
           "first_update_norm_gap": _leaf_gap(n1_got, n1_want),
           "change_norm_gap": _leaf_gap(_norms(got3, x0), _norms(want3, x0))}
    return {k: (v if np.isfinite(v) else float("inf")) for k, v in out.items()}


def check(st) -> dict:
    x1, x3 = st["first_params"][0], st["first_params"][KEPT_ROUNDS - 1]
    losses = [st["losses"][0], st["losses"][st["check_rounds"] - 1]]
    w1, w3, wl = _reference(st)
    nums = _numbers(st["x0"], x1, x3, losses, w1, w3, wl)
    return {k: (v, LIMITS[k]) for k, v in nums.items()}


def control(st) -> dict:
    """The reference in bfloat16 put in the program's place."""
    import jax.numpy as jnp

    c1, c3, cl = _reference(st, dtype=jnp.bfloat16)
    w1, w3, wl = _reference(st)
    nums = _numbers(st["x0"], c1, c3, cl, w1, w3, wl)
    return {k: (v, LIMITS[k]) for k, v in nums.items()}


def faults(st) -> dict:
    """Each fault planted in the reference put in the program's place
    → {fault: {name: (value, limit)}}."""
    w1, w3, wl = _reference(st)
    out = {}
    for fault in ("half_batch", "altered_answer", "unchanged"):
        f1, f3, fl = _reference(st, fault=fault)
        nums = _numbers(st["x0"], f1, f3, fl, w1, w3, wl)
        out[fault] = {k: (v, LIMITS[k]) for k, v in nums.items()}
    return out
