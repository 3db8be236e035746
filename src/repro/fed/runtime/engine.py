"""Round driver: K server rounds over populations up to ~10⁵ clients.

The engine is **protocol-pluggable** (DESIGN §8): every registered
:class:`repro.fed.protocols.UplinkProtocol` — ``fedscalar`` (the
paper's (r, ξ) two-scalar wire), ``fedavg`` (dense frames) and
``qsgd`` (level-code + norm frames) — runs through the same cohort
sampler, channel, streaming server and cost model, so the paper's
system-level comparison (Table I, eqs. 12–13) is a configuration
sweep, not three codebases.

Per round the engine

  1. samples a cohort from the population registry (``sampling``),
  2. serves the downlink (``transport.DownlinkChannel``, DESIGN §9):
     under ``dense`` the d·32-bit model broadcast; under ``digest``
     (fedscalar only) each sampled client first catches up from its
     last synced round via the bounded round log (dense fallback past
     the window) — both honestly priced into bits/wall/energy,
  3. runs every cohort member's S local-SGD steps **in fixed-size
     vmapped chunks** through the same ``make_local_sgd`` building
     block all protocols share (fixed chunk shape → one XLA
     compilation for any cohort size), then lets the protocol encode
     each member's update into its wire payload,
  4. pushes each frame through the protocol's byte-level wire codec
     and the lossy/laggy channel (``transport``),
  5. lets the streaming aggregator close the round at the deadline
     (``server``) and hands the surviving frames to the protocol's
     ``server_apply`` — for ``fedscalar`` that is
     x ← x + lr·Σᵢⱼ coeffᵢ·rᵢⱼ·vⱼ(ξᵢ) via the fori-loop path, the
     fused Pallas reconstruction kernel with its client-chunk **and
     block** grid dimensions (DESIGN §2/§6), or — with ``mesh_shape``
     set — the mesh-sharded apply where every device of a
     (data, model) mesh rebuilds its own slice of the direction chain
     with zero collectives (DESIGN §7); for the dense protocols it is
     the IPW-weighted frame mean (uniform full-arrival rounds use the
     exact cohort mean, bit-identical to the ``core`` round functions
     — ``tests/test_protocol_parity.py``),
  6. in digest mode, closes the round by broadcasting its
     :class:`RoundDigest` — the O(C·k)-scalar summary a
     :class:`StatefulClient` replays into the **bit-identical**
     parameter update (the DESIGN §9 invariant; ``verify_replay``
     asserts it live with a shadow client),
  7. charges the round to the two-sided bandwidth/energy cost model
     (eqs. 12′/13′) with the protocol codec's ``bits_per_upload``
     (8 bytes for the paper's protocol, Θ(d) for the baselines — the
     whole point of Table I) plus the downlink's broadcast + catch-up
     traffic.

The projection is pluggable (DESIGN §6): ``family`` selects any
registered :class:`repro.core.directions.DirectionFamily` and
``num_projections``/``projection_mode`` set the k-block-scalar upload;
uploads are float32 ``(C, payload_dim)`` with uint32 ``(C,)`` seeds
throughout.

Fast path: a fully-participating, synchronous, lossless, fp32
configuration is *exactly* the paper's §III experiment, so the engine
delegates it to ``run_simulation``'s single fused ``lax.scan`` — for
``fedscalar`` the trajectory is bit-for-bit the small-scale path, and
for ``fedavg``/``qsgd`` it is bit-for-bit the corresponding ``core``
round functions — while the runtime keeps its own cost accounting.

The dense protocols refuse ``mesh_shape``: serving a dense frame from
a sharded model would need a d-sized gather of every frame to every
model shard — exactly the communication the seed-regenerated
direction chain avoids (DESIGN §8).
"""
from __future__ import annotations

import dataclasses
import math
import time
from typing import TYPE_CHECKING, Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core import fedscalar as fs
from repro.core.prng import Distribution
from repro.core.projection import tree_size
from repro.fed.costmodel import ChannelConfig, CostModel
from repro.fed.runtime.sampling import ClientPopulation, CohortSampler
from repro.fed.runtime.server import ServerConfig, StreamingAggregator, Upload
from repro.fed.runtime.transport import (
    DownlinkChannel,
    RoundDigest,
    RoundLog,
    UplinkChannel,
    WireFormat,
)

if TYPE_CHECKING:
    from repro.fed.runtime.scheduler import SchedulerConfig

__all__ = ["RuntimeConfig", "EngineCore", "run_federation",
           "draw_cohort_batches", "StatefulClient", "cohort_sampler"]


@dataclasses.dataclass(frozen=True)
class RuntimeConfig:
    """Everything the federation runtime needs for one K-round run."""

    rounds: int = 50                    # K
    population: int = 1000              # registered clients
    participation: float = 0.01         # expected sampled fraction per round
    sampler: str = "uniform"            # uniform | weighted | poisson
    protocol_name: str = "fedscalar"    # registered uplink protocol
                                        # (fedscalar | fedavg | qsgd, DESIGN §8)
    local_steps: int = 5                # S
    batch_size: int = 32
    local_lr: float = 3e-3              # α
    server_lr: float = 1.0
    distribution: Distribution = Distribution.RADEMACHER
    family: str | None = None           # direction family name (DESIGN §6);
                                        # overrides `distribution` when set
    num_projections: int = 1            # k scalars per upload
    projection_mode: str = "full"       # "full" (m full-d projections),
                                        # "block" (k block scalars), or
                                        # "fused_kernel": block semantics
                                        # (full at k=1) served by the fused
                                        # reconstruct+apply megakernel
                                        # (DESIGN §11; fedscalar only)
    qsgd_bits: int = 8                  # level-code width of the qsgd protocol
    seed: int = 0
    scalar_format: str = "fp32"         # wire width of r (fp32 | fp16 | bf16)
    eval_every: int = 1
    client_chunk: int = 256             # cohort members per vmapped compute chunk
    kernel_cohort_threshold: int | None = None  # cohorts ≥ this → Pallas path
                                                # (None: TPU only, CPU never;
                                                # fedscalar only)
    mesh_shape: tuple | None = None     # (data, model) device mesh for the
                                        # sharded server apply (DESIGN §7);
                                        # None = single-device apply;
                                        # fedscalar only (DESIGN §8)
    downlink_mode: str = "dense"        # downlink wire discipline (DESIGN §9):
                                        # "dense" (d·32-bit model broadcast) or
                                        # "digest" (O(C·k) round digest +
                                        # stateful client replay; fedscalar only)
    downlink_log_window: int = 64       # digest mode: rounds of catch-up log
                                        # kept before a dense fallback resync
    verify_replay: bool = False         # digest mode: a shadow StatefulClient
                                        # replays every digest and the run
                                        # asserts bit-identity with the server
    server: ServerConfig = dataclasses.field(default_factory=ServerConfig)
    channel: ChannelConfig = dataclasses.field(default_factory=ChannelConfig)
    scheduler: "SchedulerConfig | None" = None
                                        # continuous-round driver (DESIGN §10):
                                        # sync (bit-identical to the legacy
                                        # loop) or async pipelined serving;
                                        # None = the legacy one-cohort loop

    def resolved_distribution(self) -> Distribution:
        if self.family is not None:
            from repro.core.directions import get_family
            return get_family(self.family).distribution
        return self.distribution

    def resolved_projection_mode(self):
        """→ the :class:`ProjectionMode` behind the config string.

        ``"fused_kernel"`` is a *routing* choice, not a new projection
        semantics: uploads are the k block scalars (plain FULL at k=1);
        only the server's decode runs the fused megakernel.
        """
        from repro.core.projection import ProjectionMode
        if self.projection_mode == "fused_kernel":
            return (ProjectionMode.BLOCK if self.num_projections > 1
                    else ProjectionMode.FULL)
        return ProjectionMode(self.projection_mode)

    def protocol(self) -> fs.FedScalarConfig:
        return fs.FedScalarConfig(
            local_steps=self.local_steps, local_lr=self.local_lr,
            server_lr=self.server_lr,
            distribution=self.resolved_distribution(),
            num_projections=self.num_projections,
            mode=self.resolved_projection_mode())

    def wire(self) -> WireFormat:
        return WireFormat(scalar=self.scalar_format,
                          num_projections=self.num_projections)

    def build_protocol(self, params_like):
        """→ the configured :class:`repro.fed.protocols.UplinkProtocol`."""
        from repro.core import fedavg as fa
        from repro.core import qsgd as q
        from repro.fed.protocols import make_protocol

        base = dict(local_steps=self.local_steps, local_lr=self.local_lr,
                    server_lr=self.server_lr)
        return make_protocol(
            self.protocol_name, params_like,
            fedscalar_config=self.protocol(), wire_format=self.wire(),
            fedavg_config=fa.FedAvgConfig(**base),
            scalar_format=self.scalar_format,
            qsgd_config=q.QSGDConfig(bits=self.qsgd_bits, **base))

    def cohort_size(self) -> int:
        return max(1, int(round(self.participation * self.population)))


def draw_cohort_batches(cx, cy, num_shards: int, seed: int, round_idx,
                        client_ids, local_steps: int, batch_size: int):
    """Deterministic per-(round, client) minibatch streams for a cohort.

    ``cx``/``cy`` are the stacked client shards (#shards, n_per, ...);
    client n reads shard n mod #shards.  The stream is a pure function
    of (run seed, round, client id) — independent of cohort makeup —
    and this function is the **single source** of the engine's batch
    draw: the parity tests replay it so the reference ``core`` round
    functions consume the exact batches the engine computed
    (``tests/test_protocol_parity.py``).

    → ``(bx, by)`` with shapes ``(C, S, B, feat...)`` / ``(C, S, B)``.
    """
    n_per = cx.shape[1]
    S, B = local_steps, batch_size
    shard = (client_ids % num_shards).astype(jnp.int32)
    sx = cx[shard]                            # (C, n_per, feat)
    sy = cy[shard]

    def draw(cid):
        key = jax.random.fold_in(
            jax.random.fold_in(jax.random.PRNGKey(seed), round_idx), cid)  # fedlint: allow[FS001] batch-draw single source: data sampling, not directions; shared with the scan path bit-for-bit
        return jax.random.randint(key, (S, B), 0, n_per)

    idx = jax.vmap(draw)(client_ids)          # (C, S, B)
    chunk = client_ids.shape[0]
    bx = jnp.take_along_axis(
        sx[:, :, None, :], idx.reshape(chunk, S * B, 1, 1), axis=1
    ).reshape((chunk, S, B) + sx.shape[2:])
    by = jnp.take_along_axis(
        sy, idx.reshape(chunk, S * B), axis=1).reshape(chunk, S, B)
    return bx, by


def cohort_sampler(cfg: RuntimeConfig, client_sets,
                   client_weights: np.ndarray | None = None) -> CohortSampler:
    """The cohort sampler a run of ``cfg`` over ``client_sets`` draws
    from; the ``weighted`` sampler defaults to the shard size behind
    each virtual client."""
    if client_weights is None and cfg.sampler == "weighted":
        shard_sizes = np.asarray([len(y) for _, y in client_sets],
                                 np.float64)  # fedlint: allow[FS002] host-side PPS sampling weights, never enters a device computation
        client_weights = shard_sizes[np.arange(cfg.population)
                                     % len(client_sets)]
    population = ClientPopulation(cfg.population, weights=client_weights)
    return CohortSampler(population, cfg.participation, cfg.sampler,
                         seed=cfg.seed)


def _fused_method(cfg: RuntimeConfig, num_shards: int) -> str | None:
    """→ the ``run_simulation`` method iff the config degenerates to it."""
    from repro.fed.simulation import METHOD_FOR_DISTRIBUTION

    base = (
        cfg.participation == 1.0
        and cfg.sampler in ("uniform", "weighted")
        and cfg.mesh_shape is None     # sharded apply never takes the shortcut
        and cfg.population == num_shards
        and not math.isfinite(cfg.server.deadline_s)   # deadline = ∞
        and cfg.server.max_staleness == 0
        and cfg.channel.drop_prob == 0.0
        and cfg.channel.base_latency_s == 0.0
        and cfg.scalar_format == "fp32"
        and cfg.server_lr == 1.0
        and cfg.projection_mode != "fused_kernel"   # explicit kernel routing
    )
    if not base:
        return None
    if cfg.protocol_name == "fedavg":
        return "fedavg"
    if cfg.protocol_name == "qsgd":
        # run_simulation's QSGDConfig carries the paper's 8-bit point.
        return "qsgd" if cfg.qsgd_bits == 8 else None
    if (cfg.num_projections == 1
            and cfg.resolved_distribution() in METHOD_FOR_DISTRIBUTION):
        return METHOD_FOR_DISTRIBUTION[cfg.resolved_distribution()]
    return None


def _pad_pow2(n: int, lo: int = 16) -> int:
    """Bucket size for round-close buffers: bounded recompilation."""
    b = lo
    while b < n:
        b *= 2
    return b


def _pad_bucket(ars: np.ndarray, acoeffs: np.ndarray,
                aseeds: np.ndarray | None = None):
    """Zero-pad the round-close buffers to a power-of-two bucket.

    Shared by the fedscalar and dense weighted applies so the padding
    convention (bucket sizing, dtypes, zero weights → zero
    contribution) cannot diverge between the two paths.
    → ``(rs_b, w_b)`` or ``(rs_b, w_b, seeds_b)`` when seeds are given.
    """
    a = len(acoeffs)
    bucket = _pad_pow2(a)
    rs_b = np.zeros((bucket, ars.shape[1]), np.float32)
    rs_b[:a] = ars
    w_b = np.zeros(bucket, np.float32)
    w_b[:a] = acoeffs.astype(np.float32)
    if aseeds is None:
        return rs_b, w_b
    seeds_b = np.zeros(bucket, np.uint32)
    seeds_b[:a] = aseeds
    return rs_b, w_b, seeds_b


class StatefulClient:
    """Client-side downlink state: holds x_j, advances by digest replay.

    The digest discipline (DESIGN §9) makes clients stateful: instead
    of receiving the d·32-bit model every round, a client keeps its
    last synced parameters and replays each :class:`RoundDigest`
    through **the same aggregation path the server ran** — the
    bucket-padded weighted ``server_apply`` for event-driven rounds,
    the exact uniform mean for full-arrival (fused) rounds — via the
    existing seeded-reconstruct machinery.  Because the digest carries
    exactly the server's ``(seeds, coefficients, scalars)`` and the
    padding/apply code is shared, the replayed x_{k+1} is
    **bit-identical** to the server's (``tests/test_downlink.py``).

    The replay is exact when client and server run the same reconstruct
    path: fori-loop and mesh-sharded applies are bitwise
    interchangeable (DESIGN §7), and the fused reconstruct+apply
    megakernel is bit-identical across its own lowerings (its chunked
    spec, DESIGN §11) but differs by ulps from fori — so a deployment
    pins the apply *method* consistently on both sides (the engine's
    ``verify_replay`` shadow mirrors the server's per-round choice).
    """

    def __init__(self, params: Any, protocol, start_round: int = 0):
        if "digest" not in protocol.downlink_modes:
            raise ValueError(f"protocol {protocol.name!r} has no digest "
                             "downlink to replay (DESIGN §9)")
        self.params = params
        self.next_round = start_round
        self.protocol = protocol
        self._weighted = jax.jit(
            lambda p, r, s, w: protocol.server_apply(p, r, s, w))
        self._weighted_kernel = jax.jit(
            lambda p, r, s, w: protocol.server_apply(p, r, s, w,
                                                     use_kernel=True))
        self._weighted_fused = jax.jit(
            lambda p, r, s, w: protocol.server_apply(p, r, s, w,
                                                     use_fused=True))
        self._mean = jax.jit(
            lambda p, r, s: protocol.server_apply(p, r, s, None))

    def apply_digest(self, dg: RoundDigest,
                     use_kernel: bool | str = False) -> Any:
        """Replay one round's digest → the post-round parameters.

        ``use_kernel`` mirrors the server's per-round apply method:
        False/"fori", True/"kernel", or "fused" (the reconstruct+apply
        megakernel) — the replay must run the identical numeric path.
        """
        if dg.round_idx != self.next_round:
            raise ValueError(f"client holds x_{self.next_round}, cannot "
                             f"apply digest of round {dg.round_idx}")
        self.next_round += 1
        if dg.num_uploads == 0:        # skipped / empty round: no-op
            return self.params
        if dg.uniform_mean:
            self.params = self._mean(self.params, jnp.asarray(dg.rs),
                                     jnp.asarray(dg.seeds))
        else:
            rs_b, w_b, seeds_b = _pad_bucket(dg.rs, dg.coeffs, dg.seeds)
            fn = {"fused": self._weighted_fused,
                  "kernel": self._weighted_kernel,
                  True: self._weighted_kernel}.get(use_kernel, self._weighted)
            self.params = fn(self.params, jnp.asarray(rs_b),
                             jnp.asarray(seeds_b), jnp.asarray(w_b))
        return self.params

    def catch_up(self, log: RoundLog, server_params: Any = None,
                 use_kernel: bool | str = False) -> dict:
        """Sync to the log head: replay the suffix, or dense-resync.

        A gap beyond the log window means the suffix was evicted — the
        client takes one dense model sync (``server_params`` required)
        exactly as the engine prices it.  ``use_kernel`` names the
        server's apply method for the replayed rounds (see
        :meth:`apply_digest`) — a client syncing to a
        ``projection_mode="fused_kernel"`` server passes ``"fused"``.
        → ``dict(mode, rounds_replayed, suffix_bits)``.
        """
        bits = log.suffix_bits(self.next_round)
        if bits is None:
            if server_params is None:
                raise ValueError(
                    f"gap {log.next_round - self.next_round} exceeds the "
                    f"{log.window}-round log window: dense resync needs "
                    "server_params")
            self.params = server_params
            self.next_round = log.next_round
            return dict(mode="dense", rounds_replayed=0, suffix_bits=0)
        frames = log.replay(self.next_round)
        for dg in frames:
            self.apply_digest(dg, use_kernel=use_kernel)
        return dict(mode="digest" if frames else "current",
                    rounds_replayed=len(frames), suffix_bits=bits)


class EngineCore:
    """One run's compiled stages + channel state, shared by both drivers.

    Everything the legacy synchronous loop (:func:`_run_legacy`) and
    the continuous-round scheduler (:mod:`repro.fed.runtime.scheduler`,
    DESIGN §10) have in common lives here: the stacked client shards,
    cohort sampler, cost model, uplink/downlink channels, streaming
    aggregator, the jitted compute/apply/eval stages and the
    per-client downlink state.  The drivers decide *when* rounds open,
    close and overlap; the core owns *how* a cohort's payloads are
    computed, how frames hit the wire, and how a closed round folds
    into the model — so the two drivers cannot drift in arithmetic.
    Construction draws nothing from the cost model's RNG (the first
    draw still happens at the first ``transmit``), which keeps the
    legacy loop's draw sequence bit-for-bit what it was before this
    class existed.

    Per-client server state is O(1) by construction: ``client_last``
    is one int32 round index per registered client (4 MB at 10⁶
    clients) and the channel/aggregator counters are scalars — the
    server never holds a per-client model copy
    (``tests/test_scheduler.py`` audits the bound).
    """

    def __init__(self, cfg: RuntimeConfig, init_params: Any, client_sets,
                 x_test, y_test, grad_fn: Callable, eval_fns, client_weights,
                 proto, d: int):
        from repro.fed.simulation import _stack_clients

        loss_fn, acc_fn = eval_fns
        self.cfg = cfg
        self.proto = proto
        self.codec = proto.wire_codec
        self.d = d
        num_shards = len(client_sets)
        self.num_shards = num_shards
        cx, cy = _stack_clients(client_sets)      # (#shards, n_per, feat...)
        xt, yt = jnp.asarray(x_test), jnp.asarray(y_test)

        self.sampler = cohort_sampler(cfg, client_sets, client_weights)
        self.cm = CostModel(
            cfg.channel, fedavg_bits_per_client=d * cfg.channel.float_bits,
            rng_seed=cfg.seed)
        self.uplink = UplinkChannel(self.cm, self.codec)
        self.digest_mode = cfg.downlink_mode == "digest"
        self.downlink = DownlinkChannel(
            self.cm, d, cfg.channel.float_bits, mode=cfg.downlink_mode,
            digest_codec=proto.digest_codec() if self.digest_mode else None,
            log_window=cfg.downlink_log_window)
        # Digest downlink makes clients stateful: each holds the round it
        # last synced to (everyone registers holding x₀), and a sampled
        # client first replays the log suffix — or takes a dense fallback
        # resync past the window — before computing on x_k (DESIGN §9).
        # One int32 round index is the *whole* per-client server state.
        self.client_last = (np.zeros(cfg.population, np.int32)
                            if self.digest_mode else None)
        self.shadow = (StatefulClient(init_params, proto)
                       if cfg.verify_replay else None)
        self.agg = StreamingAggregator(cfg.server)

        local = fs.make_local_sgd(grad_fn, cfg.local_lr, cfg.local_steps)

        # ---- jitted fixed-shape chunk: C_chunk clients' local rounds → frames ----
        @jax.jit
        def chunk_payloads(params, round_idx, client_ids):
            bx, by = draw_cohort_batches(cx, cy, num_shards, cfg.seed,
                                         round_idx, client_ids,
                                         cfg.local_steps, cfg.batch_size)
            seeds = fs.round_seeds_for(round_idx, client_ids)
            deltas = jax.vmap(local, in_axes=(None, 0))(params, (bx, by))
            payloads = proto.encode_cohort(deltas, seeds, round_idx,
                                           client_ids)
            return payloads, seeds

        self.chunk_payloads = chunk_payloads

        # ---- jitted server applies (bucketed shapes) ----
        self.apply_stats: dict = {}     # stats of the fed.apply_round span
        if proto.name == "fedscalar":
            @jax.jit
            def apply_fori(params, rs, seeds, weights):
                return proto.server_apply(params, rs, seeds, weights)

            @jax.jit
            def apply_kernel(params, rs, seeds, weights):
                return proto.server_apply(params, rs, seeds, weights,
                                          use_kernel=True)

            self.apply_fori, self.apply_kernel = apply_fori, apply_kernel

            # Fused megakernel apply (projection_mode="fused_kernel"):
            # the autotuner cache is consulted read-only for the
            # dominant leaf's tuned tile/slab — a cache miss just means
            # defaults (both knobs are bits-invariant, so tuned and
            # untuned applies agree to the bit; DESIGN §11).
            fused_params = None
            if cfg.projection_mode == "fused_kernel":
                from repro.kernels.ops import fused_tiling, shape_2d
                from repro.kernels.tune import cached_fused_params
                lead = max(jax.tree_util.tree_leaves(init_params),
                           key=lambda x: x.size, default=None)
                if lead is not None and lead.ndim:
                    fused_params = cached_fused_params(
                        *shape_2d(lead.shape), cfg.cohort_size(),
                        cfg.num_projections,
                        cfg.resolved_distribution().value)
                if cfg.mesh_shape is None:
                    # The fused close's tiling of this tree, for the
                    # apply span: worked out once here, not per round.
                    block = (fused_params or {}).get("block")
                    self.apply_stats = fused_tiling(
                        init_params, tuple(block) if block else None)

            @jax.jit
            def apply_fused(params, rs, seeds, weights):
                return proto.server_apply(params, rs, seeds, weights,
                                          use_fused=True,
                                          fused_params=fused_params)

            self.apply_fused = apply_fused
        else:
            # Dense protocols: the uniform-mean path is the exact paper
            # aggregation (→ bit-identity with the core round functions on
            # full-arrival uniform cohorts); the weighted path carries the
            # runtime's IPW×staleness coefficients over a padded bucket
            # (zero-weight rows decode to zero contribution).
            @jax.jit
            def apply_mean(params, frames):
                return proto.server_apply(params, frames, None, None)

            @jax.jit
            def apply_weighted(params, frames, weights):
                return proto.server_apply(params, frames, None, weights)

            self.apply_mean, self.apply_weighted = apply_mean, apply_weighted

        kern_thresh = cfg.kernel_cohort_threshold
        if kern_thresh is None:
            kern_thresh = 512 if jax.default_backend() == "tpu" else None
        self.kern_thresh = kern_thresh

        # --- mesh-sharded apply (DESIGN §7): each device rebuilds its d-shard ---
        self.mesh = None
        self.shard_info = None
        if cfg.mesh_shape is not None:
            from repro.launch.mesh import make_fed_mesh
            from repro.sharding.fed_rules import num_mesh_shards, plan_tree

            mesh = make_fed_mesh(tuple(cfg.mesh_shape))
            plan = plan_tree(init_params, num_mesh_shards(mesh))
            self.mesh = mesh
            self.shard_info = dict(
                mesh_shape=tuple(cfg.mesh_shape),
                devices=num_mesh_shards(mesh),
                per_device_elements=plan.per_shard_elements(),
                balance=plan.balance(),
            )

            # Params stay replicated here (the client chunks and eval read the
            # full model every round), so each apply shards/unshards the views;
            # a decode-only server holding x resident uses
            # fed_rules.sharded_apply_blocks and skips that round-trip.
            @jax.jit
            def apply_mesh(params, rs, seeds, weights):
                return proto.server_apply(params, rs, seeds, weights,
                                          mesh=mesh)

            self.apply_mesh = apply_mesh

        @jax.jit
        def evaluate(params):
            return loss_fn(params, (xt, yt)), acc_fn(params, xt, yt)

        self.evaluate = evaluate

    # ---- driver stages ----

    def compute_cohort(self, params, k: int, ids: np.ndarray):
        """Cohort local rounds in fixed-shape chunks (pad by repeating id 0)
        → (float32 (C, payload_dim) payloads, uint32 (C,) seeds)."""
        with TraceAnnotation("fed.compute_cohort"):
            c = len(ids)
            rs_np = np.zeros((max(c, 1), self.proto.payload_dim), np.float32)
            seeds_np = np.zeros(max(c, 1), np.uint32)
            chunk = self.cfg.client_chunk
            for lo in range(0, c, chunk):
                part = ids[lo:lo + chunk]
                padded = (np.zeros(chunk, np.int64) if len(part) < chunk
                          else part)
                if len(part) < chunk:
                    padded[:len(part)] = part
                rs_c, seeds_c = self.chunk_payloads(
                    params, jnp.uint32(k), jnp.asarray(padded, jnp.uint32))
                with TraceAnnotation("fed.device_wait"):
                    rs_np[lo:lo + len(part)] = np.asarray(rs_c)[:len(part)]
                    seeds_np[lo:lo + len(part)] = \
                        np.asarray(seeds_c)[:len(part)]
            return rs_np, seeds_np

    def offer_uploads(self, ids, weights, k: int, tx,
                      deadline_s: float | None = None) -> None:
        """Offer one round's transmitted cohort to the aggregator, in
        client-id order (the deterministic aggregation order).
        ``deadline_s=None`` keeps the config deadline (legacy loop);
        the scheduler passes its per-round effective close instead."""
        with TraceAnnotation("fed.offer_uploads"):
            for i in range(len(ids)):
                self.agg.offer(Upload(
                    client_id=int(ids[i]), encoded_round=k,
                    seed=int(tx.seeds[i]), r=tx.r_hat[i],
                    agg_weight=float(weights[i]),
                    latency_s=float(tx.latency_s[i]), lost=bool(tx.lost[i])),
                    deadline_s=deadline_s)

    def apply_round(self, params, aseeds, acoeffs, ars, cohort_size: int, st):
        """Fold a closed round's buffers into the model.

        → ``(params, method, apply_s)``; the apply choice — "fused" /
        "kernel" / fori (False) / mesh / exact-mean — is made here once
        for both drivers, and ``method`` is what the digest replay must
        pin (it threads opaquely to :meth:`close_digest`).
        """
        with TraceAnnotation("fed.apply_round", rows=len(aseeds),
                             **self.apply_stats):
            a = len(aseeds)
            use_kernel: bool | str = False
            apply_s = 0.0
            if a and not st.skipped:
                t_apply = time.perf_counter()
                if self.proto.name == "fedscalar":
                    rs_b, w_b, seeds_b = _pad_bucket(ars, acoeffs, aseeds)
                    # mesh apply ≡ fori bitwise (DESIGN §7), so the shadow
                    # replay must NOT take the kernel path on mesh rounds —
                    # the kernel differs by ulps (DESIGN §9).
                    if (self.mesh is None
                            and self.cfg.projection_mode == "fused_kernel"):
                        use_kernel = "fused"
                    elif (self.mesh is None
                            and self.kern_thresh is not None
                            and a >= self.kern_thresh
                            and (self.cfg.num_projections == 1
                                 or self.cfg.projection_mode == "block")):
                        use_kernel = True
                    if self.mesh is not None:
                        applier = self.apply_mesh
                    elif use_kernel == "fused":
                        applier = self.apply_fused
                    else:
                        applier = (self.apply_kernel if use_kernel
                                   else self.apply_fori)
                    params = applier(params, jnp.asarray(rs_b),
                                     jnp.asarray(seeds_b), jnp.asarray(w_b))
                else:
                    uniform_exact = (self.cfg.sampler == "uniform"
                                     and a == cohort_size
                                     and st.applied_stale == 0
                                     and bool(np.all(acoeffs == acoeffs[0])))
                    if uniform_exact:
                        params = self.apply_mean(params, jnp.asarray(ars))
                    else:
                        rs_b, w_b = _pad_bucket(ars, acoeffs)
                        params = self.apply_weighted(
                            params, jnp.asarray(rs_b), jnp.asarray(w_b))
                with TraceAnnotation("fed.device_wait"):
                    jax.block_until_ready(jax.tree_util.tree_leaves(params))
                apply_s = time.perf_counter() - t_apply
            return params, use_kernel, apply_s

    def close_digest(self, k: int, aseeds, acoeffs, ars, st, ids, params,
                     use_kernel: bool | str) -> int:
        """Digest-mode round close: broadcast the round's digest, mark
        the cohort synced, shadow-verify the replay → broadcast bits."""
        with TraceAnnotation("fed.close_digest"):
            applied_round = bool(len(aseeds)) and not st.skipped
            dg = RoundDigest(
                round_idx=k,
                seeds=aseeds if applied_round else np.zeros(0, np.uint32),
                rs=(ars if applied_round
                    else np.zeros((0, self.proto.payload_dim), np.float32)),
                coeffs=(acoeffs.astype(np.float32) if applied_round
                        else np.zeros(0, np.float32)))
            bits = self.downlink.broadcast(dg)
            self.client_last[ids] = k + 1   # the cohort heard the broadcast
            if self.shadow is not None:
                self.shadow.apply_digest(dg, use_kernel=use_kernel)
                for x, y in zip(
                        jax.tree_util.tree_leaves(params),
                        jax.tree_util.tree_leaves(self.shadow.params)):
                    if not np.array_equal(np.asarray(x), np.asarray(y)):
                        raise AssertionError(
                            f"digest replay diverged from the server at "
                            f"round {k} (DESIGN §9 invariant)")
            return bits

    @staticmethod
    def new_history(K: int) -> dict:
        hist = {k: np.zeros(K) for k in (
            "loss", "accuracy", "cum_bits", "cum_downlink_bits", "cum_wall_s",
            "cum_energy_j", "cum_downlink_wall_s", "cum_downlink_energy_j",
            "catchup_bits", "dense_resyncs", "cohort_size", "applied",
            "applied_stale", "lost_channel", "dropped_deadline",
            "dropped_stale", "weight_sum", "apply_s")}
        hist["loss"][:] = np.nan
        hist["accuracy"][:] = np.nan
        return hist

    def finalize(self, params, hist: dict, t0: float,
                 extra: dict | None = None) -> dict:
        """Cumsum the history, reconcile the downlink ledger, and
        assemble the result dict both drivers return."""
        cfg = self.cfg
        K = cfg.rounds
        for key in ("cum_bits", "cum_downlink_bits", "cum_wall_s",
                    "cum_energy_j", "cum_downlink_wall_s",
                    "cum_downlink_energy_j"):
            hist[key] = np.cumsum(hist[key])

        # Reconcile the channel's own counter against the per-round
        # history: every downlink bit (broadcasts + catch-up) must be
        # accounted — the old DownlinkBroadcast stub accumulated a
        # counter nothing ever read, so bits could silently vanish.
        if int(hist["cum_downlink_bits"][-1]) != self.downlink.total_bits:
            raise AssertionError(
                f"downlink accounting leak: channel counted "
                f"{self.downlink.total_bits} bits, history recorded "
                f"{int(hist['cum_downlink_bits'][-1])}")

        applied_rounds = hist["apply_s"] > 0
        recon_clients_per_s = (
            float(np.sum(hist["applied"][applied_rounds])
                  / np.sum(hist["apply_s"][applied_rounds]))
            if applied_rounds.any() else 0.0)

        out = dict(
            method=f"runtime_{cfg.sampler}",
            protocol=self.proto.name,
            round=np.arange(1, K + 1),
            final_params=params,
            bits_per_client_per_round=self.codec.bits_per_upload,
            sim_compute_seconds=time.perf_counter() - t0,
            fused_path=False,
            pending_rounds=self.agg.pending_rounds(),
            sharding=self.shard_info,
            recon_clients_per_s=recon_clients_per_s,
            downlink_mode=cfg.downlink_mode,
            total_downlink_bits=self.downlink.total_bits,
            downlink_stats=dict(
                broadcast_bits=self.downlink.broadcast_bits,
                catchup_bits=self.downlink.catchup_bits,
                dense_resyncs=self.downlink.dense_resyncs),
            round_log=self.downlink.log,
            **hist,
        )
        if extra:
            out.update(extra)
        return out


def run_federation(
    cfg: RuntimeConfig,
    init_params: Any,
    client_sets,
    x_test: np.ndarray,
    y_test: np.ndarray,
    grad_fn: Callable | None = None,
    eval_fns: tuple[Callable, Callable] | None = None,
    client_weights: np.ndarray | None = None,
) -> dict:
    """Run K federation rounds → history dict of numpy arrays.

    ``client_sets`` are the data shards; a population larger than the
    shard list maps client n onto shard n mod #shards (virtual
    clients).  ``grad_fn``/``eval_fns`` default to the paper's digits
    MLP and exist so tests can drive tiny custom models.
    ``client_weights`` (N,) are the ``weighted`` sampler's relative
    sampling weights; default: each virtual client's shard size.

    With ``cfg.scheduler`` set, the run is driven by the
    continuous-round scheduler (:mod:`repro.fed.runtime.scheduler`,
    DESIGN §10) — sync mode is bit-identical to the legacy loop,
    async mode pipelines rounds — instead of the one-cohort-at-a-time
    legacy driver (and never takes the fused shortcut).
    """
    if grad_fn is None:
        from repro.models.mlp_classifier import mlp_grad
        grad_fn = mlp_grad
    if eval_fns is None:
        from repro.models.mlp_classifier import mlp_accuracy, mlp_loss
        eval_fns = (mlp_loss, mlp_accuracy)

    num_shards = len(client_sets)
    proto = cfg.build_protocol(init_params)
    d = tree_size(init_params)
    if proto.name != "fedscalar" and cfg.mesh_shape is not None:
        raise ValueError(
            f"protocol {proto.name!r} cannot use mesh_shape: dense frames "
            "need a d-sized gather per upload on a sharded server "
            "(DESIGN §8); only fedscalar decodes shard-locally")
    if cfg.downlink_mode not in ("dense", "digest"):
        raise ValueError(f"unknown downlink_mode {cfg.downlink_mode!r}; "
                         "want 'dense' or 'digest'")
    if cfg.downlink_mode == "digest" and "digest" not in proto.downlink_modes:
        raise ValueError(
            f"protocol {proto.name!r} cannot use the digest downlink: its "
            "frames carry the d values themselves, so the server must ship "
            "the dense model every round (DESIGN §9)")
    if cfg.verify_replay and cfg.downlink_mode != "digest":
        raise ValueError("verify_replay checks the digest-replay invariant; "
                         "set downlink_mode='digest'")
    if cfg.scheduler is not None:
        cfg.scheduler.validate(cfg)

    method = None if cfg.scheduler is not None else _fused_method(cfg, num_shards)
    if method is not None:
        return _run_fused(cfg, init_params, client_sets, x_test, y_test,
                          method, proto, d)

    core = EngineCore(cfg, init_params, client_sets, x_test, y_test,
                      grad_fn, eval_fns, client_weights, proto, d)
    if cfg.scheduler is not None:
        from repro.fed.runtime.scheduler import run_scheduled
        return run_scheduled(core, init_params)
    return _run_legacy(core, init_params)


def _run_legacy(core: EngineCore, init_params) -> dict:
    """The pre-scheduler driver: one synchronous cohort per round.

    Statement-for-statement the historical loop, now phrased over
    :class:`EngineCore` stages — same RNG consumption order, same
    apply choices — so its trajectories and cost figures are
    bit-identical to every release before the scheduler existed (and
    the scheduler's sync mode is in turn asserted bit-identical to
    *this* loop: ``tests/test_scheduler.py``).
    """
    cfg = core.cfg
    agg, cm = core.agg, core.cm
    uplink, downlink = core.uplink, core.downlink
    params = init_params
    K = cfg.rounds
    hist = EngineCore.new_history(K)
    deadline = cfg.server.deadline_s
    t0 = time.perf_counter()

    for k in range(K):
        cohort = core.sampler.sample(k)
        ids = cohort.client_ids
        if core.digest_mode:
            # Catch-up before compute: each sampled client syncs from
            # its last round to x_k (log-suffix replay, unicast; dense
            # fallback past the window), priced in one vectorized batch
            # (counter-identical to the per-client loop).  The round's
            # closing digest broadcast is added at round close.
            catchup_bits, _, resyncs = downlink.catch_up_batch(
                core.client_last[ids], k)
            downlink_bits = catchup_bits
            hist["catchup_bits"][k] = catchup_bits
            hist["dense_resyncs"][k] = resyncs
        else:
            downlink_bits = downlink.broadcast()

        # --- client compute, fixed-shape chunks (pad by repeating id 0) ---
        c = len(ids)
        rs_np, seeds_np = core.compute_cohort(params, k, ids)

        # --- uplink: bytes on the (lossy, laggy) air ---
        tx = uplink.transmit(rs_np[:c], seeds_np[:c]) if c else None
        core.offer_uploads(ids, cohort.agg_weights, k, tx)

        # --- round close + model update ---
        aseeds, acoeffs, ars, st = agg.close_round(k)
        params, use_kernel, apply_s = core.apply_round(
            params, aseeds, acoeffs, ars, c, st)
        hist["apply_s"][k] = apply_s

        # --- digest downlink: close broadcast + stateful client sync ---
        if core.digest_mode:
            downlink_bits += core.close_digest(k, aseeds, acoeffs, ars, st,
                                               ids, params, use_kernel)

        # --- cost accounting ---
        # Sync mode: the round lasts until the deadline cuts the slowest
        # upload.  Async mode: rounds tick on the fixed cadence the
        # staleness model is defined over (stragglers' air time is still
        # billed as energy, their lateness as τ — not as this round's wall).
        async_mode = (cfg.server.max_staleness > 0
                      and math.isfinite(cfg.server.round_period_s))
        if c:
            bits, wall, energy = cm.cohort_round_cost(
                tx.latency_s, core.codec.bits_per_upload, deadline_s=deadline)
        else:
            bits, energy, wall = 0.0, 0.0, cm.t_other
        if async_mode:
            wall = cfg.server.round_period_s

        hist["cohort_size"][k] = c
        hist["applied"][k] = st.applied
        hist["applied_stale"][k] = st.applied_stale
        hist["lost_channel"][k] = st.lost_channel
        hist["dropped_deadline"][k] = st.dropped_deadline
        hist["dropped_stale"][k] = st.dropped_stale
        hist["weight_sum"][k] = st.weight_sum
        hist["cum_bits"][k] = bits
        hist["cum_downlink_bits"][k] = downlink_bits
        hist["cum_wall_s"][k] = wall
        hist["cum_energy_j"][k] = energy
        # two-sided pricing (12′)/(13′): the round's downlink traffic
        # (broadcast + catch-up) at the deterministic nominal R_down
        _, dl_wall, dl_energy = downlink.round_cost(downlink_bits)
        hist["cum_downlink_wall_s"][k] = dl_wall
        hist["cum_downlink_energy_j"][k] = dl_energy
        if k % cfg.eval_every == 0 or k == K - 1:
            loss, acc = core.evaluate(params)
            hist["loss"][k] = float(loss)
            hist["accuracy"][k] = float(acc)

    return core.finalize(params, hist, t0)


def _run_fused(cfg: RuntimeConfig, init_params, client_sets, x_test, y_test,
               method: str, proto, d: int) -> dict:
    """Full-participation sync path → one fused ``lax.scan``.

    Delegates to :func:`repro.fed.simulation.run_simulation`, so the
    trajectory is bit-for-bit the paper-scale experiment — for
    ``fedavg``/``qsgd`` that means bit-for-bit the ``core`` round
    functions; only the cost accounting is redone with the runtime's
    per-upload channel draws.

    Digest downlink (fedscalar only): the scan captures each round's
    uploaded ``(r, ξ)`` (``capture_uploads`` — extra scan outputs, no
    arithmetic change), the rounds become **uniform-mean digests**
    (full arrival: the coefficient column is implied 1/N and never
    rides the wire) appended to the round log, and the per-round
    downlink is the digest's O(N·k) bits instead of d·32.  Catch-up
    traffic is zero by construction: full participation means every
    client hears every close broadcast.
    """
    from repro.fed.costmodel import dense_downlink_bits, replay_round_costs
    from repro.fed.simulation import SimulationConfig, run_simulation

    bits_per_upload = proto.wire_codec.bits_per_upload
    digest_mode = cfg.downlink_mode == "digest"
    sim = SimulationConfig(
        method=method, rounds=cfg.rounds, num_clients=cfg.population,
        local_steps=cfg.local_steps, batch_size=cfg.batch_size,
        local_lr=cfg.local_lr, seed=cfg.seed, channel=cfg.channel,
        capture_uploads=digest_mode)
    h = run_simulation(sim, init_params, client_sets, x_test, y_test)

    K, n = cfg.rounds, cfg.population
    bits, wall, energy = replay_round_costs(
        cfg.channel, bits_per_upload, K, n,
        fedavg_bits_per_client=d * cfg.channel.float_bits, rng_seed=cfg.seed)

    cm = CostModel(cfg.channel, fedavg_bits_per_client=d * cfg.channel.float_bits,
                   rng_seed=cfg.seed)   # downlink_cost draws no RNG
    round_log = None
    if digest_mode:
        round_log = RoundLog(proto.digest_codec(),
                             window=max(cfg.downlink_log_window, K))
        dl_bits = np.zeros(K)
        for k in range(K):
            dg = RoundDigest(round_idx=k, seeds=h["seed_history"][k],
                             rs=h["r_history"][k], coeffs=None)
            dl_bits[k] = round_log.append(dg)
        if cfg.verify_replay:
            client = StatefulClient(init_params, proto)
            client.catch_up(round_log)
            for x, y in zip(jax.tree_util.tree_leaves(h["final_params"]),
                            jax.tree_util.tree_leaves(client.params)):
                if not np.array_equal(np.asarray(x), np.asarray(y)):
                    raise AssertionError("fused-path digest replay diverged "
                                         "from run_simulation (DESIGN §9)")
    else:
        dl_bits = np.full(K, float(dense_downlink_bits(d, cfg.channel.float_bits)))
    dl_costs = np.asarray([cm.downlink_cost(b) for b in dl_bits])
    total_dl = int(dl_bits.sum())

    h.update(
        method=f"runtime_{cfg.sampler}_fused",
        protocol=cfg.protocol_name,
        cum_bits=np.cumsum(bits),
        cum_downlink_bits=np.cumsum(dl_bits),
        cum_wall_s=np.cumsum(wall),
        cum_energy_j=np.cumsum(energy),
        cum_downlink_wall_s=np.cumsum(dl_costs[:, 1]),
        cum_downlink_energy_j=np.cumsum(dl_costs[:, 2]),
        catchup_bits=np.zeros(K),
        dense_resyncs=np.zeros(K),
        cohort_size=np.full(K, float(n)),
        applied=np.full(K, float(n)),
        applied_stale=np.zeros(K),
        lost_channel=np.zeros(K),
        dropped_deadline=np.zeros(K),
        dropped_stale=np.zeros(K),
        weight_sum=np.ones(K),
        apply_s=np.zeros(K),
        bits_per_client_per_round=bits_per_upload,
        fused_path=True,
        pending_rounds=[],
        sharding=None,
        recon_clients_per_s=0.0,
        downlink_mode=cfg.downlink_mode,
        total_downlink_bits=total_dl,
        downlink_stats=dict(broadcast_bits=total_dl, catchup_bits=0,
                            dense_resyncs=0),
        round_log=round_log,
    )
    return h
