"""What the two elastic workers share, written once.

`ElasticWorker` (`runtime/elastic.py`: independent leases, rebuild the mesh
in process) and `MultiHostWorker` (`runtime/multihost.py`: lockstep rounds
published by rank 0, exit ``RESCALE_EXIT_CODE`` on a membership change) are
two protocols and stay two. Everything that is not protocol lives here: the
config, the constructor's control-plane wiring (outbox facade, fault-
tolerance policy, checkpointer and checkpoint plane, compile cache, epoch
watch, instruments), the seeded jitter stream, draining the watch and the
preempt-notice decision, mesh and trainer construction, restore-or-init,
the body of one train step with its spans, and the result summary. A
feature that both workers need is wired here and nowhere else.
"""

from __future__ import annotations

import dataclasses
import logging
import math
import random
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
from jax.sharding import Mesh

from edl_tpu.coordinator.outbox import OutboxClient
from edl_tpu.coordinator.watch import make_epoch_watch
from edl_tpu.models.base import Model
from edl_tpu.obs.instruments import PreemptInstruments, WorkerInstruments
from edl_tpu.obs.tracing import Tracer, get_tracer
from edl_tpu.parallel.mesh import MeshSpec, build_hierarchical_mesh, build_mesh
from edl_tpu.parallel.planner import Plan
from edl_tpu.runtime.checkpoint import Checkpointer, abstract_like, live_state_specs
from edl_tpu.runtime.ft_policy import FTPolicy, FTPolicyConfig
from edl_tpu.runtime.train_loop import (
    Trainer, TrainerConfig, TrainState, loss_value,
)

log = logging.getLogger("edl_tpu.runtime.worker_base")


@dataclass
class ElasticConfig:
    checkpoint_dir: str = ""
    checkpoint_interval: int = 100  # steps between periodic async saves
    heartbeat_interval: float = 1.0  # seconds between coordinator heartbeats
    #: fractional jitter (±) applied per beat to the heartbeat interval,
    #: seeded by worker name: 10k workers launched from one template would
    #: otherwise phase-lock into synchronized heartbeat storms that turn
    #: the coordinator's load spiky (see doc/performance.md, control plane).
    heartbeat_jitter: float = 0.2
    #: how epoch changes reach this worker: ``"watch"`` subscribes to the
    #: coordinator's push stream (a rescale arrives in one RTT instead of a
    #: heartbeat period) and treats a dead subscription as an error to
    #: surface; ``"pull"`` keeps the pre-watch heartbeat-only discovery;
    #: ``"auto"`` (default) subscribes when the transport supports it and
    #: degrades silently to pull when it doesn't. Pull stays on as the
    #: liveness fallback in every mode — the watch only *adds* latency
    #: headroom and suppresses redundant dedicated pulls while healthy.
    epoch_discovery: str = "auto"
    #: max wait for survivors at the rescale barrier; on timeout we proceed
    #: (the checkpoint is already durable, latecomers restore from it).
    rescale_barrier_timeout: float = 60.0
    batch_axis: str = "data"
    #: optional per-step hook (step, state) -> None — e.g. a
    #: `runtime.export.PeriodicExporter` writing the serving artifact the
    #: way the reference's trainer 0 does (`ctr/train.py:169-180`).
    step_callback: Optional[Callable[[int, TrainState], None]] = None
    #: pipeline the data path: the next shard loads on a background thread
    #: while the current shard's batches feed training (costs one extra held
    #: lease + up to two shards of host RAM). See LeaseReader.
    prefetch: bool = False
    #: device-side input pipelining: > 0 runs wire encode + H2D batch
    #: placement on a pump thread (`runtime.pipeline.DevicePrefetcher`),
    #: up to this many placed batches ahead of step dispatch. 0 places
    #: synchronously. The lease RPCs move to the pump thread with the
    #: reader; CoordinatorClient serializes per-request, so heartbeats and
    #: checkpoint commits on the main thread interleave safely.
    pipeline_depth: int = 2
    #: AOT-compile the step for the new mesh on a background thread during
    #: the rescale restore window, so the first post-rescale step dispatches
    #: a ready executable instead of paying XLA inside the recovery budget.
    warm_compile: bool = True
    #: coordinator-outage budget, seconds: while the coordinator is
    #: unreachable the worker keeps stepping batches already leased (the
    #: compute never depended on the control plane) and buffers
    #: completions in an outbox; past this budget it checkpoints durably
    #: and parks, polling for the coordinator's return. See
    #: doc/robustness.md for the full failure model.
    outage_budget: float = 60.0
    #: fault-tolerance policy mode: ``adaptive`` sizes the park decision
    #: per incident from live outage statistics and measured recovery
    #: costs (`runtime.ft_policy`); ``static`` pins it to the fixed
    #: ``outage_budget`` threshold above — the pre-policy semantics.
    policy: str = "adaptive"
    #: full policy knobs; None derives FTPolicyConfig(policy=policy,
    #: outage_budget=outage_budget) with the documented defaults.
    ft_policy: Optional[FTPolicyConfig] = None
    #: serve ``/metrics`` + ``/healthz`` + ``/spans`` from this worker
    #: process on the given port (0 = ephemeral); None disables. The
    #: endpoint also bridges the coordinator's status counters, so one
    #: scrape of any worker sees control plane and data plane together.
    metrics_port: Optional[int] = None
    #: memory-resident checkpoint plane (``edl_tpu.ckpt_plane``): > 0
    #: replicates each worker's ZeRO-1 state shard to this many ring peers
    #: through the coordinator at every checkpoint, and restores assemble
    #: from peers in memory (zero blob reads) with the blob store as the
    #: group-death fallback. 0 (the default) disables the plane entirely —
    #: restores read the blob store exactly as before.
    peer_replicas: int = 0
    #: persistent AOT compile cache directory (``runtime.compile_cache``):
    #: non-empty stores every warm-compiled step executable on disk keyed by
    #: (topology, program, avals, code fingerprint), so revisiting a layout
    #: — including after a RESCALE_EXIT_CODE restart — costs zero compiles.
    #: "" (the default) disables persistence; warm-compile behaves as before.
    compile_cache_dir: str = ""
    trainer: TrainerConfig = field(default_factory=TrainerConfig)

    def __post_init__(self) -> None:
        # Fail at construction, not an hour into the job: a negative
        # outage_budget silently turned every blip into a park, a negative
        # heartbeat interval spins the beat loop hot — both were accepted
        # without complaint before this check.
        if self.heartbeat_interval < 0:
            raise ValueError(
                f"ElasticConfig.heartbeat_interval must be >= 0 seconds "
                f"(0 beats every loop iteration), got {self.heartbeat_interval!r}")
        if not 0.0 <= self.heartbeat_jitter <= 1.0:
            raise ValueError(
                f"ElasticConfig.heartbeat_jitter is a ± fraction of the "
                f"interval and must be in [0, 1], got {self.heartbeat_jitter!r}")
        if self.outage_budget <= 0:
            raise ValueError(
                f"ElasticConfig.outage_budget must be > 0 seconds (it is "
                f"the park threshold ceiling), got {self.outage_budget!r}")
        if self.rescale_barrier_timeout <= 0:
            raise ValueError(
                f"ElasticConfig.rescale_barrier_timeout must be > 0 "
                f"seconds, got {self.rescale_barrier_timeout!r}")
        if self.checkpoint_interval < 1:
            raise ValueError(
                f"ElasticConfig.checkpoint_interval must be >= 1 step, "
                f"got {self.checkpoint_interval!r}")
        if self.pipeline_depth < 0:
            raise ValueError(
                f"ElasticConfig.pipeline_depth must be >= 0 "
                f"(0 places synchronously), got {self.pipeline_depth!r}")
        if self.policy not in ("adaptive", "static"):
            raise ValueError(
                f"ElasticConfig.policy must be 'adaptive' or 'static', "
                f"got {self.policy!r}")
        if self.epoch_discovery not in ("watch", "pull", "auto"):
            raise ValueError(
                f"ElasticConfig.epoch_discovery must be 'watch', 'pull' or "
                f"'auto', got {self.epoch_discovery!r}")
        if self.peer_replicas < 0:
            raise ValueError(
                f"ElasticConfig.peer_replicas must be >= 0 "
                f"(0 disables the checkpoint plane), got "
                f"{self.peer_replicas!r}")


def _jitter_rng(worker: str) -> random.Random:
    """The per-worker jitter stream, seeded by the worker's name (str seeds
    hash stably in ``random.Random``): deterministic per name, different
    across names, so a fleet de-correlates without coordination."""
    return random.Random(f"edl-hb:{worker}")  # edl: noqa[EDL008] control-plane timing jitter, never touches model/optimizer state — per-worker decorrelation is the point


def _jitter(rng: random.Random, base: float, jitter: float) -> float:
    """``base`` ± ``jitter`` fraction, one draw from ``rng``."""
    return max(0.0, base * (1.0 + jitter * (2.0 * rng.random() - 1.0)))


def heartbeat_schedule(worker: str, base: float, jitter: float,
                       n: int) -> List[float]:
    """First ``n`` heartbeat intervals for ``worker``: ``base`` ± ``jitter``
    fraction, drawn from the stream a worker of that name draws its own
    intervals and pauses from (`WorkerBase._jittered`), so this IS the
    sequence it sleeps between beats. Exposed for tests and capacity
    planning."""
    rng = _jitter_rng(worker)
    return [_jitter(rng, base, jitter) for _ in range(n)]


class WorkerBase:
    """One trainer process's share of an elastic job, less its protocol."""

    #: coalesce-window stretch while the watch is healthy: dedicated pulls
    #: drop to 1/stretch cadence because discovery rides the push stream.
    _WATCH_PULL_STRETCH = 3.0

    def __init__(
        self,
        model: Model,
        client,  # coordinator client bound to this worker's name
        source,  # shard source with .read(shard)
        config: ElasticConfig,
        mesh_axes: Optional[Dict[str, int]] = None,
        profiler=None,  # optional edl_tpu.tools.profiler.StepProfiler
        tracer: Optional[Tracer] = None,
        layout_planner: Optional[
            Callable[[int, Sequence[jax.Device]], Optional[Plan]]] = None,
    ):
        if not config.checkpoint_dir:
            raise ValueError("ElasticConfig.checkpoint_dir is required")
        self.model = model
        #: degraded-mode facade: mutations buffer during a coordinator
        #: outage and replay idempotently on reconnect; reads fail soft.
        self.client = client if isinstance(client, OutboxClient) \
            else OutboxClient(client)
        self.source = source
        self.config = config
        self.mesh_axes = mesh_axes  # extra non-data axes, sized per full mesh
        #: hybrid-parallel replanner: ``(n_chips, devices) -> Plan | None``
        #: (typically ``parallel.planner.plan_layout`` closed over a Topology
        #: + ModelProfile). Called at every mesh build; a returned Plan's
        #: mesh axes and batch axis replace the static data-only resize, a
        #: None falls back to it. ``plan_layout`` is deterministic, so the
        #: processes of a gang converge on one layout from the same inputs.
        #: Mutually exclusive with ``mesh_axes`` — the plan owns the whole
        #: layout.
        self.layout_planner = layout_planner
        if layout_planner is not None and mesh_axes:
            raise ValueError(
                "pass either mesh_axes (static layout) or layout_planner "
                "(searched layout), not both")
        #: the Plan adopted at the last mesh build (None on the data-only
        #: path) — replan-span attribution and `edl-tpu status` style debugging.
        self.last_plan: Optional[Plan] = None
        #: persistent AOT executable store shared by every Trainer this
        #: worker builds (None when disabled): a rescale or a warm restart
        #: that revisits a layout lands on the executable compiled before.
        if config.compile_cache_dir:
            from edl_tpu.runtime.compile_cache import CompileCache

            self.compile_cache: Optional[CompileCache] = CompileCache(
                config.compile_cache_dir)
        else:
            self.compile_cache = None
        self.profiler = profiler
        #: the step's and the rescale lifecycle's spans land here (shared
        #: process tracer unless a test/bench passes its own); correlated
        #: cross-process via the membership epoch
        #: (obs.tracing.rescale_trace_id).
        self.tracer = tracer if tracer is not None else get_tracer()
        #: one set of metric families for both workers — dashboards don't
        #: care which flavor a pod runs.
        self.obs = WorkerInstruments()
        #: per-incident recovery-mode selector (doc/robustness.md, policy
        #: layer): replaces the fixed outage_budget comparison with a
        #: threshold computed from the live outage distribution and
        #: measured checkpoint/restore/re-step costs. ``policy="static"``
        #: pins it back to the old semantics. What escalation MEANS (park,
        #: or a gang's warm restart) is the subclass's.
        self.policy = FTPolicy(
            config.ft_policy if config.ft_policy is not None
            else FTPolicyConfig(policy=config.policy,
                                outage_budget=config.outage_budget),
            worker=self.client.worker,
            tracer=self.tracer,
        )
        self.client.on_outage_close = self._on_outage_close
        self.ckpt = Checkpointer(config.checkpoint_dir)
        #: memory-resident checkpoint plane (None when disabled): peer-
        #: replicated ZeRO shards pushed at every checkpoint, assembled in
        #: memory on restore, blob store as the group-death fallback.
        if config.peer_replicas > 0:
            from edl_tpu.ckpt_plane import CkptPlane

            self.ckpt_plane: Optional[CkptPlane] = CkptPlane(
                self.client, replicas=config.peer_replicas,
                tracer=self.tracer)
        else:
            self.ckpt_plane = None
        #: what the last _restore_or_init was served from — the restore
        #: span's source/bytes attribution (peer | blob | init).
        self._last_restore: Dict = {"source": "init", "bytes": 0}
        self.steps_done = 0
        self.losses: List[float] = []
        self._epoch = -1
        #: per-worker seeded jitter stream: every heartbeat interval and
        #: every poll or backoff pause draws from it, so a fleet launched
        #: from one config template de-correlates instead of arriving at
        #: the coordinator in phase-locked waves.
        self._hb_rng = _jitter_rng(self.client.worker)
        #: heartbeats satisfied from a piggybacked membership observation
        #: (no dedicated RPC issued).
        self.hb_coalesced = 0
        # Piggyback heartbeats onto in-flight calls when the transport
        # supports it: lease/kv traffic then refreshes our TTL for free and
        # most dedicated beats coalesce away entirely.
        raw = getattr(self.client, "client", self.client)
        if getattr(raw, "piggyback_heartbeat", None) == 0.0:
            raw.piggyback_heartbeat = config.heartbeat_interval
        #: push-based epoch discovery: a watch subscription on the raw
        #: transport (None when epoch_discovery='pull' or the transport
        #: supports neither flavor). Pull stays the liveness fallback.
        self._watch = make_epoch_watch(self.client, config.epoch_discovery)
        if config.epoch_discovery == "watch" and self._watch is None:
            raise ValueError(
                "epoch_discovery='watch' but the transport exposes neither "
                "a wire endpoint nor a call surface to subscribe on")
        #: dedicated pull rounds skipped because a healthy watch already
        #: covered epoch discovery (mirrors the metric family).
        self.pulls_suppressed = 0
        #: preemption sensor suite (notices, notice-to-drained, evictions).
        self.preempt_obs = PreemptInstruments()
        #: host-batch avals (shape/dtype) observed at first placement —
        #: what rescale warm-compile specializes the new mesh's step
        #: against. Written once from whichever thread places first.
        self._batch_avals: Optional[Dict[str, jax.ShapeDtypeStruct]] = None

    # -- control plane ---------------------------------------------------------

    def _on_outage_close(self, duration: float) -> None:
        """OutboxClient callback: one outage incident ended. Feeds the
        per-incident duration (the histogram the running-total gauge loses)
        and the policy's history. Runs on whichever thread's guarded call
        observed recovery — everything here is thread-safe and cheap."""
        self.obs.outage_duration.observe(duration)
        self.policy.note_outage_closed(duration)

    def _adopt_epoch(self, epoch: int, world: int, rank: int) -> None:
        """Take ``epoch`` as the one this worker trains under."""
        self._epoch = epoch
        if self._watch is not None and int(epoch) > self._watch.last_epoch:
            # Prime the resume cursor: epochs adopted via register/pull must
            # not replay as notifications on the next (re)subscribe.
            self._watch.last_epoch = int(epoch)
        self.obs.note_epoch(epoch)
        if self.ckpt_plane is not None:
            # New epoch = new rank numbering: publish the epoch's replica-
            # placement map (every rank the identical one, an idempotent
            # kv_put) and invalidate the previous epoch's key.
            self.ckpt_plane.on_epoch(epoch, world, rank)

    def _jittered(self, base: float) -> float:
        """``base`` ± ``config.heartbeat_jitter`` fraction, the next draw of
        the seeded per-worker stream (`heartbeat_schedule`)."""
        return _jitter(self._hb_rng, base, self.config.heartbeat_jitter)

    def _pause(self, base: float = 0.2) -> None:
        """Idle-poll sleep from the jitter stream: a fleet draining the same
        queue (or the same outage) would otherwise re-poll the coordinator
        in phase-locked waves — the identical hazard the heartbeat jitter
        exists for."""
        time.sleep(max(0.05, self._jittered(base)))

    def _outage_pause(self) -> None:
        """Retry pause at heartbeat cadence while the coordinator is away,
        jittered so a whole fleet's re-registrations spread out."""
        self._pause(min(1.0, max(0.1, self.config.heartbeat_interval)))

    def _coalesced_beat(self, now: float) -> Optional[Dict]:
        """The membership observation that answers the beat due at ``now``
        (monotonic) without a dedicated RPC, or None. Every coordinator
        reply carries the current epoch, and membership-shaped replies
        (piggybacked heartbeats among them) are recorded by the transport.
        A fresh one — made within the nominal interval, so the server-side
        TTL was refreshed then too — is the beat."""
        lm = getattr(self.client, "last_membership", None)
        lm_at = getattr(self.client, "last_membership_at", 0.0)
        fresh_window = self.config.heartbeat_interval
        if self._watch is not None and self._watch.connected:
            # Watch healthy: epoch discovery rides the push stream, so the
            # dedicated pull only backstops TTL refresh and liveness.
            # Stretch the coalesce window (bounded — a fully idle transport
            # still pulls at stretch x cadence, well inside the default TTL
            # of ~10 intervals).
            fresh_window *= self._WATCH_PULL_STRETCH
        if lm is None or now - lm_at >= fresh_window:
            return None
        self.hb_coalesced += 1
        self.obs.note_coalesced_heartbeat()
        if now - lm_at >= self.config.heartbeat_interval:
            # Only the stretched window made this round coalesce: a pull
            # the watch genuinely suppressed.
            self.pulls_suppressed += 1
            self.obs.note_pull_suppressed()
        return dict(lm)

    def _drain_watch(self) -> Tuple[bool, List[Dict]]:
        """Drain pushed notifications (non-blocking): whether one names an
        epoch beyond ours, and the preempt notices addressed to this worker.
        Arrival -> consumption delay feeds
        `edl_worker_epoch_notify_latency_seconds`. A dead subscription is
        not an error here: poll() re-subscribes with bounded backoff and
        the pull cadence stays the liveness fallback."""
        if self._watch is None:
            return False, []
        now = time.monotonic()
        moved = False
        for epoch, arrived in self._watch.poll():
            self.obs.note_epoch_notify(now - arrived)
            if epoch > self._epoch:
                moved = True
        take = getattr(self._watch, "take_preempts", None)
        return moved, take() if callable(take) else []

    def _decide_preempt(self, notice: Dict) -> str:
        """One revocation notice addressed to this worker: count it and run
        the policy's notice-budget decision. ``ride_out`` means keep
        stepping — the notice was too short for even a checkpoint to pay
        off; what any other mode makes the worker DO is its protocol's."""
        remaining = notice["deadline"] - time.monotonic()
        self.preempt_obs.notices.inc(reason=notice.get("reason", "preempt"))
        self.preempt_obs.notice_remaining.set(remaining)
        mode = self.policy.on_preempt_notice(remaining)
        log.warning(
            "preempt notice: %.1fs remaining (reason=%s seq=%s) -> %s",
            remaining, notice.get("reason"), notice.get("seq"), mode)
        return mode

    # -- mesh / trainer / state ------------------------------------------------

    def _build_mesh(self, devices: Sequence[jax.Device]) -> Mesh:
        devices = list(devices)
        self.last_plan = None
        if self.layout_planner is not None:
            plan = self.layout_planner(len(devices), devices)
            if plan is not None:
                self.last_plan = plan
                spec = MeshSpec(dict(plan.mesh_axes))
                if plan.hierarchical:
                    # dcn outermost: the planner only emits a dcn axis when
                    # the chips span slices, and the gradient psum over
                    # ("dcn", "data") must lower to the hierarchical reduce.
                    return build_hierarchical_mesh(spec, devices)
                return build_mesh(spec, devices)
        axes = dict(self.mesh_axes or {})
        n = len(devices)
        fixed = math.prod(axes.values())
        if n % fixed != 0:
            raise ValueError(f"{n} devices not divisible by fixed axes {axes}")
        axes["data"] = n // fixed
        return build_mesh(MeshSpec(axes), devices)

    def _trainer_config(self) -> TrainerConfig:
        """The trainer config for the CURRENT layout: a planned layout
        re-points the batch axis (a hierarchical plan shards the batch over
        ("dcn", "data")); the data-only path uses the static config as-is."""
        if (self.last_plan is None
                or self.config.trainer.batch_axis == self.last_plan.batch_axis):
            return self.config.trainer
        return dataclasses.replace(
            self.config.trainer, batch_axis=self.last_plan.batch_axis)

    def _make_trainer(self, mesh: Mesh, epoch: int) -> Trainer:
        codec_channel = None
        if self.config.trainer.wire_transport:
            from edl_tpu.runtime.wire import KVCodecChannel

            # Epoch-scoped: a new incarnation renegotiates the codec from
            # scratch (possibly under a new rank 0), while the widen floor
            # persists through the coordinator, so a restart never
            # re-learns an old overflow.
            codec_channel = KVCodecChannel(self.client, epoch)
        return Trainer(self.model, mesh, self._trainer_config(),
                       codec_channel=codec_channel,
                       compile_cache=self.compile_cache)

    def _restore_or_init(
        self, trainer: Trainer, fresh: Optional[TrainState] = None
    ) -> TrainState:
        if fresh is None:
            fresh = trainer.init_state()
        self._last_restore = {"source": "init", "bytes": 0}
        blob_step = self.ckpt.latest_step()
        if (self.ckpt_plane is not None
                and self.policy.restore_source() == "peer"):
            # Peer-first (the break-even above may demote to blob-first):
            # assemble the state from the coordinator's memory-resident
            # shards, re-sharded onto THIS mesh through the same spec
            # machinery orbax uses. min_step pins the plane to at least the
            # blob store's best — recovery never moves training backwards.
            t0 = time.time()
            got = self.ckpt_plane.restore(
                fresh, trainer.mesh, live_state_specs(fresh),
                min_step=blob_step,
            )
            if got is not None:
                state, info = got
                self.policy.note_peer_restore(time.time() - t0)
                self._last_restore = {"source": "peer",
                                      "bytes": int(info["bytes"])}
                if "reshard_start" in info:
                    # the device_put window peer_restore timed — the rescale
                    # loop records it as the `reshard` phase.
                    self._last_restore["reshard_start"] = info["reshard_start"]
                    self._last_restore["reshard_end"] = info["reshard_end"]
                log.info(
                    "restored step=%s from %d peer shard(s) onto %d-device "
                    "mesh (%d bytes in memory, zero blob reads)",
                    info["step"], info["world_at_save"], trainer.mesh.size,
                    info["bytes"])
                return state
        if blob_step is None:
            return fresh
        state = self.ckpt.restore(
            abstract_like(fresh), trainer.mesh, live_state_specs(fresh)
        )
        self._last_restore = {"source": "blob", "bytes": 0}
        if self.ckpt_plane is not None:
            # The fallback rung actually taken — the restores-by-source
            # audit is what proves a group death demoted cleanly.
            self.ckpt_plane.obs.restores.inc(source="blob")
        log.info("restored checkpoint step=%s onto %d-device mesh",
                 self.ckpt.latest_step(), trainer.mesh.size)
        return state

    # -- one step --------------------------------------------------------------

    def _dispatched(self, batches, trainer: Trainer,
                    current_task: Callable[[], Optional[str]],
                    thread_name: str):
        """Yield ``(placed, step_fn, task, samples, place_seconds)`` per
        host batch of ``batches``, placement pipelined per
        ``config.pipeline_depth`` (> 0: wire encode + H2D placement of batch
        N+1 on a pump thread overlap step N; the pump pulls from ``batches``
        itself, and what that raises, a SystemExit included, is relayed to
        the consuming thread).

        The place closure snapshots ``current_task()`` at placement time so
        per-pass step attribution follows the batch, not whatever shard the
        reader has moved on to by step time; ``place_bound`` snapshots the
        step callable for the same reason (codec widening in flight).
        ``place_seconds`` is the length of the batch's ``place`` span.
        """
        depth = self.config.pipeline_depth

        def place(batch):
            if self._batch_avals is None:
                self._batch_avals = {
                    k: jax.ShapeDtypeStruct(v.shape, v.dtype)
                    for k, v in batch.items()
                }
            task = current_task()
            with self.tracer.span("place", task=task) as span:
                placed, step_fn = trainer.place_bound(batch)
            return placed, step_fn, task, span

        if depth <= 0:
            for batch in batches:
                samples = len(next(iter(batch.values())))
                *payload, span = place(batch)
                yield (*payload, samples, span.seconds)
            return
        from edl_tpu.runtime.pipeline import DevicePrefetcher

        with DevicePrefetcher(
            batches, place, depth=depth, thread_name=thread_name
        ) as pf:
            for item in pf:
                *payload, span = item.payload
                yield (*payload, item.samples, span.seconds)

    def _step_once(self, state: TrainState, placed, step_fn, step: int,
                   samples: int, place_seconds: float
                   ) -> Tuple[TrainState, float]:
        """Dispatch optimizer step ``step`` and wait for its loss."""
        with self.tracer.span("step_dispatch", step=step) as dispatch:
            state, loss = step_fn(state, placed)
        with self.tracer.span("loss_sync", step=step) as sync:
            loss = loss_value(loss)
        # Live re-step pricing: every completed step feeds its wall seconds
        # to the policy's EMA.
        self.policy.note_step(dispatch.seconds + sync.seconds)
        if self.profiler is not None:
            self.profiler.step(samples, place_seconds=place_seconds)
        return state, loss

    def _record_step(self, step: int, state: TrainState, loss: float) -> None:
        """Count a completed step and hand it to ``config.step_callback``."""
        self.steps_done += 1
        self.obs.steps.inc()
        self.losses.append(loss)
        if self.config.step_callback is not None:
            with self.tracer.span("step_callback", step=step):
                self.config.step_callback(step, state)

    # -- result ----------------------------------------------------------------

    def _summary(self, world: int, seconds: float,
                 extra: Dict[str, float]) -> Dict[str, float]:
        """``run()``'s result: the keys both workers report, then
        ``extra``."""
        out: Dict[str, float] = {}
        if self.profiler is not None:
            out.update((f"profile_{k}", v)
                       for k, v in self.profiler.summary().items())
        out.update((f"outage_{k}", v)
                   for k, v in self.client.summary().items())
        out.update((f"policy_{m}", float(n))
                   for m, n in self.policy.decisions.items())
        out["policy_incidents"] = float(self.policy.incidents)
        out.update({
            "steps": float(self.steps_done),
            "final_loss": self.losses[-1] if self.losses else float("nan"),
            "world": float(world),
            "seconds": seconds,
        })
        out.update(extra)
        return out
