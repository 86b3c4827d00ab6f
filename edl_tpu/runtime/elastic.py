"""Elastic training: membership-driven checkpoint-restore mesh rescale.

The reference's elasticity: the autoscaler rewrites trainer Parallelism
(`pkg/autoscaler.go:361-362`), K8s adds/removes trainer pods, and correctness
rests on pserver-held state + the master task queue
(`pkg/resource/training_job.go:39-58`). On TPU all state is in the mesh, so
the flow becomes:

  register -> build mesh for current world -> restore-or-init ->
  train on leased shards, heartbeating ->
  on membership epoch change: checkpoint (async), barrier with survivors,
  rebuild mesh at the new world size, restore (reshard-on-load), resume.

Recovery time (detect -> first step on the new mesh) is measured and reported
— the north-star budget is <30 s (BASELINE.md).

``device_planner`` maps a world size to the devices this process should put
in the mesh. In production multi-host mode every process contributes its
local chips and the planner is trivial; in single-host tests/simulation it
slices the virtual CPU devices so world=1 -> 4 devices, world=2 -> 8 devices,
mimicking trainers joining a slice.
"""

from __future__ import annotations

import dataclasses
import json
import logging
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Sequence

import jax

from edl_tpu.models.base import Model
from edl_tpu.obs.tracing import Tracer, rescale_trace_id
from edl_tpu.parallel.planner import Plan
from edl_tpu.runtime.data import LeaseReader, split_pass
from edl_tpu.runtime.ft_policy import DRAIN_SHRINK, MODE_CODES, PARK, RIDE_OUT
from edl_tpu.runtime.train_loop import Trainer, TrainState
from edl_tpu.runtime.wire import WireRestartRequired
from edl_tpu.runtime.worker_base import (
    ElasticConfig, WorkerBase, heartbeat_schedule,
)
from edl_tpu.tools.profiler import annotate_step

#: coordinator KV key a worker publishes its live policy state under;
#: `edl-tpu status` enumerates members and reads these back.
FT_POLICY_KEY = "edl/ft_policy/{worker}"

__all__ = ["ElasticConfig", "ElasticWorker", "FT_POLICY_KEY", "RescaleEvent",
           "default_device_planner", "heartbeat_schedule"]

log = logging.getLogger("edl_tpu.runtime.elastic")


def default_device_planner(chips_per_trainer: int) -> Callable[[int], Sequence[jax.Device]]:
    """world -> first world*chips local devices (single-host simulation)."""

    def plan(world: int) -> Sequence[jax.Device]:
        devs = jax.devices()
        want = max(1, world * chips_per_trainer)
        if want > len(devs):
            want = len(devs)
        return devs[:want]

    return plan


@dataclass
class RescaleEvent:
    at_step: int
    from_world: int
    to_world: int
    recovery_seconds: float
    #: new-mesh step compile time, overlapped with restore on a background
    #: thread (0.0 when warm-compile was off or skipped) — reported as its
    #: own field so the recovery interval it no longer sits inside stays
    #: honest (bench_rescale.py).
    compile_seconds: float = 0.0
    #: how the warm compile was satisfied: "hit" (persistent AOT cache
    #: served a ready executable — revisit of a known layout), "miss"
    #: (compiled and stored), "off" (no cache configured / warm skipped).
    compile_cache: str = "off"
    #: the mesh layout adopted at this rescale, e.g. {"dcn": 2, "data": 4}.
    layout: Dict[str, int] = field(default_factory=dict)


class ElasticWorker(WorkerBase):
    """One trainer process's elastic loop: independent shard leases, and a
    membership change rebuilds the mesh in this process. What it shares with
    `MultiHostWorker` is `WorkerBase`'s."""

    def __init__(
        self,
        model: Model,
        client,  # coordinator client bound to this worker's name
        source,  # shard source with .read(shard)
        config: ElasticConfig,
        device_planner: Optional[Callable[[int], Sequence[jax.Device]]] = None,
        mesh_axes: Optional[Dict[str, int]] = None,
        profiler=None,  # optional edl_tpu.tools.profiler.StepProfiler
        tracer: Optional[Tracer] = None,
        layout_planner: Optional[
            Callable[[int, Sequence[jax.Device]], Optional[Plan]]] = None,
    ):
        super().__init__(model, client, source, config, mesh_axes=mesh_axes,
                         profiler=profiler, tracer=tracer,
                         layout_planner=layout_planner)
        self.planner = device_planner or default_device_planner(4)
        #: transport retry policy at construction — the regime baseline the
        #: storm deadline override is computed from and restored to.
        self._default_retry = None
        self.rescales: List[RescaleEvent] = []
        self._world = 0
        self._prev_world = 0
        self._rank = -1
        self._last_heartbeat = 0.0
        #: the current beat's interval, redrawn from the jitter stream at
        #: every beat.
        self._hb_interval = self._jittered(config.heartbeat_interval)
        #: True between observing the coordinator unreachable and the next
        #: successful control-plane call — gates benign epoch adoption.
        self._outage_open = False
        #: wall time _epoch_changed first decided to interrupt — the drain
        #: span's start (signal -> step loop quiesced), 0.0 when no signal
        #: is pending.
        self._drain_signal_t = 0.0
        #: advance-notice revocation addressed to THIS worker, consumed
        #: from the watch stream and awaiting its drain: the notice dict
        #: (worker/notice_s/reason/seq/arrival/deadline) plus the policy's
        #: ``mode`` and the wall-clock arrival for span stitching.
        self._pending_preempt: Optional[Dict] = None
        #: replay-free drain latch: the reader stops at the next shard
        #: BOUNDARY (nothing fails back) instead of interrupting mid-shard.
        self._soft_drain = False
        #: times the worker hit the outage budget and parked.
        self.parks = 0
        #: completion lag (at-least-once across hard crashes): shards whose
        #: updates the save initiated LAST is covering — their leases are
        #: completed once the NEXT save initiation proves that save durable
        #: (orbax serializes async saves).
        self._pending_commit: List[str] = []
        #: fully-consumed shards no initiated save covers yet.
        self._carry_consumed: List[str] = []
        #: per-pass step counts (multi-pass training; key = pass index).
        self.pass_steps: Dict[int, int] = {}

    # -- fault-tolerance policy plumbing ----------------------------------------

    def _on_outage_close(self, duration: float) -> None:
        """One outage incident ended (`WorkerBase._on_outage_close`): also
        re-apply the regime's transport deadline and publish the policy's
        state."""
        super()._on_outage_close(duration)
        self._apply_retry_deadline()
        self._publish_policy_state()

    def _apply_retry_deadline(self) -> None:
        """Storm regime: shorten the transport's retry deadline so calls
        fail fast into degraded mode instead of spending the policy's wait
        window inside one RPC's retry loop; restore the construction-time
        default when the regime calms."""
        raw = getattr(self.client, "client", self.client)
        retry = getattr(raw, "retry", None)
        if retry is None or not hasattr(retry, "deadline"):
            return  # in-process clients have no transport retry loop
        if self._default_retry is None:
            self._default_retry = retry
        want = self.policy.retry_deadline()
        raw.retry = (dataclasses.replace(self._default_retry, deadline=want)
                     if want is not None else self._default_retry)

    def _publish_policy_state(self) -> None:
        """Push the policy's auditable state to the coordinator KV — a
        guarded mutation, so it buffers through the outbox during the very
        outages it describes and lands on replay. `edl-tpu status` reads
        these keys back per member."""
        try:
            self.client.kv_put(
                FT_POLICY_KEY.format(worker=self.client.worker),
                json.dumps(self.policy.state()))
        except Exception:  # edl: noqa[EDL005] telemetry publish is best-effort; policy-state visibility must never take down training
            log.debug("ft_policy state publish failed", exc_info=True)

    # -- membership ------------------------------------------------------------

    def _adopt(self, info: Dict) -> None:
        self._world = max(1, info["world"])
        self._rank = int(info.get("rank", -1))
        self._adopt_epoch(info["epoch"], self._world, self._rank)

    def _sync_membership(self) -> None:
        # run() entry = incarnation boundary: a predecessor's leases (same
        # pod name, relaunched after a crash) requeue for replay.
        info = self.client.register(takeover=True)
        if not info.get("ok"):
            info = self._register_blocking(takeover=True)
        self._adopt(info)
        if self._watch is not None:
            # Subscribe after the first adoption so the cursor is primed —
            # the coordinator replays nothing we already know. Failure is
            # not fatal: poll() retries with backoff, pull covers the gap.
            self._watch.subscribe()

    def _register_blocking(self, takeover: bool = False) -> Dict:
        """Re-register, waiting out a coordinator outage — the PARKED state.

        ``takeover=False`` (the reconnect default) keeps our leases: the
        coordinator restores/renews them for a returning worker, so an
        outage shorter than the lease TTL never forfeits shards mid-
        training. The first success replays the outbox (OutboxClient)
        before we resume normal bookkeeping.
        """
        logged = False
        while True:
            reply = self.client.register(takeover=takeover)
            self.obs.note_outage_state(self.client)
            if reply.get("ok"):
                self._outage_open = False
                if logged:
                    log.info("coordinator back after %d park(s); outage "
                             "telemetry: %s", self.parks, self.client.summary())
                return reply
            if not logged:
                logged = True
                log.warning("parked: waiting for coordinator (%s)",
                            reply.get("error", "unreachable"))
            # Jittered: a coordinator restart otherwise gets the whole
            # parked fleet re-registering in phase-locked waves.
            self._outage_pause()

    def _signal_drain(self) -> bool:
        """Mark the instant the interrupt decision was made (the drain
        span's start — first signal wins: quiesce time is measured from the
        earliest observation, not the latest re-confirmation)."""
        if not self._drain_signal_t:
            self._drain_signal_t = time.time()
        return True


    def _consume_watch(self) -> bool:
        """Drain the watch (`WorkerBase._drain_watch`) and report whether
        the step loop should interrupt mid-shard: an epoch beyond ours, or
        a preempt notice whose budget is tight."""
        moved, notices = self._drain_watch()
        for notice in notices:
            if self._handle_preempt(notice):
                moved = True
        return moved

    def _handle_preempt(self, notice: Dict) -> bool:
        """Act on the policy's verdict for one revocation notice
        (`WorkerBase._decide_preempt`) and report whether the step loop
        should interrupt mid-shard. ``ride_out`` keeps stepping.
        ``drain_shrink`` (ample budget) drains at the next SHARD boundary
        via the soft latch: the in-flight shard finishes and completes, so
        NOTHING replays on the survivors. ``park`` (tight budget)
        interrupts mid-shard — the in-flight lease fails back (at-least-
        once replay accepted) to buy checkpoint time before the deadline."""
        mode = self._decide_preempt(notice)
        if mode == RIDE_OUT:
            return False
        self._pending_preempt = {
            **notice, "mode": mode,
            # monotonic arrival -> wall clock, so the preempt_drain span
            # stitches onto the survivors' rescale timeline.
            "wall_arrival": time.time() - (time.monotonic() - notice["arrival"]),
        }
        if mode == DRAIN_SHRINK:
            self._soft_drain = True
            self._signal_drain()  # drain span starts at the decision
            return False
        return True

    def _finish_preempt_drain(self, state: TrainState, drain_t0: float,
                              ck_t0: float, ck_t1: float, world: int,
                              t_start: float) -> Dict[str, float]:
        """The revoked worker's exit: evacuate this rank's shards onto
        surviving replica holders, leave (bumping the epoch the survivors
        replan under), and return a summary with ``steps_lost == 0`` — the
        blocking checkpoint that preceded this call made every consumed
        shard durable, so nothing trained here replays.

        The ``preempt_drain`` span (notice arrival -> evacuation done) is
        stamped with the POST-leave epoch's trace id: that is the rescale
        the survivors run, so their drain/replan/restore spans and our
        notice-window span stitch into one timeline.
        """
        pd = self._pending_preempt
        self._pending_preempt = None
        self._soft_drain = False
        assert pd is not None
        ev_t0 = time.time()
        if self.ckpt_plane is not None and pd["mode"] == DRAIN_SHRINK:
            # Placement override: this rank is banned from every replica
            # ring from here on, and its shards are pushed to survivors NOW
            # (peer-sourced restore must not depend on the doomed host).
            self.ckpt_plane.set_revoked([self._rank])
            self.ckpt_plane.evacuate(state, int(state.step),
                                     max(1, self._world))
        reply = self.client.leave()
        drained_mono = time.monotonic()
        ev_t1 = time.time()
        left_epoch = int(reply.get("epoch", self._epoch + 1))
        rid = rescale_trace_id(left_epoch)
        self.tracer.record("preempt_drain", pd["wall_arrival"], ev_t1,
                           trace_id=rid, component="worker", notice=True,
                           mode=pd["mode"], reason=pd.get("reason", ""),
                           notice_s=float(pd.get("notice_s", 0.0)),
                           evacuate_seconds=round(ev_t1 - ev_t0, 6))
        self.tracer.record("drain", drain_t0, ck_t0, trace_id=rid,
                           component="worker", from_world=world)
        self.tracer.record("checkpoint", ck_t0, ck_t1, trace_id=rid,
                           component="worker")
        notice_to_drained = drained_mono - pd["arrival"]
        deadline_met = drained_mono <= pd["deadline"]
        self.preempt_obs.notice_to_drained.observe(notice_to_drained)
        trigger = ("straggler" if pd.get("reason") == "straggler"
                   else "revocation")
        self.preempt_obs.evictions.inc(trigger=trigger)
        log.warning(
            "preempt drain complete: left epoch %d after %.2fs of %.1fs "
            "notice (deadline %s, trigger=%s, steps_lost=0)",
            left_epoch, notice_to_drained, float(pd.get("notice_s", 0.0)),
            "met" if deadline_met else "MISSED", trigger)
        return self._summary(world, time.perf_counter() - t_start, {
            **self._result_extra(),
            "preempted": 1.0,
            "preempt_mode_code": float(MODE_CODES[pd["mode"]]),
            "preempt_notice_s": float(pd.get("notice_s", 0.0)),
            "notice_to_drained_seconds": round(notice_to_drained, 6),
            "preempt_deadline_met": 1.0 if deadline_met else 0.0,
            # Every consumed shard was committed by the blocking checkpoint
            # above; the evacuated shards restore peer-side. Nothing replays.
            "steps_lost": 0.0,
        })

    def _result_extra(self) -> Dict[str, float]:
        """This worker's own result keys, beside `WorkerBase._summary`'s."""
        return {
            "outage_parks": float(self.parks),
            "passes_trained": float(len(self.pass_steps)),
            "rescales": float(len(self.rescales)),
            "max_recovery_seconds": max(
                (r.recovery_seconds for r in self.rescales), default=0.0),
        }

    def _epoch_changed(self, force: bool = False) -> bool:
        """Heartbeat (rate-limited) and report whether membership moved.

        Degraded mode lives here: an unreachable coordinator is NOT an
        epoch change while the outage stays inside ``outage_budget`` —
        batches already leased keep stepping, side effects buffer. Past
        the budget it reports True so run() checkpoints durably and parks.
        """
        now = time.monotonic()
        # Push fast path: the watch stream is drained BEFORE the heartbeat
        # rate limit — this is the whole latency win (a rescale notification
        # interrupts the step loop in one RTT, not a heartbeat period).
        # Draining is a non-blocking socket read, cheap enough per step.
        if self._watch is not None and self._consume_watch():
            return self._signal_drain()
        if not force and now - self._last_heartbeat < self._hb_interval:
            return False
        self._last_heartbeat = now
        self._hb_interval = self._jittered(self.config.heartbeat_interval)
        # Coalesce: every coordinator reply carries the current epoch, and
        # membership-shaped replies (piggybacked heartbeats among them) are
        # recorded by the transport. A fresh observation — made within the
        # nominal interval, so the server-side TTL was refreshed then too —
        # answers this beat without a dedicated RPC.
        reply = None if force else self._coalesced_beat(now)
        if reply is None:
            reply = self.obs.timed_heartbeat(self.client)
        self.obs.note_outage_state(self.client)
        if reply.get("unreachable"):
            self._outage_open = True
            outage = self.client.outage_seconds()
            # The policy adjudicates the incident: wait (degraded mode is
            # free while leased batches last) or escalate to checkpoint-
            # and-park. The threshold froze when the incident opened, so
            # this comparison flips at most once per incident.
            if self.policy.on_outage(outage) == PARK:
                log.warning(
                    "coordinator unreachable %.1fs (policy threshold %.1fs, "
                    "policy=%s): checkpoint-and-park", outage,
                    self.policy.frozen_threshold, self.policy.config.policy)
                self._publish_policy_state()  # buffered; lands on replay
                return self._signal_drain()
            return False
        rejoined = False
        if not reply.get("ok"):
            # We were expired (long compile stall) or the coordinator
            # restarted and forgot us: rejoin WITHOUT takeover — our leases
            # must survive the re-register (we are still training them).
            reply = self.client.register(takeover=False)
            if reply.get("unreachable"):
                self._outage_open = True
                if self.policy.on_outage(
                        self.client.outage_seconds()) == PARK:
                    return self._signal_drain()
                return False
            if not reply.get("ok") or "epoch" not in reply:
                # Repeated failure: fall back to the rendezvous path, which
                # re-registers until membership settles.
                return self._signal_drain()
            rejoined = True
        if self._outage_open or rejoined:
            self._outage_open = False
            # Reconnected (or re-registered after the coordinator forgot
            # us — an expiry, or a restart fast enough that the transport
            # retries hid the outage). A restart bumps the epoch even when
            # nobody joined or left; if world AND rank are unchanged the
            # mesh is already right — adopt the new epoch without paying a
            # rescale. Restricted to these paths: a bump_epoch with a
            # stable world is the control plane's explicit rescale nudge
            # and must still interrupt.
            if (reply["epoch"] != self._epoch
                    and int(reply.get("world", -1)) == self._world
                    and int(reply.get("rank", -2)) == self._rank):
                log.info("adopted epoch %s after outage (world/rank "
                         "unchanged)", reply["epoch"])
                self._epoch = reply["epoch"]
                return False
        if reply["epoch"] == self._epoch:
            self._rank = int(reply.get("rank", self._rank))
            return False
        return self._signal_drain()

    def _rendezvous(self) -> None:
        """Agree on (epoch, world) with every live member before building the
        mesh. The coordinator releases the sync when all current members have
        arrived at the same epoch; if membership moves mid-wait we get
        resync=True with the new epoch and retry. On timeout we proceed —
        the checkpoint is already durable and stragglers restore from it.
        An unreachable coordinator parks the rendezvous (checkpointed state
        is durable; there is nothing useful to do but wait).
        """
        attempts = 0
        while attempts < 64:
            reply = self.client.sync(
                self._epoch, timeout=self.config.rescale_barrier_timeout
            )
            if reply.get("ok"):
                self._world = max(1, reply["world"])
                return
            if reply.get("error") == "unreachable":
                # Park: does not count against the thrash bound — waiting
                # out an outage is not membership churn.
                self._adopt(self._register_blocking(takeover=False))
                continue
            attempts += 1
            if reply.get("resync"):
                self._epoch = reply["epoch"]
                self._world = max(1, reply["world"])
                continue
            if reply.get("error") == "unknown worker":
                info = self.client.register(takeover=False)
                if not info.get("ok"):
                    info = self._register_blocking(takeover=False)
                self._adopt(info)
                continue
            log.warning("rescale sync incomplete (%s); proceeding", reply)
            return
        raise RuntimeError("rendezvous thrashed: membership never settled")

    # -- state ----------------------------------------------------------------

    def _start_warm_compile(self, trainer: Trainer, fresh: TrainState,
                            trace_id: str = ""):
        """Kick off the new-mesh step compile on a daemon thread; returns
        ``join() -> compile seconds`` (0.0 when disabled/skipped/failed).

        Runs concurrently with ``ckpt.restore`` — the rescale drain already
        made state durable, so by the time restore hands back resharded
        state the executable is (ideally) ready and the first step on the
        new mesh pays dispatch, not XLA. Needs the batch avals a previous
        incarnation's first placement recorded; a cold start has none and
        compiles lazily on step 1 exactly as before. The ``warm_compile``
        span is recorded from the compile thread so its wall interval shows
        the overlap with ``restore`` on the stitched timeline.
        """
        import threading

        out = {"seconds": 0.0}
        if not self.config.warm_compile or self._batch_avals is None:
            return lambda: 0.0

        def _compile():
            t0 = time.time()
            try:
                out["seconds"] = trainer.warm_compile(fresh, self._batch_avals)
                self.tracer.record("warm_compile", t0, time.time(),
                                   trace_id=trace_id, component="worker",
                                   compile_seconds=out["seconds"],
                                   cache=trainer.last_compile_cache)
            except Exception:  # edl: noqa[EDL005] warm-compile is an optimization; a failure must degrade to the lazy step-1 compile, not kill the rescale
                self.tracer.record("warm_compile", t0, time.time(),
                                   trace_id=trace_id, component="worker",
                                   error="warm_compile_failed")
                log.warning("rescale warm-compile failed; first step will "
                            "compile lazily", exc_info=True)

        t = threading.Thread(
            target=_compile, daemon=True, name="edl-warm-compile"
        )
        t.start()

        def join() -> float:
            t.join()
            return out["seconds"]

        return join

    def _checkpoint(self, state: TrainState, block: bool = False) -> None:
        self.ckpt.save(int(state.step), state)
        if block:
            self.ckpt.wait()
        if self.ckpt_plane is not None:
            # Single-controller: this process addresses the whole mesh, so
            # one host gather covers every rank's shard. Best-effort — the
            # blob save above is the durable copy.
            self.ckpt_plane.replicate_all(
                state, int(state.step), max(1, self._world))

    def _checkpoint_and_commit(
        self, state: TrainState, reader: Optional[LeaseReader], block: bool
    ) -> None:
        """Save, then complete every shard lease a DURABLE save now covers.

        Async path: ``ckpt.save`` blocks until the previous async save
        finished, so entering it proves the prior save (covering
        ``_pending_commit``) is durable — those complete now, and the shards
        consumed since become the new in-flight pending set. Blocking path:
        everything consumed so far is durable; complete it all. A kill -9
        at ANY point replays exactly the shards no durable save covers.
        """
        consumed = self._carry_consumed + (
            reader.take_consumed() if reader is not None else []
        )
        self._carry_consumed = []
        ck_t0 = time.monotonic()
        self._checkpoint(state, block=block)
        if block:
            # Only a blocking save measures durability end-to-end (an async
            # initiation returns before the bytes land) — that is exactly
            # the cost the policy's park break-even prices.
            self.policy.note_checkpoint_cost(time.monotonic() - ck_t0)
        covered = self._pending_commit
        if block:
            covered = covered + consumed
            self._pending_commit = []
        else:
            self._pending_commit = consumed
        for task in covered:
            self.client.complete_task(task)

    # -- main loop -------------------------------------------------------------

    def run(self, max_rescales: int = 32) -> Dict[str, float]:
        """Train until the task queue is exhausted, rescaling on membership
        changes. Returns summary metrics.

        With ``config.metrics_port`` set, `/metrics` + `/healthz` + `/spans`
        are served for the run's duration (``self.metrics_url`` carries the
        bound address — port 0 means ephemeral), with the coordinator's
        status counters bridged onto the same scrape.
        """
        try:
            if self.config.metrics_port is None:
                return self._run(max_rescales)
            from edl_tpu.obs.bridge import CoordinatorStatusBridge
            from edl_tpu.obs.http import MetricsServer

            bridge = CoordinatorStatusBridge(self.client).register()
            server = MetricsServer(port=self.config.metrics_port,
                                   tracer=self.tracer,
                                   health=self._health).start()
            self.metrics_url = server.url  # edl: noqa[EDL001] set once at startup, before the serving thread handles requests
            log.info("worker metrics at %s/metrics", server.url)
            try:
                return self._run(max_rescales)
            finally:
                bridge.unregister()
                server.stop()
        finally:
            if self._watch is not None:
                self._watch.close()

    def _health(self) -> Dict:
        return {
            "worker": self.client.worker,
            "epoch": self._epoch,
            "world": self._world,
            "rank": self._rank,
            "steps": self.steps_done,
            "rescales": len(self.rescales),
            "ft_policy": self.policy.state(),
        }

    def _run(self, max_rescales: int) -> Dict[str, float]:
        self._sync_membership()
        t_start = time.perf_counter()
        #: (drain_t0, ckpt_t0, ckpt_t1) measured while the OLD epoch was
        #: draining; recorded as spans only after rendezvous settles the NEW
        #: epoch — the rescale's trace id — so all five lifecycle phases
        #: stitch under one correlator.
        pending_drain = None
        while True:
            # Rendezvous: all members agree on (epoch, world) before meshes
            # are built — joiners arrive here too, so nobody waits on a ghost.
            self._rendezvous()
            world = self._world
            rid = rescale_trace_id(self._epoch)
            if pending_drain is not None:
                drain_t0, ck_t0, ck_t1 = pending_drain
                pending_drain = None
                # No notice triggered THIS worker's drain: the zero-length
                # marker keeps the 8-phase completeness gate unconditional
                # (a revoked peer's real preempt_drain span lands on the
                # same trace id from its side of the drain).
                self.tracer.record("preempt_drain", drain_t0, drain_t0,
                                   trace_id=rid, component="worker",
                                   notice=False)
                self.tracer.record("drain", drain_t0, ck_t0, trace_id=rid,
                                   component="worker",
                                   from_world=self._prev_world)
                self.tracer.record("checkpoint", ck_t0, ck_t1, trace_id=rid,
                                   component="worker")
            rescale_t0 = time.perf_counter()
            # Replan: the layout search (planner argmin when a layout_planner
            # is wired, the static data-only resize otherwise — recorded
            # either way so every rescale timeline carries the phase and a
            # missing planner shows up as a ~0 s replan, not a missing one).
            t_replan0 = time.time()
            mesh = self._build_mesh(self.planner(world))
            replan_attrs: Dict = {"layout": json.dumps(dict(mesh.shape))}
            if self.last_plan is not None:
                replan_attrs.update(
                    planned=True,
                    schedule=self.last_plan.schedule or "none",
                    microbatches=self.last_plan.microbatches,
                    modeled_step_seconds=self.last_plan.step_seconds,
                    baseline_step_seconds=self.last_plan.baseline_step_seconds,
                )
            else:
                replan_attrs["planned"] = False
            self.tracer.record("replan", t_replan0, time.time(),
                               trace_id=rid, component="worker",
                               **replan_attrs)
            trainer = self._make_trainer(mesh, self._epoch)
            if self.profiler is not None:
                # The first step on a fresh mesh recompiles (20-40 s on TPU);
                # keep it out of steady-state summaries.
                self.profiler.mark_warmup()
            # Warm-compile overlaps restore: fresh (abstract template for
            # both) is built once, then the new mesh's step executable
            # compiles on a background thread while orbax reshards the
            # checkpoint onto the mesh.
            fresh = trainer.init_state()
            join_warm = self._start_warm_compile(trainer, fresh, trace_id=rid)
            t_restore0 = time.time()
            state = self._restore_or_init(trainer, fresh=fresh)
            self.tracer.record(
                "restore", t_restore0, time.time(), trace_id=rid,
                component="worker", world=world,
                source=self._last_restore["source"],
                bytes_from_peers=(self._last_restore["bytes"]
                                  if self._last_restore["source"] == "peer"
                                  else 0),
            )
            # Reshard: the device_put window that moved restored leaves onto
            # THIS mesh's layout. Peer restores time it explicitly
            # (ckpt_plane.recovery reports the window); a blob restore fuses
            # it into orbax's reshard-on-load and an init has nothing to
            # move — both record the zero-length marker (clamped to 1 ns by
            # the tracer) so the phase appears on every rescale timeline.
            t_restore1 = time.time()
            self.tracer.record(
                "reshard",
                self._last_restore.get("reshard_start", t_restore1),
                self._last_restore.get("reshard_end", t_restore1),
                trace_id=rid, component="worker",
                source=self._last_restore["source"],
                fused=(self._last_restore["source"] == "blob"),
            )
            if self._last_restore["source"] != "peer":
                # Peer restores feed their own EMA (note_peer_restore); only
                # a blob/init-path restore prices the blob arm.
                self.policy.note_restore_cost(time.time() - t_restore0)
            compile_seconds = join_warm()
            # first_step measures mesh-ready -> first optimizer step done:
            # the residual cost warm-compile could not hide (dispatch, any
            # lazy compile remainder, the first batch's lease + placement).
            mesh_ready = time.time()
            first_step_done = False
            #: the optimizer step, counted on the host: ``_step`` adds one a
            #: call, and reading ``state.step`` back would wait for the device
            step = last_ckpt_step = int(state.step)
            rescale = False
            finished = False

            while not rescale and not finished:
                reader = LeaseReader(
                    self.client,
                    self.source,
                    stop_check=self._epoch_changed,
                    defer_completion=True,
                    prefetch=self.config.prefetch,
                    soft_stop_check=lambda: self._soft_drain,
                    tracer=self.tracer,
                )
                if self.profiler is not None:
                    self.profiler.start()
                batches = self._dispatched(
                    reader, trainer, lambda: reader.current,
                    thread_name="edl-elastic-place-pump")
                try:
                    while True:
                        with annotate_step(step + 1), self.tracer.span(
                                "worker_step", step=step + 1) as ws:
                            with self.tracer.span("input_wait") as wait:
                                item = next(batches, None)
                                # the wait that meets the end of the input
                                # is neither a step nor a step's wait
                                ws.keep = wait.keep = item is not None
                            if item is None:
                                break
                            step += 1
                            placed, step_fn, task, samples, place_dt = item
                            state, loss = self._step_once(
                                state, placed, step_fn, step, samples,
                                place_dt)
                            if not first_step_done:
                                first_step_done = True
                                recovery = time.perf_counter() - rescale_t0
                                self.tracer.record(
                                    "first_step", mesh_ready, time.time(),
                                    trace_id=rid, component="worker",
                                    step=step, world=world,
                                )
                                if self.steps_done:  # a rescale, not cold start
                                    self.obs.rescales.inc()
                                    self.rescales.append(
                                        RescaleEvent(
                                            at_step=step,
                                            from_world=self._prev_world,
                                            to_world=world,
                                            recovery_seconds=recovery,
                                            compile_seconds=compile_seconds,
                                            compile_cache=trainer.last_compile_cache,
                                            layout={str(k): int(v) for k, v
                                                    in mesh.shape.items()},
                                        )
                                    )
                            if task is not None:
                                p = split_pass(task)[1]
                                self.pass_steps[p] = self.pass_steps.get(p, 0) + 1
                            self._record_step(step, state, loss)
                            if step - last_ckpt_step >= self.config.checkpoint_interval:
                                self._checkpoint_and_commit(state, reader, block=False)
                                last_ckpt_step = step
                            elif self._pending_commit and not self.ckpt.saving():
                                # The in-flight save landed: its shards are
                                # durable now — complete them immediately
                                # rather than holding leases until the next
                                # save initiation. (`done_task`, NOT `task`:
                                # the enclosing loop's `task` is live for
                                # per-pass step attribution above.)
                                for done_task in self._pending_commit:
                                    self.client.complete_task(done_task)
                                self._pending_commit = []
                except WireRestartRequired as e:
                    # Multi-process wire-codec overflow (only raised when
                    # jax.process_count() > 1): the widened floor is already
                    # published, and renegotiation needs a fresh membership
                    # epoch — which an in-process rebuild cannot produce (the
                    # jax.distributed world is fixed at initialize). Flush
                    # durable state and take the gang warm-restart exit.
                    from edl_tpu.launcher.launch import RESCALE_EXIT_CODE

                    batches.close()  # stop the pump before counting its reads
                    self._carry_consumed.extend(reader.take_consumed())
                    self._checkpoint_and_commit(state, None, block=True)
                    log.warning("wire codec overflow (%s); exiting %d for "
                                "gang warm-restart", e, RESCALE_EXIT_CODE)
                    raise SystemExit(RESCALE_EXIT_CODE)

                self._carry_consumed.extend(reader.take_consumed())
                if reader.interrupted is not None:
                    rescale = True
                    # Drain starts at the SIGNAL (stop_check's interrupt
                    # decision, possibly mid-step), not at this check: the
                    # interval covers finishing the in-flight batch and
                    # winding the reader down.
                    drain_t0 = self._drain_signal_t or time.time()
                    self._drain_signal_t = 0.0
                elif reader.drained:
                    # Replay-free boundary drain (advance-notice revocation
                    # with budget): the in-flight shard completed, nothing
                    # failed back.
                    rescale = True
                    drain_t0 = self._drain_signal_t or time.time()
                    self._drain_signal_t = 0.0
                elif reader.exhausted:
                    finished = True
                else:
                    # Queue empty but leases outstanding. Some may be OUR OWN
                    # completion-lagged shards: flush them durably so the
                    # queue can actually drain (multihost's tail-flush rule),
                    # then keep polling — a peer may still fail and requeue.
                    if self._carry_consumed or self._pending_commit:
                        self._checkpoint_and_commit(state, None, block=True)
                        last_ckpt_step = int(state.step)
                    self._pause()
                    if self._epoch_changed(force=True):
                        rescale = True
                        drain_t0 = self._drain_signal_t or time.time()
                        self._drain_signal_t = 0.0

            if rescale:
                # Membership changed OR the outage budget expired: make
                # state durable first. During an outage the completions
                # buffer in the outbox — this is exactly checkpoint-and-
                # park, and _register_blocking below is the park.
                ck_t0 = time.time()
                self._checkpoint_and_commit(state, None, block=True)
                ck_t1 = time.time()
                pending_drain = (drain_t0, ck_t0, ck_t1)
                if self._pending_preempt is not None:
                    # This worker is the one being revoked: finish the
                    # drain (evacuate, leave) and exit — the survivors
                    # replan and shrink under the epoch our leave bumps.
                    return self._finish_preempt_drain(
                        state, drain_t0, ck_t0, ck_t1, world, t_start)
                self._prev_world = world
                info = self.client.register(takeover=False)
                if not info.get("ok"):  # refresh observed epoch/world
                    self.parks += 1
                    self.obs.parks.inc()
                    info = self._register_blocking(takeover=False)
                self._adopt(info)
                if len(self.rescales) >= max_rescales:
                    raise RuntimeError("too many rescales; aborting")
                continue

            # Queue exhausted: final checkpoint, commit held leases, finish.
            self._checkpoint_and_commit(state, None, block=True)
            # The final commit must actually LAND (not sit buffered): wait
            # out any outage so no completed shard is lost with the process.
            while len(self.client.outbox):
                self._register_blocking(takeover=False)
                if len(self.client.outbox):
                    self.client.replay()
                if len(self.client.outbox):
                    self._pause()
            if self.pass_steps:
                log.info("per-pass steps: %s", dict(sorted(self.pass_steps.items())))
            return self._summary(self._world, time.perf_counter() - t_start,
                                 self._result_extra())
