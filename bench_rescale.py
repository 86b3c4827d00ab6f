"""North-star rescale bench: recovery time + throughput retention artifacts.

BASELINE.md's acceptance criteria, measured and committed (BENCH_RESCALE.json)
instead of asserted in passing (ref: the reference's
perf story is a measured experiment, doc/boss_tutorial.md:259-301, with the
collector loop example/fit_a_line/collector.py:215-226):

- ``max_recovery_seconds`` (< 30): membership change -> first optimizer step
  on the rebuilt mesh, through the REAL control path — the autoscaler's
  ``CoordinatorActuator`` publishes ``edl/expected_world`` and nudges the
  membership epoch, a joiner registers, and the live ``ElasticWorker``
  checkpoints, rebuilds 4 -> 8 devices, restores, resumes.
- ``retention_vs_static`` (>= 0.90): post-rescale steady-state samples/s/chip
  on the 8-device mesh vs the same model trained statically on 8 devices.
- ``restart_restore_seconds``: the warm-restart path — construct a fresh
  trainer on the full mesh, restore the checkpoint, run the first step
  (what a single-chip pod pays after RESCALE_EXIT_CODE). The step compile
  runs on a background thread overlapping the restore, and is reported
  separately (``restart_warm_compile_seconds``; the in-process rescale's
  equivalent is ``warm_compile_seconds``) instead of sitting serially
  inside the restore-to-first-step interval.
- ``restore_arms``: the paired peer-vs-blob restore comparison — the same
  state restored once from the checkpoint plane (coordinator memory, zero
  blob reads) and once from orbax, everything warm on both sides. The
  elastic run itself trains with ``peer_replicas=1``, so the rescale's
  restore phase in RESCALE_TIMELINE.json carries ``source``/
  ``bytes_from_peers`` attribution.
- ``replan_arm``: the live layout-change rescale — a worker wired with the
  hybrid-parallel planner (``parallel.planner.plan_layout``) and the
  persistent AOT compile cache walks ``{dcn:2,data:4}`` (8 chips, two
  slices) -> ``{data:6}`` (6 chips, slice lost) -> back, through the real
  join / graceful-leave / re-join control path. Each leg's recovery is
  phase-attributed (drain / replan / reshard / warm_compile / restore /
  first_step) and the RETURN leg must be served by the compile cache:
  ``compile_cache == "hit"`` with warm_compile ~ 0 — revisiting a layout
  costs zero compiles.
- ``replan_sweep``: the modeled oracle — at every sweep point (chip count x
  fabric shape) the planner's chosen layout's modeled step time must
  STRICTLY beat the naive data-only resize scored under the same model.
- ``spot_arm``: the advance-notice revocation path live — the trainer
  receives a ``preempt_notice`` push mid-training, FTPolicy prices the
  notice budget, shards evacuate off the doomed rank, the drain beats the
  deadline, and a replacement peer-restores on the shrunk replanned mesh
  with EXACT step accounting (``steps_lost: 0``). ``--spot`` runs only
  this arm (the ``make bench-spot-smoke`` gate).

Run on the CPU simulation mesh by default (8 virtual devices; CI-stable);
the same script runs unmodified on real chips. Writes BENCH_RESCALE.json
plus RESCALE_TIMELINE.json — the stitched worker+controller span breakdown
of the rescale (drain -> checkpoint -> replan -> warm_compile/restore ->
reshard -> first_step under one shared trace id; see doc/observability.md)
— and prints both. ``--replan`` runs only the replan arm + sweep (the
``make bench-replan-smoke`` gate) and merges its sections into existing
artifacts.
"""

from __future__ import annotations

import json
import os
import threading
import time

if __name__ == "__main__":
    os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax


def _devices():
    """``bench.require_devices`` with this bench's own declaration: the CPU
    is accepted as the 8-virtual-device simulation mesh unless
    ``EDL_RESCALE_PLATFORM`` is set to something else (``=`` for the chip).
    The platform itself is the environment's choice (``JAX_PLATFORMS=cpu``,
    as the make targets set it)."""
    from bench import require_devices

    return require_devices(
        cpu_declared=os.environ.get("EDL_RESCALE_PLATFORM", "cpu") == "cpu")


def _steady_rate(samples_times, drop=2):
    """samples/s over (dt, samples) records, excluding the first ``drop``."""
    keep = samples_times[drop:]
    total_t = sum(dt for dt, _ in keep)
    total_s = sum(n for _, n in keep)
    return total_s / total_t if total_t > 0 else 0.0


class PhaseProfiler:
    """Per-incarnation step timing: ElasticWorker calls mark_warmup() on each
    mesh (re)build, start() per reader, step() per batch."""

    def __init__(self):
        self.phases = []
        self._cur = None
        self._last = None

    def mark_warmup(self, n: int = 1):
        self._cur = []
        self.phases.append(self._cur)

    def start(self):
        self._last = time.perf_counter()

    def step(self, samples: int, loss=None, place_seconds=None):
        now = time.perf_counter()
        if self._last is not None and self._cur is not None:
            self._cur.append((now - self._last, samples))
        self._last = now

    def summary(self):
        return {"phases": float(len(self.phases))}


#: the modeled replan sweep: (chips, fabric slices). Collective-bound
#: profile (heavy params, light per-sample compute) — the regime where the
#: layout choice dominates and the planner must strictly beat the naive
#: data-only resize at EVERY point: multi-slice points win on hierarchy
#: (a flat ring spilling past one slice is priced entirely at DCN speed),
#: single-slice points win on pipeline hybrids (less ZeRO traffic per ring).
REPLAN_SWEEP = [
    (4, (4,)),
    (6, (6,)),
    (8, (4, 4)),
    (12, (4, 4, 4)),
    (16, (8, 8)),
    (24, (8, 8, 8)),
    (32, (16, 16)),
]


def _sweep_profile():
    from edl_tpu.parallel import ModelProfile

    return ModelProfile(
        param_bytes=400e6, replicated_bytes=20e6, n_layers=24,
        flops_per_sample=2e7, activation_bytes_per_microbatch=8e6)


def run_replan_sweep() -> dict:
    """Score every sweep point: planner argmin vs data-only baseline.
    Asserts the strict win — this is the acceptance oracle, committed."""
    from edl_tpu.parallel import Topology, plan_layout
    from edl_tpu.parallel.planner import data_only_step_seconds

    profile = _sweep_profile()
    batch = 1536  # divides every dp x microbatch grid in the sweep
    points = []
    for chips, slices in REPLAN_SWEEP:
        topo = Topology(slices=slices)
        plan = plan_layout(chips, topo, profile, batch)
        base = data_only_step_seconds(chips, topo, profile, batch)
        win = plan.step_seconds < base
        points.append({
            "chips": chips,
            "slices": list(slices),
            "planned_layout": plan.describe(),
            "planned_step_ms": round(plan.step_seconds * 1e3, 4),
            "data_only_step_ms": round(base * 1e3, 4),
            "speedup": round(base / plan.step_seconds, 3),
            "strict_win": win,
        })
        assert win, (
            f"planner failed to strictly beat data-only at {chips} chips "
            f"on {slices}: {plan.describe()} {plan.step_seconds} vs {base}")
    return {
        "global_batch": batch,
        "points": points,
        "pass_planner_beats_data_only_everywhere": all(
            p["strict_win"] for p in points),
    }


def run_replan_arm(devs) -> tuple:
    """The live 8->6->8 rescale-with-layout-change arm.

    Worlds map to chip counts (world 2 -> 8 chips over two virtual slices,
    world 1 -> 6 chips of one slice), and the layout planner re-plans per
    leg: cold start lands on ``{data:6}``, the join adopts hierarchical
    ``{dcn:2,data:4}`` (compile-cache miss, stored), the graceful leave
    falls back to ``{data:6}`` (miss, stored), and the re-join RETURNS to
    ``{dcn:2,data:4}`` — which the persistent AOT cache must now serve
    (``compile_cache == "hit"``, warm_compile ~ 0). Returns
    ``(arm_result_dict, timeline_section_dict)``.
    """
    import tempfile

    import numpy as np  # noqa: F401  (parity with main's imports)

    from edl_tpu.controller.actuation import CoordinatorActuator
    from edl_tpu.coordinator import CoordinatorServer
    from edl_tpu.models import fit_a_line
    from edl_tpu.obs.tracing import RESCALE_PHASES, Tracer, rescale_timeline
    from edl_tpu.parallel import ModelProfile, Topology, plan_layout
    from edl_tpu.runtime import (
        ElasticConfig, ElasticWorker, SyntheticShardSource, TrainerConfig,
        shard_names,
    )

    model = fit_a_line.MODEL
    tag = "rp"
    # 240 divides both legs' dp grids (8 = dcn2 x data4, and data6).
    batch_size = int(os.environ.get("EDL_REPLAN_BATCH", "240"))
    n_shards = int(os.environ.get("EDL_REPLAN_SHARDS", "30"))
    batches_per_shard = int(os.environ.get("EDL_REPLAN_BPS", "24"))
    profile = ModelProfile(param_bytes=400e6, flops_per_sample=2e7)

    def layout_planner(n_chips, devices):
        # The fabric the planner sees tracks the failure mode: 8 chips are
        # two DCN-connected 4-chip slices; losing one leaves 6 chips in a
        # single ICI domain. schedules=() — fit_a_line has no stacked-layer
        # pipeline structure, so the search is dp-shape-only here.
        topo = (Topology(slices=(4, 4)) if n_chips == 8
                else Topology(slices=(n_chips,)))
        return plan_layout(n_chips, topo, profile, batch_size, schedules=())

    workdir = tempfile.mkdtemp(prefix="edl-replan-")
    trace = Tracer(component="bench")
    with CoordinatorServer(task_lease_sec=120.0,
                           heartbeat_ttl_sec=120.0) as server:
        admin = server.client("admin")
        admin.add_tasks(shard_names(tag, n_shards))
        worker = ElasticWorker(
            model,
            server.client("trainer-0"),
            SyntheticShardSource(model, batch_size=batch_size,
                                 batches_per_shard=batches_per_shard),
            ElasticConfig(
                checkpoint_dir=os.path.join(workdir, "ck"),
                checkpoint_interval=50, heartbeat_interval=0.05,
                rescale_barrier_timeout=30.0,
                trainer=TrainerConfig(optimizer="sgd", learning_rate=0.05),
                peer_replicas=1,
                compile_cache_dir=os.path.join(workdir, "aot"),
            ),
            device_planner=lambda w: devs[:8] if w >= 2 else devs[:6],
            tracer=trace,
            layout_planner=layout_planner,
        )
        stop = threading.Event()
        follower_stops = []

        def follow(joiner, stop_evt):
            """A joiner's side of the rendezvous protocol: sync the bumped
            epoch, then heartbeat-follow until told to stop (same loop as
            the elastic arm's control plane)."""
            info = joiner.register()
            epoch = info["epoch"]
            while not stop_evt.is_set():
                reply = joiner.sync(epoch, timeout=5.0)
                if reply.get("ok"):
                    break
                epoch = reply.get("epoch", epoch)
            while not stop_evt.is_set():
                hb = joiner.heartbeat()
                if hb.get("ok") and hb["epoch"] != epoch:
                    epoch = hb["epoch"]
                    joiner.sync(epoch, timeout=5.0)
                time.sleep(0.1)

        def wait_for(cond, what, timeout=180.0):
            t0 = time.time()
            while not cond():
                if stop.is_set():
                    return False
                if time.time() - t0 > timeout:
                    raise RuntimeError(f"replan arm stuck waiting for {what}")
                time.sleep(0.02)
            return True

        def control_plane():
            actuator = CoordinatorActuator()
            actuator.set_endpoint(tag, "127.0.0.1", server.port)
            # leg 1 (cold, 6 chips, {data:6}) is underway; join -> 8 chips
            if not wait_for(lambda: worker.steps_done >= 10, "first steps"):
                return
            actuator.publish_expected_world(tag, 2)
            j1 = server.client("trainer-1")
            j1_stop = threading.Event()
            follower_stops.append(j1_stop)
            t1 = threading.Thread(target=follow, args=(j1, j1_stop),
                                  daemon=True)
            t1.start()
            if not wait_for(lambda: len(worker.rescales) >= 1,
                            "rescale to 8 chips"):
                return
            base = worker.steps_done
            if not wait_for(lambda: worker.steps_done >= base + 15,
                            "steps on {dcn:2,data:4}"):
                return
            # graceful leave -> 6 chips, flat {data:6}
            actuator.publish_expected_world(tag, 1)
            j1_stop.set()
            t1.join(timeout=10)
            j1.leave()
            if not wait_for(lambda: len(worker.rescales) >= 2,
                            "rescale back to 6 chips"):
                return
            base = worker.steps_done
            if not wait_for(lambda: worker.steps_done >= base + 15,
                            "steps on {data:6}"):
                return
            # re-join -> RETURN to {dcn:2,data:4}: the cache-hit leg
            actuator.publish_expected_world(tag, 2)
            j2 = server.client("trainer-2")
            j2_stop = threading.Event()
            follower_stops.append(j2_stop)
            threading.Thread(target=follow, args=(j2, j2_stop),
                             daemon=True).start()

        t = threading.Thread(target=control_plane, daemon=True)
        t.start()
        try:
            metrics = worker.run()
        finally:
            stop.set()
            for evt in follower_stops:
                evt.set()
            t.join(timeout=15)

    assert len(worker.rescales) >= 3, (
        f"replan arm needs 3 rescales (join/leave/re-join), got "
        f"{len(worker.rescales)}: {worker.rescales}")
    legs = worker.rescales[-3:]
    assert legs[0].layout == {"dcn": 2, "data": 4}, legs[0]
    assert legs[1].layout == {"data": 6}, legs[1]
    assert legs[2].layout == {"dcn": 2, "data": 4}, legs[2]
    # THE acceptance bit: the second visit to {dcn:2,data:4} is served by
    # the persistent AOT cache — zero compiles on the return leg.
    assert legs[2].compile_cache == "hit", (
        f"return leg not served from compile cache: {legs[2]}")
    cache = worker.compile_cache
    hits = cache.hits.value(tier="memory") + cache.hits.value(tier="disk")
    assert hits >= 1, "compile cache reported a hit leg but zero hit counts"

    timeline = rescale_timeline(trace.spans)
    complete = {tid: tl for tid, tl in timeline.items()
                if all(p in tl["phases"] for p in RESCALE_PHASES)}
    assert len(complete) >= 3, (
        f"expected 3 fully-attributed rescale traces, got "
        f"{ {tid: sorted(tl['phases']) for tid, tl in timeline.items()} }")

    def leg_doc(tid):
        tl = complete[tid]
        return {
            "trace_id": tid,
            "wall_seconds": round(tl["wall_seconds"], 6),
            "phases": {
                name: {
                    "seconds": round(ph["seconds"], 6),
                    "component": ph["component"],
                    "attrs": ph.get("attrs", {}),
                }
                for name, ph in tl["phases"].items()
            },
        }

    leg_ids = sorted(complete)[-3:]
    arm = {
        "rescale": "{dcn:2,data:4} -> {data:6} -> {dcn:2,data:4}",
        "batch_size": batch_size,
        "elastic_steps": metrics["steps"],
        "legs": [
            {
                "from_world": r.from_world,
                "to_world": r.to_world,
                "layout": r.layout,
                "recovery_seconds": round(r.recovery_seconds, 3),
                "warm_compile_seconds": round(r.compile_seconds, 3),
                "compile_cache": r.compile_cache,
            }
            for r in legs
        ],
        "compile_cache_hits_total": hits,
        "compile_cache_entries_on_disk": cache.entries(),
        "return_leg_warm_compile_seconds": round(legs[2].compile_seconds, 4),
        "pass_return_leg_cached": legs[2].compile_cache == "hit",
        "pass_all_phases_attributed": True,  # asserted above
    }
    tl_section = {
        "rescale": arm["rescale"],
        "legs": [leg_doc(tid) for tid in leg_ids],
    }
    return arm, tl_section


def run_spot_arm(devs) -> tuple:
    """The spot-revocation arm: a live training run receives an
    advance-notice revocation mid-training and drains inside the notice.

    Topology: ``trainer-0`` (the single-controller ElasticWorker, 8 chips
    as two virtual slices, planner layout ``{dcn:2,data:4}``) trains with
    member ``trainer-1`` heartbeat-following. Mid-run the bench — playing
    the cloud scheduler — issues ``preempt_notice(["trainer-0"],
    notice_s)`` through the admin client. The coordinator's watch push
    fans the ``{"notify":"preempt"}`` frame to the doomed worker, whose
    FTPolicy prices the notice budget (drain-and-shrink wins), evacuates
    its ZeRO shards onto the surviving replica ring (placement override:
    rank 0 banned), checkpoints durably, and leaves before the deadline.
    A replacement worker (``trainer-2``, the spot slice gone: 4 chips,
    replanned ``{data:4}``) peer-restores from coordinator memory and
    drains the rest of the queue. ``steps_lost == 0`` is PROVEN by exact
    step accounting: doomed + survivor steps must equal the workload —
    at-least-once would inflate it, a lost shard would starve it.

    Returns ``(arm_result_dict, timeline_section_dict)``.
    """
    import tempfile

    from edl_tpu.coordinator import CoordinatorServer
    from edl_tpu.models import fit_a_line
    from edl_tpu.obs.tracing import Tracer, rescale_timeline
    from edl_tpu.parallel import ModelProfile, Topology, plan_layout
    from edl_tpu.runtime import (
        ElasticConfig, ElasticWorker, SyntheticShardSource, TrainerConfig,
        shard_names,
    )

    model = fit_a_line.MODEL
    tag = "spot"
    batch_size = int(os.environ.get("EDL_SPOT_BATCH", "240"))
    n_shards = int(os.environ.get("EDL_SPOT_SHARDS", "24"))
    batches_per_shard = int(os.environ.get("EDL_SPOT_BPS", "24"))
    notice_s = float(os.environ.get("EDL_SPOT_NOTICE_S", "20"))
    expected_steps = n_shards * batches_per_shard
    profile = ModelProfile(param_bytes=400e6, flops_per_sample=2e7)

    def layout_planner(n_chips, devices):
        topo = (Topology(slices=(4, 4)) if n_chips == 8
                else Topology(slices=(n_chips,)))
        return plan_layout(n_chips, topo, profile, batch_size, schedules=())

    workdir = tempfile.mkdtemp(prefix="edl-spot-")
    trace = Tracer(component="bench")

    def make_worker(server, name, planner):
        return ElasticWorker(
            model,
            server.client(name),
            SyntheticShardSource(model, batch_size=batch_size,
                                 batches_per_shard=batches_per_shard),
            ElasticConfig(
                checkpoint_dir=os.path.join(workdir, "ck"),
                checkpoint_interval=50, heartbeat_interval=0.05,
                rescale_barrier_timeout=30.0,
                trainer=TrainerConfig(optimizer="sgd", learning_rate=0.05),
                peer_replicas=1,
            ),
            device_planner=planner,
            tracer=trace,
            layout_planner=layout_planner,
        )

    with CoordinatorServer(task_lease_sec=120.0,
                           heartbeat_ttl_sec=120.0) as server:
        admin = server.client("admin")
        admin.add_tasks(shard_names(tag, n_shards))
        doomed = make_worker(server, "trainer-0",
                             lambda w: devs[:8] if w >= 2 else devs[:4])
        stop = threading.Event()

        def follow():
            """trainer-1: the surviving member (replica-ring peer), the
            same heartbeat-follow loop the replan arm's joiners run."""
            j = server.client("trainer-1")
            info = j.register()
            epoch = info["epoch"]
            while not stop.is_set():
                reply = j.sync(epoch, timeout=5.0)
                if reply.get("ok"):
                    break
                epoch = reply.get("epoch", epoch)
            while not stop.is_set():
                hb = j.heartbeat()
                if hb.get("ok") and hb["epoch"] != epoch:
                    epoch = hb["epoch"]
                    j.sync(epoch, timeout=5.0)
                time.sleep(0.1)

        follower = threading.Thread(target=follow, daemon=True)
        follower.start()
        revoked_at = {}

        def scheduler():
            """The cloud control plane: wait until training is warm on the
            full mesh, then revoke the trainer with advance notice."""
            t0 = time.time()
            while doomed.steps_done < 10 and not stop.is_set():
                if time.time() - t0 > 180:
                    return
                time.sleep(0.02)
            revoked_at["t"] = time.monotonic()
            admin.preempt_notice(["trainer-0"], notice_s=notice_s,
                                 reason="spot-reclaim")

        sched = threading.Thread(target=scheduler, daemon=True)
        sched.start()
        try:
            doomed_metrics = doomed.run()
        finally:
            sched.join(timeout=30)
        assert doomed_metrics.get("preempted") == 1.0, (
            f"doomed worker was not preempted: {doomed_metrics}")

        # The survivor: spot slice gone, 4 chips, replanned {data:4},
        # restored from the checkpoint plane (coordinator memory).
        survivor = make_worker(server, "trainer-2", lambda w: devs[:4])
        try:
            survivor_metrics = survivor.run()
        finally:
            stop.set()
            follower.join(timeout=10)

    steps_total = int(doomed_metrics["steps"] + survivor_metrics["steps"])
    steps_lost = int(doomed_metrics["steps_lost"])
    notice_to_drained = float(doomed_metrics["notice_to_drained_seconds"])
    deadline_met = doomed_metrics["preempt_deadline_met"] == 1.0
    restore_source = survivor._last_restore["source"]
    assert steps_total == expected_steps, (
        f"step accounting broke: doomed {doomed_metrics['steps']} + "
        f"survivor {survivor_metrics['steps']} != {expected_steps} "
        f"(replayed or lost work)")
    assert steps_lost == 0, doomed_metrics
    assert deadline_met, (
        f"drain missed the {notice_s}s notice: "
        f"{notice_to_drained:.2f}s to drained")
    assert restore_source == "peer", (
        f"survivor restored from {restore_source!r}, not the checkpoint "
        f"plane: {survivor._last_restore}")
    assert survivor.last_plan is not None \
        and survivor.last_plan.describe() == "data4", (
            f"survivor did not replan the post-revocation mesh: "
            f"{survivor.last_plan}")

    # The doomed worker's drain trace: preempt_drain (notice arrival ->
    # shard evacuation) + drain + checkpoint under the post-leave epoch.
    timeline = rescale_timeline(trace.spans)
    drain_traces = {
        tid: tl for tid, tl in timeline.items()
        if tl["phases"].get("preempt_drain", {}).get(
            "attrs", {}).get("notice")
    }
    assert drain_traces, (
        f"no trace carries a notice-attributed preempt_drain span: "
        f"{ {tid: sorted(tl['phases']) for tid, tl in timeline.items()} }")
    did, dtl = sorted(drain_traces.items())[-1]

    arm = {
        "scenario": ("trainer-0 revoked mid-training with advance notice; "
                     "survivor peer-restores on the shrunk replanned mesh"),
        "notice_s": notice_s,
        "notice_to_drained_seconds": round(notice_to_drained, 4),
        "pass_drained_before_deadline": deadline_met,
        "decision_mode_code": doomed_metrics["preempt_mode_code"],
        "steps_lost": steps_lost,
        "pass_steps_lost_zero": steps_lost == 0,
        "steps_doomed": int(doomed_metrics["steps"]),
        "steps_survivor": int(survivor_metrics["steps"]),
        "steps_expected": expected_steps,
        "pass_exact_step_accounting": steps_total == expected_steps,
        "survivor_restore_source": restore_source,
        "survivor_restore_bytes_from_peers": int(
            survivor._last_restore.get("bytes", 0)),
        "survivor_layout": survivor.last_plan.describe(),
        "pass_survivor_replanned": True,  # asserted above
        "backend": jax.default_backend(),
    }
    tl_section = {
        "scenario": arm["scenario"],
        "drain_trace_id": did,
        "phases": {
            name: {
                "seconds": round(ph["seconds"], 6),
                "component": ph["component"],
                "attrs": ph.get("attrs", {}),
            }
            for name, ph in dtl["phases"].items()
        },
    }
    return arm, tl_section


def _merge_into_json(path: str, updates: dict) -> dict:
    """Merge ``updates`` into an existing JSON artifact (the --replan smoke
    must not clobber the full bench's sections)."""
    doc = {}
    if os.path.exists(path):
        try:
            with open(path) as f:
                doc = json.load(f)
        except (OSError, ValueError):
            doc = {}
    doc.update(updates)
    with open(path, "w") as f:
        json.dump(doc, f, indent=1)
    return doc


def replan_main() -> None:
    """`make bench-replan-smoke`: only the replan arm + modeled sweep,
    merged into the committed artifacts."""
    devs = _devices()
    if len(devs) < 8:
        print(json.dumps({"error": f"replan arm needs 8 devices, have "
                                   f"{len(devs)}"}))
        raise SystemExit(1)
    sweep = run_replan_sweep()
    arm, tl_section = run_replan_arm(devs)
    here = os.path.dirname(os.path.abspath(__file__))
    result = _merge_into_json(
        os.path.join(here, "BENCH_RESCALE.json"),
        {"replan_arm": arm, "replan_sweep": sweep})
    _merge_into_json(os.path.join(here, "RESCALE_TIMELINE.json"),
                     {"replan_arm": tl_section})
    print(json.dumps({"replan_arm": result["replan_arm"],
                      "replan_sweep": result["replan_sweep"]}))


def spot_main() -> None:
    """`make bench-spot-smoke`: only the spot-revocation arm, merged into
    the committed artifacts."""
    devs = _devices()
    if len(devs) < 8:
        print(json.dumps({"error": f"spot arm needs 8 devices, have "
                                   f"{len(devs)}"}))
        raise SystemExit(1)
    arm, tl_section = run_spot_arm(devs)
    here = os.path.dirname(os.path.abspath(__file__))
    result = _merge_into_json(
        os.path.join(here, "BENCH_RESCALE.json"), {"spot_arm": arm})
    _merge_into_json(os.path.join(here, "RESCALE_TIMELINE.json"),
                     {"spot_arm": tl_section})
    print(json.dumps({"spot_arm": result["spot_arm"]}))


def main() -> None:
    from edl_tpu.controller.actuation import CoordinatorActuator
    from edl_tpu.coordinator import CoordinatorServer
    from edl_tpu.models import fit_a_line
    from edl_tpu.parallel import MeshSpec, build_mesh
    from edl_tpu.runtime import (
        ElasticConfig, ElasticWorker, SyntheticShardSource, Trainer,
        TrainerConfig, shard_names,
    )
    from edl_tpu.runtime.checkpoint import (
        Checkpointer, abstract_like, live_state_specs,
    )
    from edl_tpu.obs.tracing import (
        RESCALE_PHASES, Tracer, rescale_timeline, rescale_trace_id,
    )
    import numpy as np

    import tempfile

    batch_size = int(os.environ.get("EDL_RESCALE_BATCH", "256"))
    n_shards = int(os.environ.get("EDL_RESCALE_SHARDS", "12"))
    batches_per_shard = int(os.environ.get("EDL_RESCALE_BPS", "24"))
    model = fit_a_line.MODEL
    devs = _devices()
    full = len(devs)  # 8 on the simulation mesh
    half = max(1, full // 2)
    tcfg = TrainerConfig(optimizer="sgd", learning_rate=0.05)

    def run_worker(tag: str, planner, join: bool, tracer=None,
                   peer_replicas: int = 0):
        """One full worker run over the identical workload/config; only the
        device plan and the mid-run membership change differ — so retention
        compares elastic-after-rescale against static on the SAME pipeline
        (leases, heartbeats, periodic checkpoints included in both)."""
        workdir = tempfile.mkdtemp(prefix=f"edl-rescale-{tag}-")
        with CoordinatorServer(task_lease_sec=120.0,
                               heartbeat_ttl_sec=120.0) as server:
            admin = server.client("admin")
            admin.add_tasks(shard_names(tag, n_shards))
            prof = PhaseProfiler()
            worker = ElasticWorker(
                model,
                server.client("trainer-0"),
                SyntheticShardSource(model, batch_size=batch_size,
                                     batches_per_shard=batches_per_shard),
                # heartbeat_interval bounds epoch-change DETECTION latency;
                # at 0.2 s a warm XLA cache could drain the whole queue
                # before the first beat saw the bump ("no rescale happened"
                # flake) — 0.05 s keeps detection well inside the workload.
                ElasticConfig(checkpoint_dir=os.path.join(workdir, "ck"),
                              checkpoint_interval=50, heartbeat_interval=0.05,
                              rescale_barrier_timeout=30.0, trainer=tcfg,
                              peer_replicas=peer_replicas),
                device_planner=planner,
                profiler=prof,
                tracer=tracer,
            )
            stop = threading.Event()
            t = None
            if join:

                def control_plane():
                    """The autoscaler's actuation, verbatim: wait for live
                    progress, publish the new expected world (epoch nudge
                    included), and bring up the 'new pod', which registers
                    and follows the rendezvous protocol."""
                    while worker.steps_done < 10 and not stop.is_set():
                        time.sleep(0.02)
                    actuate_t0 = time.time()
                    actuator = CoordinatorActuator()
                    actuator.set_endpoint(tag, "127.0.0.1", server.port)
                    actuator.publish_expected_world(tag, 2)
                    joiner = server.client("trainer-1")
                    info = joiner.register()  # membership event -> epoch bump
                    epoch = info["epoch"]
                    if tracer is not None:
                        # The register reply carries the bumped epoch — the
                        # same rescale correlator the worker stamps on its
                        # drain/checkpoint/restore spans, so the controller
                        # side stitches onto the same timeline with no
                        # propagation header (doc/observability.md).
                        tracer.record("actuate", actuate_t0, time.time(),
                                      trace_id=rescale_trace_id(epoch),
                                      component="controller", job=tag,
                                      world=2)
                    while not stop.is_set():
                        reply = joiner.sync(epoch, timeout=5.0)
                        if reply.get("ok"):
                            break
                        epoch = reply.get("epoch", epoch)
                    while not stop.is_set():
                        hb = joiner.heartbeat()
                        if hb.get("ok") and hb["epoch"] != epoch:
                            epoch = hb["epoch"]
                            joiner.sync(epoch, timeout=5.0)
                        time.sleep(0.2)

                t = threading.Thread(target=control_plane, daemon=True)
                t.start()
            try:
                metrics = worker.run()
            finally:
                stop.set()
                if t is not None:
                    t.join(timeout=10)
        return worker, prof, metrics, workdir

    # -- static reference: full mesh from step 0, same pipeline ---------------
    _, static_prof, _, _ = run_worker("st", lambda w: devs, join=False)
    static_per_chip = _steady_rate(static_prof.phases[-1]) / full

    # -- elastic run: 1 -> 2 trainers through the real actuator path ----------
    # One tracer shared by the worker (drain/checkpoint/warm_compile/restore/
    # first_step spans) and the bench's control-plane thread (the actuate
    # span): exactly what a JSONL-stream merge of two pods' sinks would hold.
    # peer_replicas=1 puts the checkpoint plane in the loop: the rescale's
    # restore is served from coordinator memory, and the timeline's restore
    # phase carries source="peer" + bytes_from_peers attribution.
    trace = Tracer(component="bench")
    worker, prof, metrics, workdir = run_worker(
        "rb", lambda w: devs[: min(full, w * half)], join=True, tracer=trace,
        peer_replicas=1,
    )

    assert worker.rescales, "no rescale happened; bench invalid"
    max_recovery = max(r.recovery_seconds for r in worker.rescales)
    post = prof.phases[-1]  # the 8-device incarnation
    post_per_chip = _steady_rate(post) / full
    retention = post_per_chip / static_per_chip if static_per_chip else 0.0

    mesh = build_mesh(MeshSpec({"data": full}), devs)
    rng = np.random.default_rng(0)
    host = [model.synthetic_batch(rng, batch_size)]

    # -- warm-restart restore cost (single-incarnation path) ------------------
    # The step compile runs on a background thread CONCURRENT with the orbax
    # restore (the same overlap ElasticWorker does during a rescale), so
    # restart_restore_seconds no longer contains XLA compile time — it is
    # reported as its own field instead.
    t0 = time.perf_counter()
    ckpt = Checkpointer(os.path.join(workdir, "ck"))
    r_trainer = Trainer(model, mesh, tcfg)
    fresh = r_trainer.init_state()
    avals = {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
             for k, v in host[0].items()}
    warm_out = {"seconds": 0.0}

    def _warm():
        warm_out["seconds"] = r_trainer.warm_compile(fresh, avals)

    warm_t = threading.Thread(target=_warm, daemon=True)
    warm_t.start()
    restored = ckpt.restore(abstract_like(fresh), mesh, live_state_specs(fresh))
    warm_t.join()
    restored, loss = r_trainer.train_step(
        restored, r_trainer.place_batch(host[0])
    )
    jax.block_until_ready(loss)
    restart_restore_seconds = time.perf_counter() - t0
    restart_warm_compile_seconds = warm_out["seconds"]

    # -- paired restore arms: peer (coordinator memory) vs blob (orbax) -------
    # Same state, same target mesh/specs, everything warm on both sides —
    # the isolated restore-path comparison the ft_policy break-even prices.
    from edl_tpu.ckpt_plane import CkptPlane
    from edl_tpu.coordinator import InProcessCoordinator

    t0 = time.perf_counter()
    blob_state = ckpt.restore(abstract_like(fresh), mesh,
                              live_state_specs(fresh))
    jax.block_until_ready(jax.tree_util.tree_leaves(blob_state))
    blob_arm_seconds = time.perf_counter() - t0

    coord = InProcessCoordinator()
    pclient = coord.client("bench-plane")
    pclient.register()
    plane = CkptPlane(pclient, replicas=1)
    rep = plane.replicate_all(restored, int(restored.step), world=2)
    assert rep is not None, "bench plane replication failed"
    t0 = time.perf_counter()
    peer_state, pinfo = plane.restore(fresh, mesh, live_state_specs(fresh))
    jax.block_until_ready(jax.tree_util.tree_leaves(peer_state))
    peer_arm_seconds = time.perf_counter() - t0

    # -- layout-change arm + modeled sweep (the replanner's acceptance) --------
    replan_sweep = run_replan_sweep()
    replan_arm, replan_tl = run_replan_arm(devs)

    # -- spot-revocation arm (advance-notice drain; doc/robustness.md) ---------
    spot_arm, spot_tl = run_spot_arm(devs)

    result = {
        "max_recovery_seconds": round(max_recovery, 3),
        "retention_vs_static": round(retention, 4),
        "restart_restore_seconds": round(restart_restore_seconds, 3),
        "restart_warm_compile_seconds": round(restart_warm_compile_seconds, 3),
        "warm_compile_seconds": round(
            max((r.compile_seconds for r in worker.rescales), default=0.0), 3
        ),
        "pass_recovery_under_30s": max_recovery < 30.0,
        "pass_retention_over_90pct": retention >= 0.90,
        "restore_arms": {
            "blob_seconds": round(blob_arm_seconds, 4),
            "peer_seconds": round(peer_arm_seconds, 4),
            "peer_bytes": int(pinfo["bytes"]),
            "pass_peer_faster": peer_arm_seconds < blob_arm_seconds,
        },
        "replan_arm": replan_arm,
        "replan_sweep": replan_sweep,
        "spot_arm": spot_arm,
        "details": {
            "devices": full,
            "rescale": f"{half}->{full} devices (world 1->2)",
            "static_samples_per_sec_per_chip": round(static_per_chip, 2),
            "post_rescale_samples_per_sec_per_chip": round(post_per_chip, 2),
            "elastic_steps": metrics["steps"],
            "rescale_events": [
                {"at_step": r.at_step, "from_world": r.from_world,
                 "to_world": r.to_world,
                 "recovery_seconds": round(r.recovery_seconds, 3),
                 "compile_seconds": round(r.compile_seconds, 3)}
                for r in worker.rescales
            ],
            "backend": jax.default_backend(),
        },
    }
    here = os.path.dirname(os.path.abspath(__file__))
    out = os.path.join(here, "BENCH_RESCALE.json")
    with open(out, "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps(result))

    # -- the stitched rescale timeline (RESCALE_TIMELINE.json) ----------------
    # The cold-start trace id carries warm_compile/restore/first_step only;
    # the REAL rescale's id carries the full lifecycle, controller included —
    # that one is the headline artifact.
    timeline = rescale_timeline(trace.spans)
    complete = {
        tid: t for tid, t in timeline.items()
        if all(p in t["phases"] for p in RESCALE_PHASES)
    }
    phases_seen = {tid: sorted(t["phases"]) for tid, t in timeline.items()}
    assert complete, (
        f"no trace carries every lifecycle phase {RESCALE_PHASES}; "
        f"saw {phases_seen}"
    )
    rid, breakdown = sorted(complete.items())[-1]  # latest epoch = the rescale
    timeline_doc = {
        "rescale_trace_id": rid,
        "phase_order": list(RESCALE_PHASES),
        "phases": {
            name: {
                "seconds": round(ph["seconds"], 6),
                "start": round(ph["start"], 6),
                "end": round(ph["end"], 6),
                "component": ph["component"],
                "count": ph["count"],
                "attrs": ph.get("attrs", {}),
            }
            for name, ph in breakdown["phases"].items()
        },
        "components": breakdown["components"],
        "wall_seconds": round(breakdown["wall_seconds"], 6),
        "span_count": breakdown["span_count"],
        "note": (
            "phase seconds may sum past wall_seconds: warm_compile runs "
            "concurrent with restore by design (see doc/observability.md)"
        ),
        "replan_arm": replan_tl,
        "spot_arm": spot_tl,
    }
    tl_out = os.path.join(here, "RESCALE_TIMELINE.json")
    with open(tl_out, "w") as f:
        json.dump(timeline_doc, f, indent=1)
    print(json.dumps(timeline_doc))


if __name__ == "__main__":
    import sys

    if "--replan" in sys.argv:
        replan_main()
    elif "--spot" in sys.argv:
        spot_main()
    else:
        main()
