"""`runtime/worker_base.py`: what `ElasticWorker` and `MultiHostWorker` share
is defined once, so every case here runs against both classes. The one-process
`MultiHostWorker` is rank 0 of a world of one over an `InProcessCoordinator`."""

import types

import jax
import pytest

from edl_tpu.coordinator import InProcessCoordinator
from edl_tpu.models import fit_a_line
from edl_tpu.obs.tracing import Tracer
from edl_tpu.runtime import (
    ElasticConfig, ElasticWorker, MultiHostWorker, SyntheticShardSource,
    shard_names,
)
from edl_tpu.runtime.elastic import heartbeat_schedule
from edl_tpu.runtime.ft_policy import DRAIN_SHRINK, RIDE_OUT
from edl_tpu.runtime.train_loop import TrainerConfig
from edl_tpu.runtime.worker_base import WorkerBase

BOTH = pytest.mark.parametrize("cls", [ElasticWorker, MultiHostWorker])
MODEL = fit_a_line.MODEL


def make(cls, tmp_path, client=None, name="w0", **kw):
    config = kw.pop("config", None) or ElasticConfig(
        checkpoint_dir=str(tmp_path / "ck"),
        trainer=TrainerConfig(optimizer="sgd", learning_rate=0.05))
    if client is None:
        client = InProcessCoordinator().client(name)
    source = SyntheticShardSource(MODEL, batch_size=8, batches_per_shard=2)
    return cls(MODEL, client, source, config, **kw)


def plan(axes, batch_axis="data"):
    """What `_build_mesh` and `_trainer_config` read of a `planner.Plan`."""
    return types.SimpleNamespace(
        mesh_axes=tuple(axes.items()), batch_axis=batch_axis,
        hierarchical=axes.get("dcn", 1) > 1)


@BOTH
def test_both_workers_are_the_base_and_define_no_shared_member_twice(cls):
    assert issubclass(cls, WorkerBase)
    for member in ("_jittered", "_pause", "_outage_pause", "_drain_watch",
                   "_decide_preempt", "_coalesced_beat", "_adopt_epoch",
                   "_build_mesh", "_trainer_config", "_make_trainer",
                   "_restore_or_init", "_dispatched", "_step_once",
                   "_record_step", "_summary", "_WATCH_PULL_STRETCH"):
        assert member not in vars(cls), member
        assert hasattr(WorkerBase, member), member


@BOTH
def test_checkpoint_dir_is_required(cls, tmp_path):
    with pytest.raises(ValueError, match="checkpoint_dir"):
        make(cls, tmp_path, config=ElasticConfig())


@BOTH
def test_layout_planner_excludes_mesh_axes(cls, tmp_path):
    with pytest.raises(ValueError, match="not both"):
        make(cls, tmp_path, mesh_axes={"model": 2},
             layout_planner=lambda n, devices: None)


@BOTH
def test_watch_mode_refuses_a_transport_with_no_watch_surface(cls, tmp_path):
    bare = types.SimpleNamespace(worker="w0")  # no host/port, no call
    config = ElasticConfig(checkpoint_dir=str(tmp_path / "ck"),
                           epoch_discovery="watch")
    with pytest.raises(ValueError, match="epoch_discovery='watch'"):
        make(cls, tmp_path, client=bare, config=config)
    # 'auto' on the same transport degrades to pull without complaint
    auto = ElasticConfig(checkpoint_dir=str(tmp_path / "ck"))
    assert make(cls, tmp_path, client=bare, config=auto)._watch is None


@BOTH
def test_build_mesh_static_axes_size_data_from_what_is_left(cls, tmp_path):
    worker = make(cls, tmp_path, mesh_axes={"model": 2})
    mesh = worker._build_mesh(jax.devices()[:8])
    assert dict(mesh.shape) == {"model": 2, "data": 4}
    assert worker.last_plan is None
    assert worker._trainer_config() is worker.config.trainer


@BOTH
def test_build_mesh_refuses_devices_the_fixed_axes_do_not_divide(cls, tmp_path):
    worker = make(cls, tmp_path, mesh_axes={"model": 4})
    with pytest.raises(ValueError, match="not divisible"):
        worker._build_mesh(jax.devices()[:6])


@BOTH
def test_build_mesh_adopts_a_planned_layout(cls, tmp_path):
    seen = []

    def planner(n, devices):
        seen.append((n, len(devices)))
        return plan({"data": 2, "model": 2}) if n == 4 else None

    worker = make(cls, tmp_path, layout_planner=planner)
    mesh = worker._build_mesh(jax.devices()[:4])
    assert dict(mesh.shape) == {"data": 2, "model": 2}
    assert worker.last_plan is not None and seen == [(4, 4)]
    # the planner declines: the static data-only resize, and no stale plan
    mesh = worker._build_mesh(jax.devices()[:2])
    assert dict(mesh.shape) == {"data": 2} and worker.last_plan is None


@BOTH
def test_trainer_config_follows_the_plans_batch_axis(cls, tmp_path):
    axes = {"dcn": 2, "data": 2}
    worker = make(cls, tmp_path,
                  layout_planner=lambda n, d: plan(axes, ("dcn", "data")))
    mesh = worker._build_mesh(jax.devices()[:4])
    assert mesh.axis_names[0] == "dcn"  # hierarchical: dcn outermost
    config = worker._trainer_config()
    assert config.batch_axis == ("dcn", "data")
    assert config is not worker.config.trainer
    assert worker.config.trainer.batch_axis == "data"  # the static one is kept
    # a plan on the static config's own axis hands that config back
    worker.last_plan = plan({"data": 4})
    assert worker._trainer_config() is worker.config.trainer


@BOTH
def test_jitter_stream_is_the_heartbeat_schedule(cls, tmp_path):
    worker = make(cls, tmp_path, name="trainer-7")
    cfg = worker.config
    want = heartbeat_schedule("trainer-7", cfg.heartbeat_interval,
                              cfg.heartbeat_jitter, 16)
    # ElasticWorker drew its first interval when it was built
    drawn = [worker._hb_interval] if cls is ElasticWorker else []
    drawn += [worker._jittered(cfg.heartbeat_interval)
              for _ in range(16 - len(drawn))]
    assert drawn == want
    assert len(set(want)) > 1
    assert want != heartbeat_schedule("trainer-8", cfg.heartbeat_interval,
                                      cfg.heartbeat_jitter, 16)


@BOTH
def test_pauses_draw_from_the_same_stream(cls, tmp_path, monkeypatch):
    slept = []
    monkeypatch.setattr("edl_tpu.runtime.worker_base.time.sleep", slept.append)
    config = ElasticConfig(checkpoint_dir=str(tmp_path / "ck"),
                           heartbeat_interval=4.0, heartbeat_jitter=0.5)
    worker = make(cls, tmp_path, name="p", config=config)
    skip = 1 if cls is ElasticWorker else 0
    worker._pause()           # base 0.2
    worker._outage_pause()    # heartbeat cadence, held to [0.1, 1.0] s
    unit = [v / 4.0 for v in heartbeat_schedule("p", 4.0, 0.5, skip + 2)][skip:]
    assert slept == pytest.approx([0.2 * unit[0], 1.0 * unit[1]])


@BOTH
def test_ride_out_latches_nothing_and_counts_the_notice(cls, tmp_path, monkeypatch):
    import time

    worker = make(cls, tmp_path)
    notices = worker.preempt_obs.notices  # one registry a process: count up
    before = notices.value(reason="spot")
    verdicts = iter([RIDE_OUT, DRAIN_SHRINK])
    asked = []

    def on_preempt_notice(remaining):
        asked.append(remaining)
        return next(verdicts)

    monkeypatch.setattr(worker.policy, "on_preempt_notice", on_preempt_notice)
    now = time.monotonic()
    notice = {"worker": "w0", "notice_s": 30.0, "reason": "spot", "seq": 1,
              "arrival": now, "deadline": now + 30.0}
    latch = ("_pending_preempt" if cls is ElasticWorker else "_preempt_notice")

    assert worker._decide_preempt(notice) == RIDE_OUT
    assert not worker._handle_preempt(notice)  # second verdict: drain_shrink
    assert getattr(worker, latch)["mode"] == DRAIN_SHRINK

    setattr(worker, latch, None)
    verdicts = iter([RIDE_OUT])
    assert not worker._handle_preempt(notice)
    assert getattr(worker, latch) is None
    assert len(asked) == 3 and all(29.0 < r <= 30.0 for r in asked)
    assert notices.value(reason="spot") - before == 3


@BOTH
def test_drain_watch_reports_moves_beyond_the_adopted_epoch(cls, tmp_path):
    import time

    class Watch:
        connected = True
        last_epoch = -1

        def __init__(self):
            self.epochs, self.preempts = [], []

        def poll(self, timeout=0.0):
            out, self.epochs = self.epochs, []
            return [(e, time.monotonic()) for e in out]

        def take_preempts(self):
            out, self.preempts = self.preempts, []
            return out

    worker = make(cls, tmp_path, config=ElasticConfig(
        checkpoint_dir=str(tmp_path / "ck"), epoch_discovery="pull"))
    assert worker._drain_watch() == (False, [])  # no watch at all
    worker._watch = Watch()
    worker._adopt_epoch(5, 1, 0)
    assert worker._watch.last_epoch == 5  # the resume cursor is primed
    worker._watch.epochs = [4, 5]
    assert worker._drain_watch() == (False, [])
    worker._watch.epochs, worker._watch.preempts = [6], [{"seq": 1}]
    assert worker._drain_watch() == (True, [{"seq": 1}])


@BOTH
def test_run_records_the_steps_spans_and_the_shared_summary(cls, tmp_path):
    coord = InProcessCoordinator(task_lease_sec=60.0, heartbeat_ttl_sec=60.0)
    coord.client("admin").add_tasks(shard_names("fit", 3))
    tracer = Tracer()
    steps = []
    config = ElasticConfig(
        checkpoint_dir=str(tmp_path / "ck"), checkpoint_interval=1000,
        step_callback=lambda step, state: steps.append(step),
        trainer=TrainerConfig(optimizer="sgd", learning_rate=0.05))
    worker = make(cls, tmp_path, client=coord.client("w0"), config=config,
                  tracer=tracer)
    if cls is ElasticWorker:
        worker.planner = lambda world: jax.devices()[:1]
    result = worker.run()

    assert result["steps"] == 6.0 and steps == [1, 2, 3, 4, 5, 6]
    assert result["final_loss"] == worker.losses[-1] and len(worker.losses) == 6
    for key in ("world", "seconds", "policy_incidents", "outage_outages"):
        assert key in result, key
    own = ("rescales", "max_recovery_seconds", "passes_trained") \
        if cls is ElasticWorker else ("rank",)
    assert all(key in result for key in own)
    for name in ("place", "step_dispatch", "loss_sync", "step_callback"):
        spans = tracer.find(name=name)
        assert len(spans) == 6, (name, len(spans))
    assert [s.attrs["step"] for s in tracer.find(name="loss_sync")] == steps
    assert worker._last_restore == {"source": "init", "bytes": 0}
    assert int(coord.client("probe").status()["done"]) == 3
