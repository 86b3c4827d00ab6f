"""The EDL-TPU controller: job watch → per-job actors + global autoscaler.

Merges the reference's two controller generations (SURVEY §1): the legacy
path's informer + autoscaler wiring (`pkg/controller.go:44-161`,
`cmd/edl/edl.go:39-50`) and the newer CRD path's per-job lifecycle actors
(`pkg/updater/trainingJobUpdater.go`) — the merge the reference never shipped
(no caller of `updater.NewUpdater` outside its package).

Event flow (ref: Controller.onAdd, `pkg/controller.go:110-148`):

  store.create(job) ─watch→ controller.on_add
      ├─ admission: set_defaults + validate (reject to Failed, not crash)
      ├─ JobUpdater(job).start()   — materializes coordinator → trainers
      └─ autoscaler.on_add(job)    — elastic jobs join the scaling loop

Deletion mirrors it; update forwards the new spec to both consumers.
"""

from __future__ import annotations

import copy
import logging
import threading
from typing import Dict, Optional

from edl_tpu.api.types import JobPhase, TrainingJob
from edl_tpu.api.validation import ValidationError, normalize
from edl_tpu.controller.actuation import CoordinatorActuator
from edl_tpu.controller.autoscaler import Autoscaler, AutoscalerConfig
from edl_tpu.controller.cluster import ClusterProvider
from edl_tpu.controller.store import FuncWatcher, JobStore
from edl_tpu.controller.updater import JobUpdater, UpdaterConfig

log = logging.getLogger("edl_tpu.controller.controller")


class Controller:
    """Owns the store subscription, one JobUpdater per live job, and the
    autoscaler (ref: edl.New + Run, `pkg/controller.go:51-76`)."""

    def __init__(
        self,
        cluster: ClusterProvider,
        store: Optional[JobStore] = None,
        max_load_desired: float = 0.97,  # ref default, cmd/edl/edl.go:19
        autoscaler_config: Optional[AutoscalerConfig] = None,
        updater_config: Optional[UpdaterConfig] = None,
    ):
        self.cluster = cluster
        self.store = store or JobStore()
        self.updater_config = updater_config
        cfg = autoscaler_config or AutoscalerConfig(max_load_desired=max_load_desired)
        self.autoscaler = Autoscaler(cluster, cfg)
        self.autoscaler.on_scaled = self._on_scaled
        # Rescale targets also flow into each job's coordinator KV so live
        # workers actually observe them (the elastic story's two halves,
        # connected).
        self.actuator = CoordinatorActuator()
        self.autoscaler.actuator = self.actuator
        self.updaters: Dict[str, JobUpdater] = {}
        self._lock = threading.Lock()
        self._started = False
        self._watcher: Optional[FuncWatcher] = None

    # -- lifecycle (ref: controller.go:64-76) ----------------------------------

    def start(self) -> "Controller":
        """Subscribe to the store (replaying existing jobs) and start the
        autoscaler loop — the two goroutines of the reference's Run."""
        with self._lock:
            self._started = True
            watcher = FuncWatcher(self.on_add, self.on_update, self.on_del)
            self._watcher = watcher
        # Outside the lock: replay delivers on_add synchronously, and those
        # callbacks re-enter self._lock to register updaters.
        self.store.watch(watcher, replay=True)
        self.autoscaler.start()
        return self

    def stop(self) -> None:
        with self._lock:
            self._started = False
            watcher, self._watcher = self._watcher, None
            updaters = list(self.updaters.values())
            self.updaters.clear()
        if watcher is not None:
            self.store.unwatch(watcher)
        self.autoscaler.stop()
        for u in updaters:
            u.stop()

    # -- convenience API (what kubectl create/delete is to the reference) ------

    def submit(self, job: TrainingJob) -> TrainingJob:
        return self.store.create(job)

    def delete(self, name: str, namespace: str = "default") -> None:
        self.store.delete(name, namespace)

    def job_status(self, name: str, namespace: str = "default") -> TrainingJob:
        return self.store.get(name, namespace)

    def _on_scaled(self, job_name: str, record) -> None:
        """Route autoscaler actuations to the owning updater — the job's sole
        status writer — so scale history lands in the store."""
        with self._lock:
            for key, updater in self.updaters.items():
                if key.split("/", 1)[1] == job_name:
                    updater.record_scale(record)
                    return

    # -- watch callbacks (ref: onAdd/onUpdate/onDelete, controller.go:110-161) --

    def on_add(self, job: TrainingJob) -> None:
        key = f"{job.namespace}/{job.name}"
        if job.status.phase.terminal():
            # Watch replay after a controller restart: a finished job must not
            # be re-materialized (its updater would reset the phase and
            # re-create roles).
            return
        try:
            job = normalize(job)
            # Duplicate-name check and updater insertion must be one atomic
            # section, or two concurrent submits could both pass the scan.
            # The data plane (ClusterProvider, autoscaler, coordinator) keys
            # by bare job name, so a name reused across namespaces would
            # alias workloads; reject it at admission instead of misrouting.
            with self._lock:
                if key in self.updaters:
                    return
                for existing in self.updaters:
                    if existing.split("/", 1)[1] == job.name:
                        raise ValidationError(
                            f"job name {job.name!r} already in use by {existing!r}"
                        )
                updater = JobUpdater(job, self.cluster, self.store, self.updater_config)
                self.updaters[key] = updater
        except ValidationError as e:
            # Admission failure is a status, not a controller crash
            # (the reference logs and skips, controller.go:115-118).
            log.error("job %s rejected: %s", key, e)
            job.status.phase = JobPhase.FAILED
            job.status.reason = f"admission: {e}"
            try:
                self.store.update_status(job.name, job.status, job.namespace)
            except KeyError:
                pass
            return
        updater.start()
        self.actuator.track(job)
        # The updater owns (and mutates) `job`; the autoscaler gets its own
        # copy so a shared scale_history list can't collect duplicate records.
        self.autoscaler.on_add(copy.deepcopy(job))
        log.info("job %s admitted (elastic=%s)", key, job.elastic())

    def on_update(self, job: TrainingJob) -> None:
        key = f"{job.namespace}/{job.name}"
        with self._lock:
            updater = self.updaters.get(key)
        if updater is None:
            return  # never admitted (e.g. rejected duplicate) — the
            # name-keyed autoscaler must not see its events
        updater.notify_update(job)
        # Refresh the actuator's view too: the updater mints spec.auth_token
        # AFTER admission (its store write echoes back as this update), and
        # the actuator's dials must authenticate once the token exists.
        self.actuator.track(job)
        self.autoscaler.on_update(job)

    def on_del(self, job: TrainingJob) -> None:
        key = f"{job.namespace}/{job.name}"
        with self._lock:
            updater = self.updaters.pop(key, None)
        if updater is None:
            return
        updater.notify_delete()
        updater.stop()
        self.autoscaler.on_del(job)
        self.actuator.forget(job.name)
        log.info("job %s deleted", key)
