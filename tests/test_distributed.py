"""Distributed-identity derivation tests (hermetic, in-process coordinator)."""

import threading

import pytest

from conftest import multiprocess_on_cpu
from edl_tpu.coordinator.inprocess import InProcessCoordinator
from edl_tpu.launcher.launch import LaunchContext
from edl_tpu.runtime.distributed import (
    JAX_COORD_KEY,
    derive_identity,
    distributed_init,
    local_host_ip,
)


def ctx_with(num_trainers, port=7164):
    return LaunchContext.from_env({
        "EDL_JOB_NAME": "t",
        "EDL_NUM_TRAINERS": str(num_trainers),
        "EDL_PORT": str(port),
    })


def test_rank0_publishes_and_peer_reads():
    coord = InProcessCoordinator()
    c0 = coord.client("w0")
    c1 = coord.client("w1")
    c0.register(), c1.register()
    ctx = ctx_with(2)

    got = {}

    def peer():
        got["ident"] = derive_identity(ctx, c1, timeout=10.0)

    t = threading.Thread(target=peer, daemon=True)
    t.start()
    ident0 = derive_identity(ctx, c0, timeout=10.0)
    t.join(timeout=10)
    assert ident0.process_id == 0
    assert ident0.num_processes == 2
    assert ident0.coordinator_address.endswith(":7165")  # port + offset
    assert got["ident"].process_id == 1
    assert got["ident"].coordinator_address == ident0.coordinator_address
    epoch = c0.register()["epoch"]
    assert c0.kv_get(f"{JAX_COORD_KEY}/{epoch}") == ident0.coordinator_address


def test_peer_times_out_without_rank0():
    coord = InProcessCoordinator()
    c0 = coord.client("w0")
    c1 = coord.client("w1")
    c0.register(), c1.register()  # w1 gets rank 1
    with pytest.raises(TimeoutError):
        derive_identity(ctx_with(2), c1, timeout=0.5)


def test_single_process_is_noop():
    coord = InProcessCoordinator()
    c = coord.client("w0")
    c.register()
    assert distributed_init(ctx_with(1), c) is None
    assert distributed_init(ctx_with(4), None) is None


def test_explicit_jax_port():
    coord = InProcessCoordinator()
    c = coord.client("w0")
    c.register()
    ident = derive_identity(ctx_with(1), c, jax_port=9999)
    assert ident.coordinator_address.endswith(":9999")


def test_expected_world_kv_overrides_stale_env():
    """After a rescale the pod env's EDL_NUM_TRAINERS is stale; the control
    plane's published target wins."""
    from edl_tpu.runtime.distributed import EXPECTED_WORLD_KEY, expected_world

    coord = InProcessCoordinator()
    c = coord.client("w0")
    c.register()
    ctx = ctx_with(4)
    assert expected_world(ctx, c) == 4
    c.kv_put(EXPECTED_WORLD_KEY, "2")
    assert expected_world(ctx, c) == 2


def test_epoch_scoped_address_ignores_stale_key():
    """A dead rank 0's address from a previous epoch must never be read."""
    coord = InProcessCoordinator()
    c0 = coord.client("w0")
    c0.register()
    # a previous incarnation published under an old epoch
    c0.kv_put(f"{JAX_COORD_KEY}/0", "10.0.0.99:7165")
    ident = derive_identity(ctx_with(1), c0, timeout=10.0)
    assert ident.coordinator_address != "10.0.0.99:7165"


def test_local_host_ip_shape():
    ip = local_host_ip()
    assert ip.count(".") == 3


@multiprocess_on_cpu
def test_two_process_jax_distributed_bringup(tmp_path):
    """THE multi-host proof: two OS processes, each with 2 virtual CPU
    devices, form one 4-device jax.distributed world via the real C++
    coordinator — rank from registration, rank 0's address via KV."""
    import os
    import subprocess
    import sys

    from edl_tpu.coordinator import CoordinatorServer
    from edl_tpu.coordinator.server import ensure_built, free_port

    ensure_built()
    jax_port = free_port()
    worker_src = f"""
import os, sys
sys.path.insert(0, {repr(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))})
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
os.environ["JAX_PLATFORMS"] = "cpu"
import jax
jax.config.update("jax_platforms", "cpu")
from edl_tpu.launcher.launch import LaunchContext
from edl_tpu.launcher.discovery import wait_coordinator
from edl_tpu.runtime.distributed import distributed_init

ctx = LaunchContext.from_env()
client = wait_coordinator(ctx.coordinator_endpoint)
client.worker = "w-" + sys.argv[1]
ident = distributed_init(ctx, client, timeout=60.0, jax_port={jax_port})
assert ident is not None
assert jax.process_count() == 2, jax.process_count()
assert jax.device_count() == 4, jax.device_count()
assert len(jax.local_devices()) == 2
from jax.experimental import multihost_utils
ranks = multihost_utils.process_allgather(__import__("numpy").array([jax.process_index()]))
assert sorted(ranks.ravel().tolist()) == [0, 1], ranks
print("WORKER-OK", ident.process_id)
"""
    with CoordinatorServer() as server:
        env = dict(os.environ)
        env["EDL_COORDINATOR_ENDPOINT"] = server.address
        env["EDL_NUM_TRAINERS"] = "2"
        procs = [
            subprocess.Popen(
                [sys.executable, "-c", worker_src, str(i)],
                env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                text=True,
            )
            for i in range(2)
        ]
        outs = [p.communicate(timeout=180) for p in procs]
        for p, (out, err) in zip(procs, outs):
            assert p.returncode == 0, f"worker failed:\n{err[-2000:]}"
            assert "WORKER-OK" in out


def test_launcher_relaunches_on_rescale_exit(tmp_path):
    """An entry exiting RESCALE_EXIT_CODE is warm-restarted without burning
    the job failure budget; a normal exit ends the loop."""
    import os

    from edl_tpu.coordinator import CoordinatorServer
    from edl_tpu.launcher.launch import (
        FAILED_COUNT_KEY,
        LaunchContext,
        RESCALE_EXIT_CODE,
        start_trainer,
    )

    marker = tmp_path / "ran"
    entry = tmp_path / "entry.sh"
    entry.write_text(
        "#!/bin/sh\n"
        f"if [ -f {marker} ]; then exit 0; fi\n"
        f"touch {marker}\n"
        f"exit {RESCALE_EXIT_CODE}\n"
    )
    entry.chmod(0o755)

    with CoordinatorServer() as server:
        ctx = LaunchContext.from_env({
            "EDL_JOB_NAME": "t",
            "EDL_COORDINATOR_ENDPOINT": server.address,
            "EDL_ENTRY": f"sh {entry}",
            "EDL_TERMINATION_LOG": str(tmp_path / "term"),
        })
        rc = start_trainer(ctx)
        assert rc == 0
        assert marker.exists()  # first run happened, second run returned 0
        failed = server.client("probe").kv_get(FAILED_COUNT_KEY)
        assert not failed or int(failed) == 0


def test_late_joiner_exits_cleanly_when_job_drained():
    """A pod scaled up in the job's last seconds: peers completed and left,
    the queue is fully drained — the joiner must exit 0 ('nothing to do'),
    not time out as a failure waiting for a world that never assembles."""
    coord = InProcessCoordinator()
    finisher = coord.client("w-old")
    finisher.register()
    finisher.add_tasks(["s0", "s1"])
    assert finisher.acquire_task() and finisher.acquire_task()
    finisher.complete_task("s0"), finisher.complete_task("s1")
    finisher.leave()

    joiner = coord.client("w-new")
    with pytest.raises(SystemExit) as exc:
        derive_identity(ctx_with(2), joiner, timeout=10.0)
    assert exc.value.code == 0
    st = joiner.status()
    assert int(st["queued"]) == 0 and int(st["done"]) == 2
