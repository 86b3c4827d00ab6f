"""Multi-host JAX runtime bring-up from the coordinator protocol.

The reference's trainers learn their distributed identity from K8s-API
polling — rank = index of own pod in the sorted name list
(`docker/k8s_tools.py:127-151`), pserver endpoints from per-pod IPs
(`:108-124`) — and hand it to Paddle via `PADDLE_INIT_*` env vars
(`pkg/jobparser.go:263-311`). The TPU equivalent hands the same facts to
``jax.distributed.initialize``, which wires every host's chips into one
global mesh (ICI in-slice, DCN across hosts):

- **process_id** — the coordinator-leased dense rank (cannot collide or
  reuse mid-epoch, unlike the sorted-name trick).
- **num_processes** — the controller-stamped parallelism (`EDL_NUM_TRAINERS`).
- **coordinator_address** — rank 0 publishes ``host:port`` in the
  coordinator KV (the etcd-role subset); peers block on the key.

``jax.distributed`` world size is fixed at init — that is WHY elastic
rescale is checkpoint-restore. Single-host jobs (`runtime.elastic`,
`ElasticWorker`) rescale in-process: the device planner re-slices local
devices. Multi-host jobs run `runtime.multihost.MultiHostWorker`: on an epoch
change every process of the gang exits with ``RESCALE_EXIT_CODE``; the pod
launcher (`edl_tpu.launcher.launch.start_trainer`) relaunches the entry,
which calls ``distributed_init`` again and comes up at the new world size,
restoring from the last collective checkpoint.

Bring-up protocol (per process):

1. wait until live membership reaches the expected world size (the
   controller publishes rescale targets under ``edl/expected_world``;
   falls back to ``EDL_NUM_TRAINERS``),
2. rendezvous: settle on a common (epoch, rank) — re-registering while a
   stale member's lease still holds a rank ≥ world,
3. rank 0 publishes ``host:port`` under an epoch-scoped KV key (stale
   addresses from previous epochs can never be read back), peers block on
   that exact key.
"""

from __future__ import annotations

import logging
import socket
import time
from dataclasses import dataclass
from typing import Optional

from edl_tpu.coordinator import CoordinatorError

log = logging.getLogger("edl_tpu.runtime.distributed")

#: KV key prefix rank 0 publishes the jax.distributed endpoint under; the
#: membership epoch is appended so peers never read a stale address.
JAX_COORD_KEY = "edl/jax_coordinator_address"
#: KV key the control plane sets to the target world size on rescale.
EXPECTED_WORLD_KEY = "edl/expected_world"
#: offset from the EDL coordinator port for jax.distributed's own service.
JAX_COORD_PORT_OFFSET = 1


@dataclass(frozen=True)
class DistributedIdentity:
    """What `jax.distributed.initialize` needs, and where each field came from."""

    process_id: int
    num_processes: int
    coordinator_address: str

    def initialize_kwargs(self) -> dict:
        return {
            "coordinator_address": self.coordinator_address,
            "num_processes": self.num_processes,
            "process_id": self.process_id,
        }


def local_host_ip() -> str:
    """This host's routable IP (the address peers dial rank 0 on)."""
    s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    try:
        # No packets are sent; connect() on UDP just resolves the route.
        s.connect(("8.8.8.8", 80))
        return s.getsockname()[0]
    except OSError:
        return "127.0.0.1"
    finally:
        s.close()


def expected_world(ctx, client) -> int:
    """Target world size: the control plane's rescale target if published
    (`EXPECTED_WORLD_KEY`), else the pod-creation-time `EDL_NUM_TRAINERS`
    (which goes stale across rescales — restarted entries must prefer KV)."""
    published = client.kv_get(EXPECTED_WORLD_KEY)
    if published:
        return max(1, int(published))
    return max(1, int(ctx.num_trainers))


def derive_identity(
    ctx,
    client,
    timeout: float = 300.0,
    jax_port: Optional[int] = None,
) -> DistributedIdentity:
    """Compute (process_id, num_processes, coordinator_address) from the env
    protocol (`LaunchContext`) + a coordinator client.

    Waits for full membership, settles (epoch, rank) via the rendezvous
    sync, then exchanges rank 0's address through an epoch-scoped KV key.
    A restarted worker whose previous incarnation's lease has not yet
    expired can transiently draw rank >= world; it re-registers until the
    stale entry ages out and ranks re-pack.
    """
    world = expected_world(ctx, client)
    port = jax_port if jax_port is not None else ctx.port + JAX_COORD_PORT_OFFSET
    deadline = time.monotonic() + timeout

    # First register of this incarnation: takeover requeues any leases a
    # dead same-name predecessor still holds; the bring-up refreshes below
    # are plain (this process may acquire nothing until training starts,
    # but mid-loop refreshes must never forfeit anything either way).
    info = client.register(takeover=True)
    last_drain_check = 0.0
    while True:
        if time.monotonic() > deadline:
            raise TimeoutError(
                f"distributed bring-up did not settle within {timeout}s: "
                f"members={len(client.members())}/{world} rank={info.get('rank')}"
            )
        if len(client.members()) < world:
            # Late join against a FINISHED job: if the shard queue is fully
            # drained (done work exists, nothing queued or leased) and the
            # missing peers are gone because they completed, the expected
            # world will never assemble — a pod scaled up in the job's last
            # seconds must exit cleanly, not time out as a failure.
            # Rate-limited: the condition can only become true once, and a
            # large slowly-assembling job must not multiply coordinator
            # load during exactly its busiest window.
            now = time.monotonic()
            st = {}
            if now - last_drain_check >= 2.0:
                last_drain_check = now
                try:
                    st = client.status()
                except CoordinatorError:
                    # A timed-out probe during the coordinator's busiest
                    # window is "not drained", not a bring-up failure —
                    # the loop keeps registering and retrying.
                    st = {}
            if (st
                    and int(st.get("queued", 0)) == 0
                    and int(st.get("leased", 0)) == 0
                    and int(st.get("done", 0)) > 0):
                log.info(
                    "job already drained (done=%s) while waiting for "
                    "world=%d (members=%d); exiting with nothing to do",
                    st.get("done"), world, len(client.members()),
                )
                try:
                    client.leave()
                finally:
                    raise SystemExit(0)
            time.sleep(0.2)
            info = client.register()  # refresh; also re-leases our entry
            continue
        rank, epoch = int(info["rank"]), int(info["epoch"])
        if rank >= world:
            # A stale member still holds a low rank; wait for its lease to
            # expire, after which ranks re-pack densely.
            time.sleep(0.5)
            info = client.register()
            continue
        reply = client.sync(
            epoch, timeout=min(30.0, max(1.0, deadline - time.monotonic()))
        )
        if reply.get("ok") and int(reply.get("world", 0)) == world:
            break
        # resync (epoch moved) or timeout: refresh identity and retry.
        info = client.register()

    key = f"{JAX_COORD_KEY}/{epoch}"
    if rank == 0:
        address = f"{local_host_ip()}:{port}"
        client.kv_put(key, address)
        return DistributedIdentity(rank, world, address)
    while time.monotonic() < deadline:
        address = client.kv_get(key)
        if address:
            return DistributedIdentity(rank, world, address)
        time.sleep(0.2)
    raise TimeoutError(f"rank {rank}: rank 0 never published {key} within {timeout}s")


def distributed_init(
    ctx,
    client=None,
    timeout: float = 300.0,
    jax_port: Optional[int] = None,
) -> Optional[DistributedIdentity]:
    """Initialize the multi-host JAX runtime; no-op for single-process jobs.

    Call once per process, before any jax computation, from the trainer
    entrypoint (after `wait_coordinator`). Returns the identity used, or
    None when the job is single-process (num_trainers <= 1 or no client) —
    local runs and tests skip the global runtime entirely.
    """
    if client is None or expected_world(ctx, client) <= 1:
        return None
    ident = derive_identity(ctx, client, timeout=timeout, jax_port=jax_port)
    import jax

    jax.distributed.initialize(**ident.initialize_kwargs())
    log.info(
        "jax.distributed up: process %d/%d via %s",
        ident.process_id, ident.num_processes, ident.coordinator_address,
    )
    return ident
