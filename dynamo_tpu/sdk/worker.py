"""Worker process entry: host one service of a component graph.

`python -m dynamo_tpu.sdk.worker <entry_ident> --service-name S --worker-id N`
— the serve_dynamo.py equivalent (reference:
deploy/dynamo/sdk/cli/serve_dynamo.py:186-300): connect the distributed
runtime, instantiate the service class, resolve its depends() edges to live
clients, run @async_on_start hooks, then serve every @endpoint method on
`dyn://{namespace}.{service}.{endpoint}` until SIGTERM.
"""

from __future__ import annotations

import argparse
import asyncio
import logging
import os
import signal
from typing import Any, AsyncIterator

from dynamo_tpu.utils.logging import configure_logging

log = logging.getLogger("dynamo_tpu.sdk.worker")


class _BoundEngine:
    """AsyncEngine over a bound @endpoint method."""

    def __init__(self, fn):
        self._fn = fn

    async def generate(self, request) -> AsyncIterator[Any]:
        return await self._fn(request)


async def publish_worker_lease(drt, watcher_name: str, worker_id: int) -> None:
    """Register this worker's primary-lease id under the supervisor's
    well-known key (sdk/supervisor.worker_lease_key), ATTACHED to the
    lease itself so the key dies with the worker. The watcher reads it
    back at scale-down to revoke the lease before stopping the process
    (docs/control.md "Graceful drain")."""
    from dynamo_tpu.sdk.supervisor import worker_lease_key

    if drt.primary_lease is None:
        return
    await drt.hub.kv_put(
        worker_lease_key(watcher_name, worker_id),
        str(drt.primary_lease.lease_id).encode(),
        lease=drt.primary_lease,
    )


async def lease_gate(drt, stop_evt: asyncio.Event, poll_s: float = 0.5) -> None:
    """Drain trigger: poll primary-lease validity (the PrefillHandler
    gate pattern, llm/disagg) and set `stop_evt` when the lease is gone
    — the supervisor revoked it for a graceful scale-down, or the hub
    expired it. The worker then stops pulling, finishes in-flight work
    and exits 0."""
    while not stop_evt.is_set():
        await asyncio.sleep(poll_s)
        try:
            ok = await drt.primary_lease.is_valid()
        except Exception:  # noqa: BLE001 — a hub hiccup is not a revoke
            continue
        if not ok:
            log.info("primary lease revoked/expired; draining worker")
            stop_evt.set()
            return


# libtpu's process bounds for a process that owns `n` of a host's chips
# (x,y,z chips). TPU_VISIBLE_DEVICES alone is not enough when several
# processes share a host: each still claims the whole host's topology
# and the second one dies on libtpu's lockfile (seen on a four-chip v5e
# host, libtpu 0.0.34, PR 21); with the bounds each process brings up
# only its own chips.
_CHIP_BOUNDS = {1: "1,1,1", 2: "1,2,1", 4: "2,2,1", 8: "2,4,1"}


def _apply_chip_env(worker_id: int) -> None:
    """Slice this worker's disjoint chip range out of the watcher's
    allocation (reference: ResourceAllocator.assign_gpus setting
    CUDA_VISIBLE_DEVICES per worker, sdk cli/allocator.py:54-251)."""
    chips = os.environ.get("DYN_TPU_CHIPS")
    if not chips:
        return
    per = int(os.environ.get("DYN_TPU_CHIPS_PER_WORKER", "1"))
    ids = [c for c in chips.split(",") if c]
    mine = ids[worker_id * per : (worker_id + 1) * per]
    os.environ["TPU_VISIBLE_DEVICES"] = ",".join(mine)
    bounds = _CHIP_BOUNDS.get(len(mine))
    if bounds is None:
        log.warning(
            "no libtpu process bounds known for %d chips per worker; "
            "several such workers on one host may not start", len(mine),
        )
        return
    os.environ["TPU_CHIPS_PER_PROCESS_BOUNDS"] = bounds
    os.environ["TPU_PROCESS_BOUNDS"] = "1,1,1"


async def amain(entry_ident: str, service_name: str, worker_id: int) -> None:
    from dynamo_tpu.runtime.distributed import DistributedRuntime
    from dynamo_tpu.sdk.config import ServiceConfig
    from dynamo_tpu.sdk.service import collect_on_start
    from dynamo_tpu.sdk.supervisor import find_spec, load_entry

    entry_cls = load_entry(entry_ident)
    spec = find_spec(entry_cls, service_name)
    cfg = ServiceConfig.from_env().for_service(spec.name)

    # DYN_LEASE_TTL: how fast a hard-killed worker vanishes from
    # discovery (chaos/failover scenarios shrink it so recovery clocks
    # measure the CONTROLLER, not the lease horizon)
    kw = {}
    if os.environ.get("DYN_LEASE_TTL"):
        try:
            kw["lease_ttl"] = float(os.environ["DYN_LEASE_TTL"])
        except ValueError:
            # a typo'd knob must not crash-loop the worker under its
            # supervisor; the default TTL is always safe
            log.warning("ignoring malformed DYN_LEASE_TTL=%r",
                        os.environ["DYN_LEASE_TTL"])
    drt = await DistributedRuntime.from_settings(**kw)  # DYN_HUB_ADDR
    stop_evt = asyncio.Event()
    loop = asyncio.get_running_loop()
    for sig in (signal.SIGINT, signal.SIGTERM):
        loop.add_signal_handler(sig, stop_evt.set)

    # lease-revoke drain contract with the supervisor: publish the lease
    # id under the watcher's key and stop when the lease is revoked
    watcher_name = os.environ.get("DYN_WATCHER_NAME")
    gate_task = None
    if watcher_name:
        await publish_worker_lease(drt, watcher_name, worker_id)
        gate_task = asyncio.create_task(lease_gate(drt, stop_evt))

    instance = spec.cls.__new__(spec.cls)
    # runtime context available to __init__ and hooks (reference:
    # dynamo_context in serve_dynamo.py)
    instance.dynamo_context = {
        "runtime": drt,
        "service": spec.name,
        "namespace": spec.namespace,
        "worker_id": worker_id,
        "config": cfg,
    }
    instance.__init__()

    for dep in spec.dependencies.values():
        await dep.resolve(drt)
    for hook in collect_on_start(instance):
        result = hook()
        if asyncio.iscoroutine(result):
            await result

    comp = drt.namespace(spec.namespace).component(spec.name)
    # a service exposing `dynamo_stats_handler` rides its load/SLO
    # gauges on the endpoint's stats replies — the KvMetricsAggregator
    # scrapes them, which is how @service workers feed the planner's
    # attainment fold and the router's saturation view (the reference's
    # ForwardPassMetrics path; docs/control.md)
    stats = getattr(instance, "dynamo_stats_handler", None)
    served = []
    for ep_name in spec.endpoints:
        ep = comp.endpoint(ep_name)
        served.append(
            await ep.serve_engine(
                _BoundEngine(getattr(instance, ep_name)),
                stats_handler=stats,
            )
        )
        log.info("%s[%d]: serving %s", spec.name, worker_id, ep.subject)

    await stop_evt.wait()
    log.info("%s[%d]: draining", spec.name, worker_id)
    if gate_task is not None:
        gate_task.cancel()
    for s in served:
        await s.shutdown()
    await drt.shutdown()


def main() -> None:
    p = argparse.ArgumentParser(prog="dynamo_tpu.sdk.worker")
    p.add_argument("entry")
    p.add_argument("--service-name", required=True)
    p.add_argument("--worker-id", type=int, default=0)
    args = p.parse_args()
    configure_logging()
    _apply_chip_env(args.worker_id)
    asyncio.run(amain(args.entry, args.service_name, args.worker_id))


if __name__ == "__main__":
    main()
