"""SDK e2e: `Supervisor` serves a 2-component graph as real processes
(reference behavior: `dynamo serve graphs.agg:Frontend`,
deploy/dynamo/sdk/cli/serving.py:307 serve_dynamo_graph)."""

from __future__ import annotations

import asyncio
import os
import signal

from dynamo_tpu.runtime.component import EndpointId
from dynamo_tpu.runtime.distributed import DistributedRuntime
from dynamo_tpu.sdk import ServiceConfig
from dynamo_tpu.sdk.service import discover_graph
from dynamo_tpu.sdk.supervisor import Supervisor, load_entry

GRAPH = os.path.join(os.path.dirname(__file__), "sdk_graph.py")
ENTRY = f"{GRAPH}:EchoFrontend"


def test_import_surface():
    # the package façade must import and re-export the serve machinery
    import dynamo_tpu.sdk as sdk

    for name in sdk.__all__:
        assert getattr(sdk, name) is not None


def test_graph_discovery():
    entry = load_entry(ENTRY)
    specs = discover_graph(entry)
    assert [s.name for s in specs] == ["EchoBackend", "EchoFrontend"]
    backend = specs[0]
    assert "generate" in backend.endpoints
    assert backend.endpoint_path("generate") == "dyn://sdktest.EchoBackend.generate"


async def _call(drt, path: str, payload: dict) -> list[dict]:
    eid = EndpointId.parse(path)
    ep = drt.namespace(eid.namespace).component(eid.component).endpoint(eid.name)
    client = await ep.client()
    await client.wait_for_instances(timeout=30.0)
    out = []
    async for item in await client.generate(payload):
        out.append(item)
    return out


async def test_serve_graph_e2e():
    entry = load_entry(ENTRY)
    cfg = ServiceConfig({"EchoBackend": {"prefix": "~"}})
    sup = Supervisor.for_graph(ENTRY, entry, config=cfg)
    # keep worker subprocesses on CPU jax
    for w in sup.watchers.values():
        w.env["JAX_PLATFORMS"] = "cpu"
    await sup.start()
    try:
        drt = await DistributedRuntime.from_settings(hub_addr=sup.hub_addr)
        try:
            # full path: client -> frontend process -> backend process
            out = await _call(
                drt, "dyn://sdktest.EchoFrontend.generate", {"text": "lazy dog"}
            )
            assert out == [{"word": "~LAZY"}, {"word": "~DOG"}]

            # crash recovery: kill -9 the backend; the watcher restarts it
            backend = sup.watchers["EchoBackend"]
            pid = next(iter(backend._procs.values())).pid
            os.kill(pid, signal.SIGKILL)
            await asyncio.sleep(0.2)
            for _ in range(100):
                if backend.alive_count() == 1:
                    break
                await asyncio.sleep(0.1)
            assert backend.alive_count() == 1

            # the restarted instance serves again (old instance must fall
            # out of discovery via lease expiry; retry through that window)
            deadline = asyncio.get_event_loop().time() + 30
            while True:
                try:
                    out = await _call(
                        drt, "dyn://sdktest.EchoBackend.generate", {"text": "again"}
                    )
                    assert out == [{"word": "~again"}]
                    break
                except Exception:
                    if asyncio.get_event_loop().time() > deadline:
                        raise
                    await asyncio.sleep(0.5)
        finally:
            await drt.shutdown()
    finally:
        await sup.stop()
    # graceful stop leaves nothing behind
    assert all(w.alive_count() == 0 for w in sup.watchers.values())


async def test_scale_up_down():
    entry = load_entry(ENTRY)
    sup = Supervisor.for_graph(ENTRY, entry)
    # only serve the backend for this test: scale primitive is per-watcher
    del sup.watchers["EchoFrontend"]
    for w in sup.watchers.values():
        w.env["JAX_PLATFORMS"] = "cpu"
    await sup.start()
    try:
        drt = await DistributedRuntime.from_settings(hub_addr=sup.hub_addr)
        try:
            eid = EndpointId.parse("dyn://sdktest.EchoBackend.generate")
            ep = (
                drt.namespace(eid.namespace)
                .component(eid.component)
                .endpoint(eid.name)
            )
            client = await ep.client()
            await client.wait_for_instances(timeout=30.0)

            await sup.scale("EchoBackend", 3)
            for _ in range(200):
                if len(client.instance_ids()) == 3:
                    break
                await asyncio.sleep(0.1)
            assert len(client.instance_ids()) == 3

            await sup.scale("EchoBackend", 1)
            for _ in range(200):
                if len(client.instance_ids()) == 1:
                    break
                await asyncio.sleep(0.1)
            assert len(client.instance_ids()) == 1
            assert sup.watchers["EchoBackend"].alive_count() == 1
        finally:
            await drt.shutdown()
    finally:
        await sup.stop()


def test_for_graph_honors_restart_policy_keys():
    """Spec-level restart policy (chaos deployments park crashed
    victims; crash-loopy services cap restarts) rides the service
    config into the Watcher."""
    entry = load_entry(ENTRY)
    cfg = ServiceConfig({
        "EchoBackend": {"restart_backoff_s": 120.0, "max_restarts": 1},
    })
    sup = Supervisor.for_graph(ENTRY, entry, config=cfg)
    w = sup.watchers["EchoBackend"]
    assert w.restart_backoff_s == 120.0
    assert w.max_restarts == 1
    # unconfigured services keep the defaults
    front = sup.watchers["EchoFrontend"]
    assert front.restart_backoff_s == 1.0
    assert front.max_restarts == 5


def test_worker_chip_env_sets_libtpu_process_bounds(monkeypatch):
    """Several one-chip workers on one host each need libtpu's process
    bounds beside TPU_VISIBLE_DEVICES (without them the second worker
    dies on libtpu's lockfile — seen on a four-chip v5e host)."""
    from dynamo_tpu.sdk import worker

    # a private copy: _apply_chip_env writes os.environ directly, and
    # nothing it sets may leak into the tests that follow in this process
    env = {k: v for k, v in os.environ.items()
           if not k.startswith(("TPU_", "DYN_TPU_"))}
    monkeypatch.setattr(os, "environ", env)
    worker._apply_chip_env(0)  # no allocation: nothing is touched
    assert "TPU_VISIBLE_DEVICES" not in os.environ
    env["DYN_TPU_CHIPS"] = "0,1,2,3"
    env["DYN_TPU_CHIPS_PER_WORKER"] = "1"
    worker._apply_chip_env(2)
    assert os.environ["TPU_VISIBLE_DEVICES"] == "2"
    assert os.environ["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"
    assert os.environ["TPU_PROCESS_BOUNDS"] == "1,1,1"
    env["DYN_TPU_CHIPS_PER_WORKER"] = "2"
    worker._apply_chip_env(1)
    assert os.environ["TPU_VISIBLE_DEVICES"] == "2,3"
    assert os.environ["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,2,1"
