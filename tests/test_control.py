"""Fleet control plane tests (docs/control.md): frontend admission
ladder, tenant-priority engine scheduling, disagg deadline clamp, and
the k8s controller's planner-status mirror."""

import asyncio
import contextlib
import json

import aiohttp

from dynamo_tpu.engine.scheduler import (
    pick_admission_index,
    pick_preemption_victim,
)
from dynamo_tpu.llm.engines import EchoEngineFull
from dynamo_tpu.llm.http.admission import (
    AdmissionConfig,
    AdmissionController,
    priorities_from_targets,
)
from dynamo_tpu.llm.http.service import HttpService

from .helpers import hub_server

# -------------------------------------------------------------- admission


def make_controller(queue=0.0, attain=None, **cfg_kw):
    sig = {"queue": queue, "attain": attain}
    cfg = AdmissionConfig(eval_interval_s=0.0, **cfg_kw)
    ctl = AdmissionController(
        priorities={"interactive": 10, "batch": 0, "default": 0},
        cfg=cfg,
        queue_depth_fn=lambda: sig["queue"],
        attainment_fn=lambda: sig["attain"],
    )
    return ctl, sig


def test_admission_ok_admits_everyone():
    ctl, _ = make_controller(queue=100.0, attain=None)  # no SLO data
    assert ctl.check("batch") is None
    ctl2, _ = make_controller(queue=0.0, attain=0.5)  # burn but no queue
    assert ctl2.check("batch") is None


def test_admission_overload_sheds_lowest_priority_with_429():
    ctl, _ = make_controller(queue=10.0, attain=0.5)
    shed = ctl.check("batch")
    assert shed is not None and shed.status == 429
    assert shed.retry_after_s >= 1
    # the configured interactive class rides through
    assert ctl.check("interactive") is None


def test_admission_critical_sheds_mid_priority_with_503():
    ctl, _ = make_controller(queue=20.0, attain=0.5)  # > 2x watermark
    shed = ctl.check("batch")
    assert shed is not None and shed.status == 503
    # the TOP configured class is never shed by this gate
    assert ctl.check("interactive") is None


def test_admission_recovers_when_signals_heal():
    ctl, sig = make_controller(queue=10.0, attain=0.5)
    assert ctl.check("batch") is not None
    sig["attain"] = 1.0
    assert ctl.check("batch") is None


def test_admission_without_priority_classes_is_inert():
    """No configured priority classes = nothing to discriminate by: the
    gate must admit everyone (shedding 100% of uniform-class traffic
    would deliver zero goodput), honoring check()'s top-class promise."""
    cfg = AdmissionConfig(eval_interval_s=0.0)
    ctl = AdmissionController(
        priorities={}, cfg=cfg,
        queue_depth_fn=lambda: 100.0, attainment_fn=lambda: 0.1,
    )
    assert ctl.check("anyone") is None


def test_admission_shed_counter_bounds_tenant_cardinality():
    """The x-tenant-id header is attacker-controlled: unconfigured
    tenants must fold into the "default" counter row (the SloTracker
    rule), not mint one Prometheus series per unique header."""
    ctl, _ = make_controller(queue=10.0, attain=0.5)
    for i in range(20):
        assert ctl.check(f"rando-{i}") is not None
    rows = {k for k in ctl.shed_total._values}
    assert rows == {(("level", "overload"), ("tenant", "default"))}, rows


def test_admission_broken_signal_fails_open():
    cfg = AdmissionConfig(eval_interval_s=0.0)

    def boom():
        raise RuntimeError("metrics backend down")

    ctl = AdmissionController(
        priorities={}, cfg=cfg, queue_depth_fn=boom, attainment_fn=boom
    )
    assert ctl.check("anyone") is None


def test_priorities_from_targets():
    targets = {
        "interactive": {"ttft_s": 0.5, "priority": 10},
        "batch": {"ttft_s": 30.0},
        "weird": {"priority": "nope"},
    }
    assert priorities_from_targets(targets) == {
        "interactive": 10, "batch": 0, "weird": 0,
    }


def test_priority_of_falls_through_to_default():
    ctl, _ = make_controller()
    ctl.priorities["default"] = 3
    assert ctl.priority_of("interactive") == 10
    assert ctl.priority_of("never-seen") == 3


@contextlib.asynccontextmanager
async def admission_service(ctl):
    svc = HttpService(admission=ctl)
    svc.manager.add_chat_model("echo", EchoEngineFull())
    await svc.start("127.0.0.1", 0)
    async with aiohttp.ClientSession(f"http://127.0.0.1:{svc.port}") as s:
        yield svc, s
    await svc.stop()


async def test_http_admission_gate_sheds_and_stamps_priority():
    """End to end through the HTTP frontend: under overload the batch
    tenant gets the typed 429 + Retry-After BEFORE any engine work, the
    interactive tenant is served with its priority class stamped into
    Context metadata, and the shed counter rides /metrics."""
    ctl, sig = make_controller(queue=10.0, attain=0.5)
    seen = {}

    async def spy_generate(ctx):
        seen["metadata"] = dict(ctx.metadata)

        async def s():
            yield {
                "id": "x", "object": "chat.completion.chunk", "model": "echo",
                "choices": [{"index": 0, "delta": {"content": "hi"},
                             "finish_reason": "stop"}],
            }

        return s()

    async with admission_service(ctl) as (svc, session):
        engine = svc.manager.get_chat("echo")
        engine.generate = spy_generate
        body = {"model": "echo", "messages": [{"role": "user", "content": "x"}]}
        r = await session.post(
            "/v1/chat/completions", json=body,
            headers={"x-tenant-id": "batch"},
        )
        assert r.status == 429
        assert r.headers.get("Retry-After") == "1"
        assert "metadata" not in seen  # shed BEFORE the engine
        r2 = await session.post(
            "/v1/chat/completions", json=body,
            headers={"x-tenant-id": "interactive"},
        )
        assert r2.status == 200
        assert seen["metadata"]["tenant"] == "interactive"
        assert seen["metadata"]["priority"] == 10
        scrape = await (await session.get("/metrics")).text()
        assert "admission_shed_total" in scrape
        assert 'tenant="batch"' in scrape
        # idle gate: once signals heal, everything admits again
        sig["attain"] = 1.0
        r3 = await session.post(
            "/v1/chat/completions", json=body,
            headers={"x-tenant-id": "batch"},
        )
        assert r3.status == 200


# ------------------------------------------------- engine priority policy


class _FakeSeq:
    def __init__(self, seq_id, priority=0):
        self.seq_id = seq_id
        self.priority = priority


def test_pick_admission_index_fifo_within_class():
    waiting = [_FakeSeq(1, 0), _FakeSeq(2, 0), _FakeSeq(3, 0)]
    assert pick_admission_index(waiting) == 0  # uniform = pure FIFO
    waiting = [_FakeSeq(1, 0), _FakeSeq(2, 5), _FakeSeq(3, 5)]
    assert pick_admission_index(waiting) == 1  # highest class, FIFO inside


def test_pick_preemption_victim_lowest_priority_most_recent():
    seqs = [_FakeSeq(1, 0), _FakeSeq(2, 0), _FakeSeq(3, 0)]
    assert pick_preemption_victim(seqs).seq_id == 3  # uniform = recency
    seqs = [_FakeSeq(1, 0), _FakeSeq(2, 0), _FakeSeq(3, 10)]
    # the newest seq is interactive: the newest BATCH one yields instead
    assert pick_preemption_victim(seqs).seq_id == 2


def _engine(**kw):
    from dynamo_tpu.engine import EngineConfig, JaxEngine
    from dynamo_tpu.models import config as cfgmod

    defaults = dict(
        model=cfgmod.get_config("tiny"),
        dtype="float32",
        page_size=8,
        num_pages=64,
        max_batch_size=4,
        max_model_len=128,
        prefill_chunk=32,
        seed=0,
    )
    defaults.update(kw)
    return JaxEngine(EngineConfig(**defaults))


def _pre(prompt, max_tokens=8):
    from dynamo_tpu.llm.protocols.common import (
        PreprocessedRequest,
        SamplingOptions,
        StopConditions,
    )

    return PreprocessedRequest(
        token_ids=list(prompt),
        stop_conditions=StopConditions(max_tokens=max_tokens),
        sampling_options=SamplingOptions(greedy=True),
    )


async def _collect(engine, pre, priority=None):
    from dynamo_tpu.runtime.pipeline.context import Context

    ctx = Context(pre.to_dict())
    if priority is not None:
        ctx.metadata["priority"] = priority
    frames = [f async for f in await engine.generate(ctx)]
    return [t for f in frames for t in f.get("token_ids") or []]


async def test_priority_admission_jumps_queue():
    """One slot, three queued requests: the high-priority one admits
    before the earlier-submitted batch ones (FIFO broken exactly where
    the priority class says so)."""
    engine = _engine(max_batch_size=1)
    try:
        hold_t = asyncio.create_task(_collect(engine, _pre([5, 6, 7], 6)))
        await asyncio.sleep(0.2)  # occupy the single slot
        order: list[str] = []

        async def tagged(tag, prompt, priority):
            toks = await _collect(engine, _pre(prompt, 3), priority)
            order.append(tag)
            return toks

        low_t = asyncio.create_task(tagged("low", [9, 10, 11], 0))
        await asyncio.sleep(0.05)  # low is queued first
        hi_t = asyncio.create_task(tagged("hi", [12, 13, 14], 10))
        await asyncio.gather(hold_t, low_t, hi_t)
        assert order == ["hi", "low"], order
    finally:
        await engine.close()


async def test_one_priority_class_is_fifo_and_preempts_most_recent():
    """Requests of ONE priority class (unset, or every request the same):
    admitted in arrival order, and when the pages run out the sequence
    admitted last gives way first."""
    engine = _engine(max_batch_size=1)
    try:
        hold_t = asyncio.create_task(_collect(engine, _pre([5, 6, 7], 6)))
        await asyncio.sleep(0.2)  # occupy the single slot
        order: list[int] = []

        async def tagged(i, priority):
            await _collect(engine, _pre([9 + i, 10, 11], 3), priority)
            order.append(i)

        tasks = []
        for i in range(3):
            tasks.append(asyncio.create_task(tagged(i, 5)))
            await asyncio.sleep(0.05)  # queued in this order
        await asyncio.gather(hold_t, *tasks)
        assert order == [0, 1, 2], order
    finally:
        await engine.close()

    engine = _engine(num_pages=30, decode_steps=4)
    victims: list[tuple[int, int]] = []
    preempt = engine._preempt

    def spy(seq):
        live = [s.seq_id for s in engine.slots if s is not None]
        victims.append((seq.seq_id, max(live)))
        preempt(seq)

    engine._preempt = spy
    try:
        got = await asyncio.gather(*(
            _collect(engine, _pre(range(3 + i, 27 + 4 * i), 60))
            for i in range(4)
        ))
        assert all(len(g) == 60 for g in got)
        assert victims and all(v == newest for v, newest in victims), victims
        assert engine.kv_ledger.audit() == []
    finally:
        await engine.close()


# ------------------------------------------------- disagg deadline clamp


async def test_disagg_remote_wait_sheds_at_deadline():
    """_generate_remote must clamp the remote-KV wait to the request
    deadline and shed with DeadlineExceededError instead of starting a
    doomed local prefill (ISSUE 11 satellite)."""
    import time

    import pytest

    from dynamo_tpu.llm.disagg import DisaggDecodeWorker, DisaggRouter
    from dynamo_tpu.llm.protocols.common import DeadlineExceededError
    from dynamo_tpu.runtime.distributed import DistributedRuntime
    from dynamo_tpu.runtime.pipeline.context import Context

    async with hub_server() as server:
        drt = await DistributedRuntime.from_settings(
            hub_addr=f"127.0.0.1:{server.port}"
        )
        try:
            local_calls = []

            class _NeverEngine:
                page_size = 8

                class allocator:
                    @staticmethod
                    def peek_prefix_tokens(tokens):
                        return 0

                async def generate(self, ctx, _blocks=None):
                    local_calls.append(ctx)

                    async def s():
                        yield {}

                    return s()

            await drt.ensure_data_plane()
            worker = DisaggDecodeWorker(
                drt, _NeverEngine(), "ctrl", "backend", router=DisaggRouter()
            )
            pre = _pre(list(range(32)), 4)
            ctx = Context(pre.to_dict())
            ctx.metadata["deadline"] = time.time() + 0.3  # tight budget
            t0 = time.monotonic()
            with pytest.raises(DeadlineExceededError):
                await worker._generate_remote(ctx, pre)
            assert time.monotonic() - t0 < 5.0  # clamped, not 120 s
            assert not local_calls  # no doomed local prefill
            assert worker.stats()["remote_timeouts"] == 1
            # an ALREADY-expired deadline sheds before even queueing
            ctx2 = Context(pre.to_dict())
            ctx2.metadata["deadline"] = time.time() - 1.0
            with pytest.raises(DeadlineExceededError):
                await worker._generate_remote(ctx2, pre)
        finally:
            await drt.shutdown()


# ------------------------- admission signals on non-kv ingress modes


async def test_non_kv_ingress_admission_gets_fleet_signals():
    """round_robin/random ingress previously ran the admission gate
    BLIND (no aggregator = no signals = always admit). With
    collect_stats the ModelWatcher starts a standalone stats aggregator
    per service, so queue depth + worst attainment reach the gate the
    same way the kv mode's router aggregator feeds it."""
    from dynamo_tpu.llm.engines import EchoEngineCore
    from dynamo_tpu.llm.http.discovery import ModelWatcher, register_llm
    from dynamo_tpu.llm.model_card import ModelDeploymentCard
    from dynamo_tpu.run import _bind_ingress_admission
    from dynamo_tpu.runtime.distributed import DistributedRuntime

    from .fixtures import tiny_model_dir

    async with hub_server() as server:
        hub_addr = f"127.0.0.1:{server.port}"
        worker = await DistributedRuntime.from_settings(hub_addr=hub_addr)
        frontend = await DistributedRuntime.from_settings(hub_addr=hub_addr)
        svc = HttpService()
        watcher = ModelWatcher(
            frontend, svc.manager, router_mode="round_robin",
            collect_stats=True,
        )
        try:
            # worker stats reply: a burning, deeply-queued snapshot
            def stats_handler():
                return {
                    "num_requests_waiting": 40,
                    "request_active_slots": 4,
                    "slo_attainment": {"default/ttft": 0.5},
                }

            card = ModelDeploymentCard.from_local_path(
                tiny_model_dir(), name="tiny-echo"
            )
            await register_llm(
                worker, EchoEngineCore(), card,
                "dyn://demo.backend.generate", stats_handler=stats_handler,
            )
            await watcher.start()
            for _ in range(50):
                if svc.manager.get_chat("tiny-echo"):
                    break
                await asyncio.sleep(0.1)
            assert "tiny-echo" in [
                m for m in svc.manager.list_models()
            ]
            assert watcher.stats_aggregators, "no stats aggregator started"

            ctl = AdmissionController(
                priorities={"interactive": 10, "batch": 0, "default": 0},
                cfg=AdmissionConfig(
                    eval_interval_s=0.0, queue_high_watermark=8.0
                ),
            )
            _bind_ingress_admission(ctl, watcher)
            # the aggregator scraped at start(); signals must reach the
            # gate and trip the ladder (queue 40 > 2x8 => critical)
            for _ in range(50):
                if ctl.check("batch") is not None:
                    break
                await asyncio.sleep(0.1)
            shed = ctl.check("batch")
            assert shed is not None and shed.status == 503
            assert ctl.check("interactive") is None
        finally:
            await watcher.stop()
            await worker.shutdown()
            await frontend.shutdown()


# ------------------------------------------------- k8s planner mirror


async def test_k8s_controller_mirrors_planner_status():
    """CrdController watches the planner's hub status document and
    patches CR status with the desired-replica block (the operator path
    shows the same truth the planner actuated)."""
    from dynamo_tpu.llm.planner import planner_status_key
    from dynamo_tpu.runtime.hub.client import HubClient
    from dynamo_tpu.sdk.k8s_controller import CrdController, K8sApi
    from dynamo_tpu.sdk.operator import GRAPH_PREFIX

    patches = []

    class _FakeApi(K8sApi):
        def __init__(self):
            super().__init__("http://unused")

        async def patch_status(self, namespace, name, status):
            patches.append((namespace, name, status))

        async def close(self):
            pass

    async with hub_server() as server:
        hub_addr = f"127.0.0.1:{server.port}"
        ctl = CrdController(_FakeApi(), hub_addr)
        ctl._hub = await HubClient.connect(hub_addr)
        try:
            # a reconciled CR the mirror can patch
            ctl._applied[f"{GRAPH_PREFIX}demo.graph1"] = {"entry": "m:C"}
            mirror = asyncio.create_task(ctl._mirror_planner())
            await asyncio.sleep(0.1)
            status = {
                "namespace": "dynamo",
                "desired": {"backend": 3, "prefill": 1},
                "attainment": {"min": 0.97, "mean": 0.99, "target": 0.99},
                "last_decision": "burn",
                "adjustments": 7,
            }
            await ctl._hub.kv_put(
                planner_status_key("dynamo"), json.dumps(status).encode()
            )
            for _ in range(50):
                if patches:
                    break
                await asyncio.sleep(0.1)
            assert patches, "no CR status patch arrived"
            ns, name, st = patches[-1]
            assert (ns, name) == ("demo", "graph1")
            # keyed by the planner's dynamo namespace so multi-namespace
            # planners merge-patch their own subkey
            block = st["planner"]["dynamo"]
            assert block["desiredReplicas"] == {"backend": 3, "prefill": 1}
            assert block["lastDecision"] == "burn"
            mirror.cancel()
            with contextlib.suppress(asyncio.CancelledError):
                await mirror
            if ctl._planner_watch is not None:
                await ctl._planner_watch.cancel()
        finally:
            await ctl._hub.close()
