"""Fleet observability plane (ISSUE 10): cross-process merged tracing,
device/KV telemetry gauges, SLO attainment windows, the per-request
prefix/offload ledger, and the Prometheus format checker.
"""

from __future__ import annotations

import asyncio
import time

import pytest

from dynamo_tpu.utils import counters, instance, tracing

from .helpers import hub_pair
from .test_engine import collect, greedy_request, make_engine
from .test_tracing import armed


def _non_meta(trace):
    return [e for e in trace["traceEvents"] if e["ph"] != "M"]


# ----------------------------------------------------- wire/ingest merge


def test_traceparent_roundtrip():
    tp = tracing.make_traceparent("req-with-dashes-42")
    rid, span = tracing.parse_traceparent(tp)
    assert rid == "req-with-dashes-42"
    assert span and len(span) == 16
    assert tracing.parse_traceparent("garbage") == (None, None)


def test_wire_ingest_merged_two_tracks():
    """Two-context merged-trace round trip: spans recorded under a
    'worker' process label ship via the wire form, a 'frontend' context
    ingests them, and ONE export renders both processes on distinct
    named tracks with the same request id and monotonic ts."""
    with armed():
        rid = "r-merge-1"
        # --- worker context: engine-ish spans
        tracing.set_process("worker-a")
        t0 = time.perf_counter()
        tracing.complete("prefill", t0, t0 + 0.001, track="engine.steps",
                         req=rid)
        tracing.instant("seq.first_token", req=rid)
        wire = tracing.wire_events(request_id=rid)
        assert wire["process"] == "worker-a"
        assert {w["name"] for w in wire["events"]} == {
            "prefill", "seq.first_token"
        }
        assert all("ts_unix_us" in w for w in wire["events"])

        # --- frontend context: clear local state, record the http span,
        # ingest the worker batch
        tracing.clear()
        tracing.set_process("frontend")
        t1 = time.perf_counter()
        tracing.complete("http.request", t1, t1 + 0.002, req=rid)
        n = tracing.ingest(wire["events"], process="worker-a")
        assert n == 2

        trace = tracing.export()
        evs = _non_meta(trace)
        # both processes present, distinct pids
        pids = {e["pid"] for e in evs}
        assert len(pids) == 2
        # consistent request id across processes
        assert all(e["args"]["request_id"] == rid for e in evs)
        # monotonic after the merge sort
        ts = [e["ts"] for e in evs]
        assert ts == sorted(ts)
        # process_name metadata names both sides; the worker's named
        # track survives the hop
        procs = {
            e["args"]["name"]
            for e in trace["traceEvents"]
            if e["ph"] == "M" and e["name"] == "process_name"
        }
        assert {"frontend", "worker-a"} <= procs
        tracks = {
            e["args"]["name"]
            for e in trace["traceEvents"]
            if e["ph"] == "M" and e["name"] == "thread_name"
        }
        assert "engine.steps" in tracks
        # request filter keeps the merged view
        filtered = _non_meta(tracing.export(request_id=rid))
        assert len(filtered) == len(evs)
        assert _non_meta(tracing.export(request_id="other")) == []
    tracing.set_process(None)


def test_foreign_registries_bounded():
    """Weeks of worker churn (a fresh process label per restart) must
    not grow the foreign pid/track registries without bound."""
    with armed():
        for i in range(tracing._FOREIGN_PIDS_MAX + 50):
            tracing.ingest(
                [{"name": "x", "ph": "i",
                  "ts_unix_us": time.time() * 1e6, "track": "t"}],
                process=f"worker-{i}",
            )
        tracing.export()
        assert len(tracing._foreign_pids) <= tracing._FOREIGN_PIDS_MAX
        assert len(tracing._foreign_tracks) <= tracing._TRACKS_MAX
        # evicted processes dropped their track entries too
        assert all(
            k[0] in tracing._foreign_pids for k in tracing._foreign_tracks
        )


def test_ingest_drops_malformed():
    with armed():
        n = tracing.ingest(
            [{"name": "x"}, 7, {"name": "ok", "ph": "i",
                                "ts_unix_us": time.time() * 1e6}],
            process="w",
        )
        assert n == 1


async def test_span_shipper_aggregator_over_hub():
    """Full round trip over a real hub: a SpanShipper sink forwards
    worker spans to the trace subject, a TraceAggregator ingests them,
    and the merged export shows the foreign process."""
    from dynamo_tpu.runtime.trace_plane import SpanShipper, TraceAggregator

    async with hub_pair() as (_, client):
        with armed():
            tracing.set_process("worker-hub")
            agg = await TraceAggregator(client).start()
            shipper = SpanShipper(client, flush_interval_s=0.05).start()
            rid = "r-hub-1"
            with tracing.span("engine.step", req=rid, track="engine.steps"):
                pass
            tracing.instant("seq.admit", req=rid)
            for _ in range(100):
                if agg.ingested >= 2:
                    break
                await asyncio.sleep(0.02)
            assert agg.ingested >= 2
            await shipper.close()
            await agg.close()
            trace = tracing.export(request_id=rid)
            evs = _non_meta(trace)
            # events exist locally (pid 0) AND as ingested foreign
            # copies (pid > 0, counter-assigned) under the shipped label
            pids = {e["pid"] for e in evs}
            assert 0 in pids and len(pids) == 2, pids
            assert max(pids) > 0
            procs = {
                e["args"]["name"]
                for e in trace["traceEvents"]
                if e["ph"] == "M" and e["name"] == "process_name"
            }
            assert "worker-hub" in procs
        tracing.set_process(None)


async def test_ingress_binds_traceparent():
    """The data-plane Ingress must bind the caller's request id for the
    handler task and record the rpc.recv hop."""
    from dynamo_tpu.runtime.component import Ingress, pack_payload
    from dynamo_tpu.runtime.pipeline.context import Context

    seen = {}

    class StubEngine:
        async def generate(self, ctx):
            seen["rid"] = tracing.current_request()

            async def _g():
                yield {"ok": 1}

            return _g()

    with armed():
        tp = tracing.make_traceparent("req-ingress")
        ctx = Context(
            pack_payload({"x": 1}), request_id="req-ingress",
            metadata={"traceparent": tp},
        )
        stream = await Ingress(StubEngine())(ctx)
        [_ async for _ in stream]
        assert seen["rid"] == "req-ingress"
        evs = _non_meta(tracing.export())
        recv = [e for e in evs if e["name"] == "rpc.recv"]
        assert recv and recv[0]["args"]["request_id"] == "req-ingress"
        _, span = tracing.parse_traceparent(tp)
        assert recv[0]["args"]["parent_span"] == span


# ------------------------------------------------------- telemetry gauges


async def test_engine_telemetry_gauges_cpu():
    """KV pool gauges, slot occupancy, the compile counters (with their
    split into real compiles and cache reads) and the preemption count
    must render on the CPU backend (HBM gauges are absent there —
    memory_stats() returns None)."""
    engine = make_engine()
    tokens, _, _ = await collect(engine, greedy_request([5, 6, 7], max_tokens=3))
    assert len(tokens) == 3
    m = engine.metrics()
    assert m["kv_pages_used"] >= 0
    assert m["kv_pages_free"] > 0
    assert m["kv_pages_peak_used"] >= 1  # the serve allocated pages
    assert 0.0 <= m["kv_fragmentation"] <= 1.0
    assert 0.0 <= m["slot_occupancy"] <= 1.0
    # compile listener: the serve jitted at least one step family
    assert m["compile_events"] >= 1
    assert m["compile_time_s"] > 0
    # the split of compile_events: nothing is served from a persistent
    # cache on the CPU backend, so every event is a real compile
    assert m["backend_compiles"] == m["compile_events"] - m["persistent_cache_hits"]
    assert m["cache_read_s"] >= 0
    assert m["preemptions_total"] == 0
    # dispatch-call walls are not device time and are no longer exported
    assert "step_device_s" not in m and "step_stall_s" not in m
    # pool accounting consistency: used + cached + free == usable pages
    assert (
        m["kv_pages_used"] + m["kv_pages_cached"] + m["kv_pages_free"]
        == m["kv_total_blocks"]
    )
    await engine.close()


async def test_compile_span_on_trace():
    with armed():
        engine = make_engine()
        await collect(engine, greedy_request([9, 8, 7, 6], max_tokens=2))
        evs = _non_meta(tracing.export())
        compiles = [e for e in evs if e["name"] == "engine.compile"]
        assert compiles, "no engine.compile spans recorded"
        assert all(e["ph"] == "X" and e["dur"] > 0 for e in compiles)
        await engine.close()


# -------------------------------------------------------------- SLO math


def test_slo_window_boundary_and_breaches():
    from dynamo_tpu.llm.http.metrics import SloTracker

    slo = SloTracker(
        {"default": {"ttft_s": 1.0}, "gold": {"ttft_s": 0.5}},
        window_s=10.0,
    )
    # zero-series at registration, idle attainment 1.0
    text = "\n".join(slo.render())
    assert 'slo_breaches_total{metric="ttft",tenant="default"} 0' in text
    assert 'slo_attainment{metric="ttft",tenant="default"} 1.0' in text

    # synthetic stamps stay in the monotonic domain: render() prunes
    # with the real clock, so offsets must be relative to it
    base = time.monotonic()
    # boundary: EXACTLY at target attains
    slo.observe({"tenant": "default", "ttft_s": 1.0}, now=base)
    assert slo.attained_fraction("default", "ttft", now=base) == 1.0
    # over target breaches
    slo.observe({"tenant": "default", "ttft_s": 1.0001}, now=base + 1)
    assert slo.attained_fraction("default", "ttft", now=base + 1) == 0.5
    # burn-rate counters are monotonic
    text = "\n".join(slo.render())
    assert 'slo_breaches_total{metric="ttft",tenant="default"} 1' in text
    assert 'slo_requests_total{metric="ttft",tenant="default"} 2' in text

    # unknown tenant rides the default target, aggregated under default
    slo.observe({"tenant": "mystery", "ttft_s": 5.0}, now=base + 2)
    assert slo.attained_fraction(
        "default", "ttft", now=base + 2
    ) == pytest.approx(1 / 3)
    # configured tenant keeps its own row and target (0.5s)
    slo.observe({"tenant": "gold", "ttft_s": 0.7}, now=base + 3)
    assert slo.attained_fraction("gold", "ttft", now=base + 3) == 0.0

    # rolling window: old samples age out -> idle window back to 1.0
    assert slo.attained_fraction("default", "ttft", now=base + 900) == 1.0


def test_slo_empty_spec_exempts_tenant():
    """An explicitly EMPTY tenant spec means exempt — it must not fall
    through to the default targets or mint undeclared series."""
    from dynamo_tpu.llm.http.metrics import SloTracker

    slo = SloTracker({"default": {"ttft_s": 1.0}, "internal": {}})
    base = time.monotonic()
    slo.observe({"tenant": "internal", "ttft_s": 99.0}, now=base)
    text = "\n".join(slo.render())
    assert 'tenant="internal"' not in text
    assert 'slo_requests_total{metric="ttft",tenant="default"} 0' in text


def test_slo_snapshot_rides_worker_stats():
    from dynamo_tpu.llm.http.metrics import SloTracker
    from dynamo_tpu.llm.kv_router.metrics_aggregator import (
        KvMetricsAggregator,
        ProcessedEndpoints,
    )
    from dynamo_tpu.llm.kv_router.protocols import ForwardPassMetrics
    from dynamo_tpu.llm.kv_router.publisher import KvMetricsPublisher

    slo = SloTracker({"default": {"ttft_s": 1.0}})
    base = time.monotonic()
    slo.observe({"ttft_s": 0.2}, now=base)
    slo.observe({"ttft_s": 3.0}, now=base + 0.5)

    class Eng:
        def metrics(self):
            return {"kv_active_blocks": 3}

    pub = KvMetricsPublisher.for_engine(Eng(), slo=slo)
    stats = pub.stats_handler()
    assert stats["slo_attainment"]["default/ttft"] == 0.5
    # survives the wire round trip (from_dict keeps the field, older
    # senders without it default to {})
    fpm = ForwardPassMetrics.from_dict(stats)
    assert fpm.slo_attainment == {"default/ttft": 0.5}
    assert ForwardPassMetrics.from_dict({}).slo_attainment == {}

    # fleet fold: aggregator reports mean/min/workers per key
    snap = ProcessedEndpoints(endpoints={
        1: ForwardPassMetrics(slo_attainment={"default/ttft": 0.5}),
        2: ForwardPassMetrics(slo_attainment={"default/ttft": 1.0}),
        3: ForwardPassMetrics(),  # no tracker: doesn't vote
    })
    agg = KvMetricsAggregator.__new__(KvMetricsAggregator)
    agg.current = snap
    fleet = agg.attainment()
    assert fleet["default/ttft"] == {
        "mean": 0.75, "min": 0.5, "workers": 2
    }


# ------------------------------------------------- prefix/offload ledger


async def test_finish_summary_carries_prefix_ledger():
    engine = make_engine()
    summaries = []
    engine.subscribe_requests(summaries.append)
    prompt = list(range(2, 2 + 24))  # 3 full pages at page_size=8
    await collect(engine, greedy_request(prompt, max_tokens=2))
    await collect(engine, greedy_request(prompt, max_tokens=2))
    assert len(summaries) == 2
    cold, warm = summaries
    assert cold["prefix"]["reused_blocks"] == 0
    assert warm["prefix"]["reused_blocks"] >= 2  # repeat hits the cache
    assert warm["tenant"] == "default"
    await engine.close()


async def test_offload_ledger_restored_under_pressure():
    """Forced pressure: HBM evicted between serves, host tier populated
    -> the repeat's ledger must show restored blocks (restored > 0) and
    the gate stats must agree."""
    engine = make_engine(
        num_pages=12, host_kv_pages=32, offload_batch_pages=8,
        max_batch_size=2, prefill_chunk=16,
    )
    summaries = []
    engine.subscribe_requests(summaries.append)
    prompt = list(range(2, 2 + 24))
    await collect(engine, greedy_request(prompt, max_tokens=4))
    for _ in range(100):
        if len(engine.host_pool) >= 3:
            break
        engine._maybe_start_offload()
        await asyncio.sleep(0.05)
    assert len(engine.host_pool) >= 3
    # evict the HBM prefix entirely
    for i in range(4):
        filler = list(range(100 + 24 * i, 100 + 24 * (i + 1)))
        await collect(engine, greedy_request(filler, max_tokens=2))
    engine.allocator.clear_cache()

    await collect(engine, greedy_request(prompt, max_tokens=4))
    ledger = summaries[-1]["prefix"]
    assert ledger["restored_blocks"] > 0, ledger
    assert engine.offload_gate_stats["restored"] > 0
    assert engine.metrics()["offload_restored"] > 0
    await engine.close()


async def test_declined_gate_reason_in_ledger():
    engine = make_engine(
        num_pages=12, host_kv_pages=32, offload_batch_pages=8,
        max_batch_size=2, prefill_chunk=16, max_model_len=96,
    )
    summaries = []
    engine.subscribe_requests(summaries.append)
    prompt = list(range(40, 72))
    await collect(engine, greedy_request(prompt, max_tokens=2))
    for _ in range(100):
        if len(engine.host_pool) >= 3:
            break
        engine._maybe_start_offload()
        await asyncio.sleep(0.05)
    engine.allocator.clear_cache()
    # losing economy: the gate must decline and say why
    engine._ema_restore_bps = 1e3
    engine._ema_prefill_tps = 1e6
    await collect(engine, greedy_request(prompt, max_tokens=2))
    ledger = summaries[-1]["prefix"]
    if ledger["declined_blocks"]:  # tier population is best-effort
        assert ledger["gate_reason"] == "restore_slower_than_recompute"
        assert ledger["restored_blocks"] == 0
    await engine.close()


# ---------------------------------------------- satellites: labels, prom


def test_counters_declare_zero_series():
    from dynamo_tpu.utils.counters import PromCounters

    counters.reset()
    try:
        counters.declare("my_new_total")
        text = "\n".join(PromCounters().render())
        assert "dynamo_tpu_my_new_total 0.0" in text
        assert "# TYPE dynamo_tpu_my_new_total counter" in text
        counters.inc("my_new_total", 2)
        text = "\n".join(PromCounters().render())
        assert "dynamo_tpu_my_new_total 2.0" in text
    finally:
        counters.reset()


def test_http_counter_gauge_declare():
    from dynamo_tpu.llm.http.metrics import Counter, Gauge

    c = Counter("x_total", "t")
    c.declare(model="m")
    lines = list(c.render())
    assert 'x_total{model="m"} 0.0' in lines
    c.inc(model="m")
    lines = list(c.render())
    assert 'x_total{model="m"} 1.0' in lines
    g = Gauge("y", "t")
    g.declare(a="1")
    assert 'y{a="1"} 0.0' in list(g.render())


def test_worker_id_label_and_jsonl():
    import json as _json
    import logging

    from dynamo_tpu.llm.http.metrics import (
        EngineMetrics,
        ServiceMetrics,
    )
    from dynamo_tpu.utils.logging import JsonlFormatter

    instance.set_worker_id("w-test-1")
    try:
        sm = ServiceMetrics()

        class Stub:
            def subscribe_requests(self, cb):
                pass

            def metrics(self):
                return {"request_active_slots": 1}

        sm.extra.append(EngineMetrics(Stub(), worker_id="w-test-1"))
        text = sm.render()
        assert 'dynamo_tpu_instance_info{worker_id="w-test-1"} 1' in text
        assert (
            'dynamo_tpu_engine_request_active_slots'
            '{worker_id="w-test-1"} 1.0' in text
        )
        rec = logging.LogRecord("t", logging.INFO, "f", 1, "hello", (), None)
        out = _json.loads(JsonlFormatter().format(rec))
        assert out["worker_id"] == "w-test-1"
    finally:
        instance.set_worker_id(None)


def test_check_prom_validator():
    import importlib.util
    import os

    spec = importlib.util.spec_from_file_location(
        "check_prom",
        os.path.join(os.path.dirname(__file__), "..", "scripts",
                     "check_prom.py"),
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)

    good = (
        "# TYPE a_total counter\na_total 0\n"
        "# TYPE h_seconds histogram\n"
        'h_seconds_bucket{le="1.0"} 0\nh_seconds_bucket{le="+Inf"} 0\n'
        "h_seconds_sum 0.0\nh_seconds_count 0\n"
    )
    assert mod.validate(good) == []
    # duplicate series
    assert mod.validate("# TYPE a counter\na 1\na 2\n")
    # duplicate TYPE line — even a consistent one — is what the real
    # Prometheus parser rejects
    assert mod.validate(
        "# TYPE a counter\na 1\n# TYPE a counter\n"
    )
    # sample without TYPE
    assert mod.validate("b_total 1\n")
    # declared family with no samples (zero-series rule)
    assert mod.validate("# TYPE c_total counter\n")
    # incomplete histogram
    assert mod.validate(
        "# TYPE h histogram\n" 'h_bucket{le="1.0"} 0\nh_count 0\n'
    )
    # the real exposition passes
    from dynamo_tpu.llm.http.metrics import ServiceMetrics
    from dynamo_tpu.utils.counters import PromCounters

    sm = ServiceMetrics()
    sm.extra.append(PromCounters())
    assert mod.validate(sm.render()) == []


def test_metrics_export_single_type_line_per_family():
    """The standalone exporter's per-worker loops must declare each
    family ONCE however many labeled series they emit (Prometheus
    rejects a scrape with a second TYPE line)."""
    from dynamo_tpu.llm.kv_router.metrics_aggregator import (
        KvMetricsAggregator,
        ProcessedEndpoints,
    )
    from dynamo_tpu.llm.kv_router.protocols import ForwardPassMetrics
    from dynamo_tpu.metrics_export import MetricsExporter

    exp = MetricsExporter.__new__(MetricsExporter)
    exp.hit_events = exp.hit_tokens = exp.request_tokens = 0
    agg = KvMetricsAggregator.__new__(KvMetricsAggregator)
    agg.current = ProcessedEndpoints(endpoints={
        1: ForwardPassMetrics(
            slo_attainment={"default/ttft": 1.0, "default/itl": 0.5}
        ),
        2: ForwardPassMetrics(slo_attainment={"default/ttft": 0.8}),
    })
    exp.aggregator = agg
    # control-plane fields __init__ would set (this test bypasses it)
    exp.prefill_queue_depth = 3
    exp.planner_status = {"desired": {"backend": 2}, "adjustments": 1}
    text = exp.render()
    types = [ln for ln in text.splitlines() if ln.startswith("# TYPE")]
    assert len(types) == len(set(types)), types
    assert 'slo_attainment{worker_id="2"' in text
    assert "slo_attainment_fleet_min" in text


async def test_debug_trace_request_filter():
    from aiohttp.test_utils import TestClient, TestServer

    from dynamo_tpu.llm.engines import EchoEngineFull
    from dynamo_tpu.llm.http.service import HttpService

    with armed():
        svc = HttpService()
        svc.manager.add_chat_model("echo", EchoEngineFull())
        client = TestClient(TestServer(svc.app))
        await client.start_server()
        try:
            resp = await client.post(
                "/v1/chat/completions",
                json={"model": "echo",
                      "messages": [{"role": "user", "content": "hi"}]},
                headers={"x-request-id": "rid-filter-1"},
            )
            assert resp.status == 200
            await client.post(
                "/v1/chat/completions",
                json={"model": "echo",
                      "messages": [{"role": "user", "content": "yo"}]},
                headers={"x-request-id": "rid-filter-2"},
            )
            trace = await (await client.get(
                "/debug/trace", params={"request_id": "rid-filter-1"}
            )).json()
            evs = [e for e in trace["traceEvents"] if e["ph"] != "M"]
            assert evs, "filtered trace empty"
            assert all(
                e["args"].get("request_id") == "rid-filter-1" for e in evs
            )
        finally:
            await client.close()
