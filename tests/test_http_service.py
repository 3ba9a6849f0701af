"""HTTP service tests: SSE round trips, aggregation, error paths, metrics.

Mirrors reference coverage in lib/llm/tests/http-service.rs (counting /
always-fail engines, full SSE round trip) using aiohttp's client.
"""

import contextlib
import json

import aiohttp

from dynamo_tpu.llm.backend import Backend
from dynamo_tpu.llm.engines import AlwaysFailEngine, EchoEngineCore, EchoEngineFull
from dynamo_tpu.llm.http.service import HttpService
from dynamo_tpu.llm.model_card import ModelDeploymentCard
from dynamo_tpu.llm.preprocessor import OpenAIPreprocessor
from dynamo_tpu.runtime.pipeline.engine import link

from .fixtures import tiny_model_dir


@contextlib.asynccontextmanager
async def http_service():
    svc = HttpService()
    card = ModelDeploymentCard.from_local_path(tiny_model_dir(), name="tiny")
    pipeline = link(OpenAIPreprocessor(card), Backend.from_card(card), EchoEngineCore())
    svc.manager.add_chat_model("tiny", pipeline)
    svc.manager.add_completion_model("tiny", pipeline)
    svc.manager.add_chat_model("echo", EchoEngineFull())
    svc.manager.add_chat_model("broken", AlwaysFailEngine())
    await svc.start("127.0.0.1", 0)
    async with aiohttp.ClientSession(f"http://127.0.0.1:{svc.port}") as session:
        try:
            yield svc, session
        finally:
            pass
    await svc.stop()


async def _read_sse(resp):
    """Parse an SSE body into (events, data_items, done_seen)."""
    events, items, done = [], [], False
    current_event = None
    async for raw_line in resp.content:
        line = raw_line.decode().rstrip("\n")
        if line.startswith("event: "):
            current_event = line[len("event: ") :]
        elif line.startswith("data: "):
            data = line[len("data: ") :]
            if data == "[DONE]":
                done = True
            elif current_event:
                events.append((current_event, json.loads(data)))
                current_event = None
            else:
                items.append(json.loads(data))
    return events, items, done


async def test_models_and_health():
    async with http_service() as (svc, session):
        r = await session.get("/v1/models")
        assert r.status == 200
        names = {m["id"] for m in (await r.json())["data"]}
        assert {"tiny", "echo", "broken"} <= names
        r = await session.get("/health")
        assert r.status == 200


async def test_chat_streaming_sse():
    async with http_service() as (svc, session):
        r = await session.post(
            "/v1/chat/completions",
            json={
                "model": "tiny",
                "messages": [{"role": "user", "content": "hello world"}],
                "stream": True,
                "dyn_ext": {"annotations": ["token_ids"]},
            },
        )
        assert r.status == 200
        assert r.headers["Content-Type"].startswith("text/event-stream")
        events, items, done = await _read_sse(r)
        assert done
        assert any(name == "token_ids" for name, _ in events)
        text = "".join(
            c["choices"][0]["delta"].get("content", "")
            for c in items
            if c.get("choices")
        )
        assert "hello world" in text
        finishes = [
            c["choices"][0].get("finish_reason") for c in items if c.get("choices")
        ]
        assert finishes[-1] is not None


async def test_chat_non_streaming():
    async with http_service() as (svc, session):
        r = await session.post(
            "/v1/chat/completions",
            json={
                "model": "tiny",
                "messages": [{"role": "user", "content": "pack my box"}],
            },
        )
        assert r.status == 200
        body = await r.json()
        assert body["object"] == "chat.completion"
        assert "pack my box" in body["choices"][0]["message"]["content"]
        assert body["usage"]["total_tokens"] > 0


async def test_completions_endpoint():
    async with http_service() as (svc, session):
        r = await session.post(
            "/v1/completions",
            json={"model": "tiny", "prompt": "five dozen liquor jugs"},
        )
        assert r.status == 200
        body = await r.json()
        assert body["object"] == "text_completion"
        assert "five dozen" in body["choices"][0]["text"]


async def test_completions_echo_and_n():
    """Legacy completions options: echo=True prefixes the prompt text;
    n=2 returns two indexed choices."""
    async with http_service() as (svc, session):
        r = await session.post(
            "/v1/completions",
            json={
                "model": "tiny",
                "prompt": "pack my box",
                "echo": True,
            },
        )
        assert r.status == 200
        body = await r.json()
        assert body["choices"][0]["text"].startswith("pack my box")

        r = await session.post(
            "/v1/completions",
            json={"model": "tiny", "prompt": "two choices", "n": 2},
        )
        assert r.status == 200
        body = await r.json()
        assert [c["index"] for c in body["choices"]] == [0, 1]
        assert all("two choices" in c["text"] for c in body["choices"])


async def test_error_paths():
    async with http_service() as (svc, session):
        r = await session.post(
            "/v1/chat/completions",
            json={"model": "nope", "messages": [{"role": "user", "content": "x"}]},
        )
        assert r.status == 404
        r = await session.post(
            "/v1/chat/completions", data=b"{not json", headers={"Content-Type": "application/json"}
        )
        assert r.status == 400
        r = await session.post("/v1/chat/completions", json={"model": "tiny"})
        assert r.status == 400  # missing messages
        r = await session.post(
            "/v1/chat/completions",
            json={"model": "broken", "messages": [{"role": "user", "content": "x"}]},
        )
        assert r.status == 502


def test_histogram_buckets_are_cumulative_once():
    """Regression: bucket counts must never exceed +Inf/_count."""
    from dynamo_tpu.llm.http.metrics import Histogram

    h = Histogram("t_seconds", "test", buckets=(0.1, 1.0, 10.0))
    h.observe(0.05)
    lines = list(h.render())
    counts = {
        line.split("le=")[1].split("}")[0].strip('"'): float(line.rsplit(" ", 1)[1])
        for line in lines
        if "_bucket" in line
    }
    assert counts == {"0.1": 1.0, "1.0": 1.0, "10.0": 1.0, "+Inf": 1.0}


async def test_content_parts_messages():
    """OpenAI content-part lists are flattened to text before templating."""
    async with http_service() as (svc, session):
        r = await session.post(
            "/v1/chat/completions",
            json={
                "model": "tiny",
                "messages": [
                    {
                        "role": "user",
                        "content": [{"type": "text", "text": "hello world"}],
                    }
                ],
            },
        )
        assert r.status == 200
        body = await r.json()
        assert "hello world" in body["choices"][0]["message"]["content"]
        assert "'type'" not in body["choices"][0]["message"]["content"]
        # unsupported part type → 400, not 502
        r = await session.post(
            "/v1/chat/completions",
            json={
                "model": "tiny",
                "messages": [
                    {"role": "user", "content": [{"type": "image_url", "image_url": {}}]}
                ],
            },
        )
        assert r.status == 400


async def test_metrics_exposed():
    async with http_service() as (svc, session):
        await session.post(
            "/v1/chat/completions",
            json={"model": "tiny", "messages": [{"role": "user", "content": "hi"}]},
        )
        r = await session.get("/metrics")
        text = await r.text()
        assert 'dynamo_tpu_http_service_requests_total{endpoint="chat",model="tiny",status="success"} 1' in text
        assert "dynamo_tpu_http_service_request_duration_seconds_bucket" in text


async def test_engine_metrics_render_through_extra():
    """ServiceMetrics.extra: one scrape covers service + engine (the
    run.py serving path appends an EngineMetrics per local engine)."""
    from dynamo_tpu.llm.http.metrics import EngineMetrics

    class StubEngine:
        def metrics(self):
            return {"request_active_slots": 3, "kv_total_blocks": 63}

    async with http_service() as (svc, session):
        svc.metrics.extra.append(EngineMetrics(StubEngine()))
        r = await session.get("/metrics")
        text = await r.text()
        assert "dynamo_tpu_engine_request_active_slots 3.0" in text
        assert "dynamo_tpu_engine_kv_total_blocks 63.0" in text
        # histograms render complete zero series before any traffic
        assert "dynamo_tpu_engine_ttft_seconds_count 0" in text
        assert 'dynamo_tpu_engine_itl_seconds_bucket{le="+Inf"} 0' in text


async def test_debug_trace_request_span():
    """/debug/trace returns Chrome trace-event JSON carrying the request
    span (x-request-id echoed end to end) for a completed completion."""
    from dynamo_tpu.utils import tracing

    tracing.enable()
    tracing.clear()
    try:
        async with http_service() as (svc, session):
            r = await session.post(
                "/v1/completions",
                json={"model": "tiny", "prompt": "hello world"},
                headers={"x-request-id": "trace-me-1"},
            )
            assert r.status == 200
            assert r.headers["X-Request-Id"] == "trace-me-1"
            # a request without the header gets a minted id echoed back
            r2 = await session.post(
                "/v1/completions",
                json={"model": "tiny", "prompt": "again", "stream": True},
            )
            assert r2.status == 200
            minted = r2.headers["X-Request-Id"]
            assert minted
            await _read_sse(r2)

            r = await session.get("/debug/trace")
            assert r.status == 200
            d = await r.json()
            evs = d["traceEvents"]
            ts = [e["ts"] for e in evs if e["ph"] != "M"]
            assert ts == sorted(ts)
            assert all(e["ph"] in ("X", "i", "M") for e in evs)
            spans = [
                e for e in evs
                if e["name"] == "http.request" and e["ph"] == "X"
            ]
            mine = [
                e for e in spans if e["args"].get("request_id") == "trace-me-1"
            ]
            assert mine and mine[0]["args"]["status"] == 200
            assert mine[0]["dur"] >= 0
            assert any(
                e["args"].get("request_id") == minted for e in spans
            )
            # the preprocessor span joined the same request id via the
            # handler's contextvar binding
            assert any(
                e["name"] == "fe.preprocess"
                and e["args"].get("request_id") == "trace-me-1"
                for e in evs
            )
    finally:
        tracing.disable()
        tracing.clear()


# ------------------------------------------- deadlines & typed errors
# (fault-tolerance spine, docs/robustness.md: x-request-timeout rides
# Context metadata; DeadlineExceeded -> 429 + Retry-After; PoolExhausted
# -> 503 + Retry-After)


async def test_request_timeout_header_invalid_is_400():
    async with http_service() as (svc, session):
        r = await session.post(
            "/v1/chat/completions",
            json={"model": "echo", "messages": [{"role": "user", "content": "x"}]},
            headers={"x-request-timeout": "soon"},
        )
        assert r.status == 400
        assert "x-request-timeout" in (await r.json())["error"]["message"]


async def test_request_timeout_zero_sheds_429_with_retry_after():
    async with http_service() as (svc, session):
        r = await session.post(
            "/v1/chat/completions",
            json={"model": "echo", "messages": [{"role": "user", "content": "x"}]},
            headers={"x-request-timeout": "0"},
        )
        assert r.status == 429
        assert r.headers.get("Retry-After") == "1"
        assert (await r.json())["error"]["type"] == "rate_limit_error"


async def test_request_timeout_header_rides_context_metadata():
    from dynamo_tpu.runtime.pipeline.context import Context

    seen = {}

    class CapturingEngine:
        async def generate(self, ctx: Context):
            seen.update(ctx.metadata)

            async def _gen():
                yield {"id": "x", "choices": [], "object": "chat.completion.chunk"}

            return _gen()

    svc = HttpService()
    svc.manager.add_chat_model("cap", CapturingEngine())
    await svc.start("127.0.0.1", 0)
    try:
        import aiohttp
        import time as _time

        async with aiohttp.ClientSession(f"http://127.0.0.1:{svc.port}") as s:
            t0 = _time.time()
            r = await s.post(
                "/v1/chat/completions",
                json={"model": "cap", "messages": [{"role": "user", "content": "x"}]},
                headers={"x-request-timeout": "12.5"},
            )
            assert r.status == 200
        assert seen.get("timeout_s") == 12.5
        assert abs(seen["deadline"] - (t0 + 12.5)) < 5.0
    finally:
        await svc.stop()


async def test_typed_engine_errors_map_to_429_and_503():
    from dynamo_tpu.llm.protocols.common import (
        DeadlineExceededError,
        PoolExhaustedError,
    )

    class ShedEngine:
        async def generate(self, ctx):
            raise DeadlineExceededError("budget spent", retry_after_s=2)

    class FullEngine:
        async def generate(self, ctx):
            raise PoolExhaustedError("no pages", retry_after_s=3)

    svc = HttpService()
    svc.manager.add_chat_model("shed", ShedEngine())
    svc.manager.add_chat_model("full", FullEngine())
    await svc.start("127.0.0.1", 0)
    try:
        import aiohttp

        async with aiohttp.ClientSession(f"http://127.0.0.1:{svc.port}") as s:
            body = {"messages": [{"role": "user", "content": "x"}]}
            r = await s.post(
                "/v1/chat/completions", json={"model": "shed", **body}
            )
            assert r.status == 429
            assert r.headers.get("Retry-After") == "2"
            r = await s.post(
                "/v1/chat/completions", json={"model": "full", **body}
            )
            assert r.status == 503
            assert r.headers.get("Retry-After") == "3"
            assert (await r.json())["error"]["type"] == "server_error"
    finally:
        await svc.stop()


async def test_nonstreaming_queue_timeout_converts_to_429():
    """A zero-token all-`timeout` aggregate (deadline died in the
    admission queue) becomes a REAL 429 on the non-streaming path."""

    class QueueTimeoutEngine:
        async def generate(self, ctx):
            async def _gen():
                yield {
                    "id": "x", "object": "chat.completion.chunk",
                    "choices": [{
                        "index": 0, "delta": {}, "finish_reason": "timeout",
                    }],
                }

            return _gen()

    svc = HttpService()
    svc.manager.add_chat_model("q", QueueTimeoutEngine())
    await svc.start("127.0.0.1", 0)
    try:
        import aiohttp

        async with aiohttp.ClientSession(f"http://127.0.0.1:{svc.port}") as s:
            r = await s.post(
                "/v1/chat/completions",
                json={"model": "q", "messages": [{"role": "user", "content": "x"}]},
            )
            assert r.status == 429
            assert r.headers.get("Retry-After") == "1"
    finally:
        await svc.stop()


async def test_global_health_counters_render_via_extra():
    from dynamo_tpu.utils import counters
    from dynamo_tpu.utils.counters import PromCounters

    counters.reset()
    try:
        async with http_service() as (svc, session):
            svc.metrics.extra.append(PromCounters())
            counters.inc("hub_reconnects_total")
            r = await session.get("/metrics")
            text = await r.text()
            assert "dynamo_tpu_hub_reconnects_total 1.0" in text
            # known counters render 0 before first increment
            assert "dynamo_tpu_lease_expired_total 0.0" in text
            assert "dynamo_tpu_breaker_open_total 0.0" in text
    finally:
        counters.reset()
