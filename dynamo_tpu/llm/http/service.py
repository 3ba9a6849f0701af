"""OpenAI-compatible HTTP service.

Equivalent of the reference's axum HttpService (reference:
lib/llm/src/http/service/service_v2.rs:25-130, openai.rs:133-559):

- ``POST /v1/chat/completions`` / ``POST /v1/completions`` — streaming (SSE)
  and non-streaming; client disconnect kills the request context so engines
  stop wasting compute (openai.rs:433 monitor_for_disconnects);
- ``GET /v1/models`` — model listing;
- ``GET /metrics`` — Prometheus text;
- ``GET /health`` / ``GET /live``.

`ModelManager` (reference: lib/llm/src/http/service.rs:59-130) maps model
name → engine per flavor (chat/completion). Engines here are full pipelines:
for discovered backend workers that's preprocessor → backend → push-router.
"""

from __future__ import annotations

import asyncio
import contextlib
import json
import time
import uuid
from typing import Optional

from aiohttp import web

from dynamo_tpu.llm.http.failover import (
    RelayGapError,
    RelayTakenOverError,
    SseRelay,
)
from dynamo_tpu.llm.http.metrics import ServiceMetrics
from dynamo_tpu.utils import counters, tracing
from dynamo_tpu.llm.protocols.common import (
    FINISH_REASON_TIMEOUT,
    DeadlineExceededError,
    PoolExhaustedError,
)
from dynamo_tpu.llm.protocols.openai import (
    ChatCompletionRequest,
    CompletionRequest,
    RequestError,
    aggregate_chat_stream,
    aggregate_completion_stream,
)
from dynamo_tpu.runtime.pipeline.context import Context
from dynamo_tpu.runtime.pipeline.engine import AsyncEngine
from dynamo_tpu.utils.logging import get_logger

log = get_logger("dynamo_tpu.http")


class ModelManager:
    def __init__(self) -> None:
        self._chat: dict[str, AsyncEngine] = {}
        self._completion: dict[str, AsyncEngine] = {}
        self.cards: dict[str, dict] = {}  # display info for /v1/models

    def add_chat_model(self, name: str, engine: AsyncEngine) -> None:
        self._chat[name] = engine

    def add_completion_model(self, name: str, engine: AsyncEngine) -> None:
        self._completion[name] = engine

    def remove_model(self, name: str) -> None:
        self._chat.pop(name, None)
        self._completion.pop(name, None)
        self.cards.pop(name, None)

    def get_chat(self, name: str) -> Optional[AsyncEngine]:
        return self._chat.get(name)

    def get_completion(self, name: str) -> Optional[AsyncEngine]:
        return self._completion.get(name)

    def list_models(self) -> list[str]:
        return sorted(set(self._chat) | set(self._completion))


class HttpService:
    def __init__(
        self,
        manager: Optional[ModelManager] = None,
        metrics: Optional[ServiceMetrics] = None,
        request_template=None,
        request_timeout_s: Optional[float] = None,
        admission=None,
        sse_reconnect_s: Optional[float] = None,
    ):
        self.manager = manager or ModelManager()
        self.metrics = metrics or ServiceMetrics()
        # llm.http.admission.AdmissionController: front-door overload
        # gate — sheds lowest-priority tenants with the typed 429/503 +
        # Retry-After ladder BEFORE any engine work, and stamps the
        # tenant's priority class into Context metadata so the engine's
        # admission/preemption see the same ordering (docs/control.md).
        # None = every request admitted (the gate idle is a no-op).
        self.admission = admission
        if admission is not None:
            self.metrics.extra.append(admission)
        # llm.request_template.RequestTemplate: deployment defaults filled
        # into bodies that omit model/temperature/max tokens (reference:
        # request_template.rs applied by dynamo-run)
        self.request_template = request_template
        # deployment-default end-to-end deadline (seconds; None = none).
        # A request's `x-request-timeout` header overrides it. The
        # resolved deadline rides Context metadata through the
        # preprocessor into the engine (docs/robustness.md "Deadlines").
        self.request_timeout_s = request_timeout_s
        # SSE reconnect window (docs/robustness.md "Request failover"):
        # streams always carry monotonic `id:` lines; with a relay armed
        # (ctor arg > 0, else DYN_FAILOVER_RECONNECT_S) a dropped client
        # re-POSTs with `Last-Event-ID` + its `x-request-id` and resumes
        # the SAME generation from the bounded replay window — no
        # repeated or gapped events, no re-paid prefill.
        if sse_reconnect_s is not None:
            self.sse_relay = (
                SseRelay(grace_s=sse_reconnect_s)
                if sse_reconnect_s > 0 else None
            )
        else:
            self.sse_relay = SseRelay.from_env()
        self.app = web.Application()
        self.app.add_routes(
            [
                web.post("/v1/chat/completions", self._chat_completions),
                web.post("/v1/completions", self._completions),
                web.get("/v1/models", self._models),
                web.get("/metrics", self._metrics),
                web.get("/debug/trace", self._debug_trace),
                web.get("/debug/snapshot", self._debug_snapshot),
                web.get("/debug/kv", self._debug_kv),
                web.post("/debug/profile", self._debug_profile),
                web.get("/health", self._health),
                web.get("/live", self._health),
            ]
        )
        self._runner: Optional[web.AppRunner] = None
        self.port: int = 0

    # ------------------------------------------------------------ lifecycle

    async def start(self, host: str = "0.0.0.0", port: int = 0) -> None:
        self._runner = web.AppRunner(self.app)
        await self._runner.setup()
        site = web.TCPSite(self._runner, host, port)
        await site.start()
        self.port = site._server.sockets[0].getsockname()[1]
        log.info("http service listening on %s:%d", host, self.port)

    async def stop(self) -> None:
        if self._runner:
            await self._runner.cleanup()
            self._runner = None

    # --------------------------------------------------------------- routes

    async def _health(self, request: web.Request) -> web.Response:
        return web.json_response({"status": "ok", "models": self.manager.list_models()})

    async def _models(self, request: web.Request) -> web.Response:
        return web.json_response(
            {
                "object": "list",
                "data": [
                    {"id": name, "object": "model", "owned_by": "dynamo-tpu"}
                    for name in self.manager.list_models()
                ],
            }
        )

    async def _metrics(self, request: web.Request) -> web.Response:
        return web.Response(
            text=self.metrics.render(), content_type="text/plain", charset="utf-8"
        )

    async def _debug_trace(self, request: web.Request) -> web.Response:
        """Chrome/Perfetto trace-event JSON of the span ring
        (utils/tracing.py) MERGED with spans shipped from other
        processes (runtime/trace_plane.py) — a request that crossed
        frontend → router → worker renders each process as its own
        named track group. `?request_id=<id>` filters to one request,
        `?track=<name>` to one named track (e.g. ``engine.steps``).
        The response is CAPPED at `?limit=` newest non-metadata events
        (default ``DYN_TRACE_HTTP_MAX_EVENTS``, 20000; ``limit=0``
        lifts the cap) — the merged fleet ring can exceed multi-MB and
        one scrape must not serialize everything unconditionally; a
        capped body carries ``truncatedEvents``. Empty unless tracing
        is armed (DYN_TRACE=1); load the body at
        https://ui.perfetto.dev — see docs/observability.md."""
        import os

        rid = request.query.get("request_id")
        track = request.query.get("track")
        raw_limit = request.query.get("limit")
        if raw_limit is not None:
            try:
                limit = int(raw_limit)
            except ValueError:
                return _error_response(
                    400, f"invalid limit {raw_limit!r} (want an int)"
                )
        else:
            # an operator typo in the env default must not brick the
            # endpoint with a 400 blaming the client's absent ?limit=
            try:
                limit = int(
                    os.environ.get("DYN_TRACE_HTTP_MAX_EVENTS", "")
                    or 20000
                )
            except ValueError:
                limit = 20000
        return web.json_response(
            tracing.export(
                request_id=rid, track=track,
                max_events=limit if limit > 0 else None,
            )
        )

    async def _debug_snapshot(self, request: web.Request) -> web.Response:
        """Manual flight-recorder trigger (docs/observability.md
        "Forensics plane"): every registered recorder dumps its
        correlated forensic artifact NOW (rate limit bypassed — a human
        asked) and the paths come back. ``?request_id=<id>`` scopes the
        embedded trace slice to one request."""
        from dynamo_tpu.engine import flight_recorder

        rid = request.query.get("request_id")
        arts = []
        for rec in flight_recorder.registered():
            path = rec.trigger("manual", request_id=rid, force=True)
            arts.append({
                "path": path,
                "digests": rec.count,
                "dumps_total": rec.dumps_total,
            })
        return web.json_response(
            {"recorders": len(arts), "artifacts": arts}
        )

    async def _debug_kv(self, request: web.Request) -> web.Response:
        """KV page-custody snapshot (docs/observability.md "KV ledger"):
        every registered ledger reports tier breakdown, per-tenant
        attribution, top-N holders (``?top=N``, default 10), eviction
        churn, open in-flight windows, and the bounded violation log —
        live custody truth without an artifact dump."""
        from dynamo_tpu.engine import kv_ledger

        try:
            top_n = int(request.query.get("top", "") or 10)
        except ValueError:
            return _error_response(400, "invalid top= (want an int)")
        ledgers = [led.snapshot(top_n=top_n) for led in kv_ledger.registered()]
        return web.json_response({"ledgers": len(ledgers), "kv": ledgers})

    async def _debug_profile(self, request: web.Request) -> web.Response:
        """On-demand on-device profiling (``POST /debug/profile?``
        ``duration_ms=N``): one bounded `jax.profiler` capture into
        ``DYN_PROFILE_DIR``, phase-annotated to join the Perfetto ring
        export by name (engine/profiler.py). A capture already in
        flight answers 409 — the single-capture gate."""
        from dynamo_tpu.engine import profiler

        raw = request.query.get("duration_ms", "1000")
        try:
            duration_ms = float(raw)
        except ValueError:
            return _error_response(
                400, f"invalid duration_ms {raw!r} (want milliseconds)"
            )
        duration_ms = min(max(duration_ms, 1.0), 60000.0)
        if not profiler.available():
            return _error_response(
                501, "jax.profiler unavailable (or DYN_PROFILE=0)"
            )
        try:
            info = await profiler.capture(duration_ms)
        except profiler.ProfilerBusy as exc:
            return _error_response(409, str(exc))
        except profiler.ProfilerUnavailable as exc:
            return _error_response(501, str(exc))
        except Exception as exc:  # noqa: BLE001 — capture is best-effort
            log.exception("profile capture failed")
            return _error_response(500, f"profile capture failed: {exc}")
        return web.json_response(info)

    async def _chat_completions(self, request: web.Request) -> web.StreamResponse:
        return await self._serve_llm(
            request, kind="chat", parse=ChatCompletionRequest.from_body
        )

    async def _completions(self, request: web.Request) -> web.StreamResponse:
        return await self._serve_llm(
            request, kind="completion", parse=CompletionRequest.from_body
        )

    async def _serve_llm(self, request: web.Request, kind: str, parse) -> web.StreamResponse:
        # request id: echo the caller's x-request-id (distributed callers
        # stitch their own traces with it) or mint one; it becomes the
        # Context id, the trace/span key, and the JSONL log join key for
        # everything downstream in this task tree
        rid = request.headers.get("x-request-id") or uuid.uuid4().hex
        t0 = time.perf_counter()
        status = 500
        token = tracing.set_request(rid)
        try:
            resp = await self._handle_llm(request, kind, parse, rid)
            status = resp.status
            if not resp.prepared:
                # streaming responses already sent their headers (the
                # echo rides in _stream_sse); only unsent ones take it here
                resp.headers.setdefault("X-Request-Id", rid)
            return resp
        except (asyncio.CancelledError, ConnectionResetError):
            # client closed the request (nginx's 499 convention): a
            # flaky-client trace must not read as server 500s — aiohttp
            # cancels the handler on disconnect, and a mid-stream drop
            # surfaces as ConnectionResetError from resp.write()
            status = 499
            raise
        finally:
            tracing.reset_request(token)
            tracing.complete(
                "http.request", t0, time.perf_counter(), cat="http",
                req=rid, endpoint=kind, status=status,
            )

    async def _handle_llm(
        self, request: web.Request, kind: str, parse, rid: str
    ) -> web.StreamResponse:
        # SSE reconnect: a dropped client re-POSTs with Last-Event-ID +
        # the same x-request-id; the parked stream resumes from the
        # replay window — before body parsing, admission, or any engine
        # work (the generation this resumes is already running/parked)
        if self.sse_relay is not None:
            last_eid = request.headers.get("Last-Event-ID")
            if last_eid is not None:
                return await self._resume_sse(request, rid, last_eid)
        try:
            body = await request.json()
        except (json.JSONDecodeError, UnicodeDecodeError):
            return _error_response(400, "invalid JSON body")
        if self.request_template is not None:
            body = self.request_template.apply(body)
        try:
            req = parse(body)
        except RequestError as exc:
            return _error_response(400, str(exc))

        engine = (
            self.manager.get_chat(req.model)
            if kind == "chat"
            else self.manager.get_completion(req.model)
        )
        if engine is None:
            return _error_response(404, f"model {req.model!r} not found")

        # end-to-end deadline: x-request-timeout (seconds) or the service
        # default; stamped into Context metadata as an absolute epoch
        # deadline so it survives process hops on the data plane. A
        # non-positive service default means DISABLED (same contract as
        # EngineConfig.request_timeout_s) — only an explicit header can
        # express "already expired".
        timeout_s = (
            self.request_timeout_s
            if self.request_timeout_s and self.request_timeout_s > 0
            else None
        )
        hdr = request.headers.get("x-request-timeout")
        if hdr is not None:
            try:
                timeout_s = float(hdr)
            except ValueError:
                return _error_response(
                    400, f"invalid x-request-timeout {hdr!r} (want seconds)"
                )
            if timeout_s <= 0:
                # an already-spent budget is shed before any work at all
                return _error_response(
                    429, "request deadline already expired",
                    headers={"Retry-After": "1"},
                )

        # tenant label for per-tenant SLO attainment: rides Context
        # metadata across process hops like the deadline; the engine
        # stamps it into the finish summary (docs/observability.md)
        tenant = request.headers.get("x-tenant-id")

        # front-door admission ladder: under overload (attainment burn +
        # queue over watermark) the lowest-priority classes shed HERE,
        # before tokenization or engine admission, with the same typed
        # 429/503 + Retry-After responses as the deadline/pool ladder
        if self.admission is not None:
            verdict = self.admission.check(tenant or "default")
            if verdict is not None:
                return _error_response(
                    verdict.status, verdict.message,
                    headers={"Retry-After": str(max(1, verdict.retry_after_s))},
                )

        guard = self.metrics.inflight_guard(req.model, kind)
        ctx = Context(req, request_id=rid)
        if tenant:
            ctx.metadata["tenant"] = tenant
        if self.admission is not None:
            # the admitted request's priority class rides to the engine:
            # Sequence.priority orders admission picks and preemption
            # victims (engine/scheduler.py)
            ctx.metadata["priority"] = self.admission.priority_of(
                tenant or "default"
            )
        if timeout_s is not None:
            ctx.metadata["timeout_s"] = timeout_s
            ctx.metadata["deadline"] = time.time() + timeout_s
        try:
            stream = await engine.generate(ctx)
        except Exception as exc:  # noqa: BLE001 — admission or engine failure
            if not isinstance(
                exc, (ValueError, DeadlineExceededError, PoolExhaustedError)
            ):
                log.error("engine failed for %s", req.model, exc_info=exc)
            guard.close()
            return _classify_error(exc)

        try:
            if req.stream:
                return await self._stream_sse(request, ctx, stream, guard)
            return await self._respond_full(ctx, stream, guard, kind)
        except asyncio.CancelledError:
            # client disconnected (aiohttp cancels the handler) → kill the
            # context so remote engines stop generating for a vanished
            # caller — UNLESS the SSE relay just parked this stream for a
            # Last-Event-ID reconnect (the grace-expiry clock owns the
            # kill decision then, llm/http/failover.SseRelay)
            if ctx.metadata.get("sse_parked"):
                log.info("request %s parked; not killing on disconnect",
                         ctx.id)
            else:
                log.info("client disconnected; killing request %s", ctx.id)
                ctx.kill()
            raise
        finally:
            guard.close()

    async def _stream_sse(self, request, ctx, stream, guard) -> web.StreamResponse:
        # Peek the first item BEFORE committing the 200/SSE headers: with
        # lazily-started streams (the n>1 fan-out) admission errors only
        # surface at first iteration, and they should map to a real HTTP
        # status, matching the eager n==1 path.
        it = stream.__aiter__()
        first_items: list = []
        try:
            first_items.append(await it.__anext__())
        except StopAsyncIteration:
            pass
        except Exception as exc:  # noqa: BLE001 — mapped to a status code
            if not isinstance(
                exc, (ValueError, DeadlineExceededError, PoolExhaustedError)
            ):
                log.error("stream failed before first frame for %s", ctx.id,
                          exc_info=exc)
            ctx.kill()
            return _classify_error(exc)

        async def _chained():
            for x in first_items:
                yield x
            async for x in it:
                yield x

        entry = (
            self.sse_relay.open(
                ctx, model=guard._model, endpoint=guard._endpoint
            )
            if self.sse_relay is not None else None
        )
        headers = {
            "Content-Type": "text/event-stream",
            "Cache-Control": "no-cache",
            "Connection": "keep-alive",
            "X-Request-Id": ctx.id,
        }
        if entry is not None:
            # the resume credential: x-request-id is client-chosen (and
            # guessable), so a Last-Event-ID reconnect must echo this
            # server-minted token or the parked stream stays private
            headers["X-Resume-Token"] = entry.token
        resp = web.StreamResponse(headers=headers)
        await resp.prepare(request)
        if entry is None:
            # direct path (relay off or at capacity): frames carry
            # monotonic ids but a dropped client cannot resume — the
            # disconnect kills the request like PR 6 shipped it
            eid = 0
            ok = False
            try:
                async for fkind, frame in self._sse_frames(ctx, _chained()):
                    eid += 1
                    await resp.write(b"id: %d\n" % eid + frame)
                    if fkind == "done":
                        ok = True
                if ok:
                    guard.mark_ok()
            except (ConnectionResetError, asyncio.CancelledError):
                # client went away → kill the context so the engine stops
                # (reference: openai.rs:433 monitor_for_disconnects)
                log.info("client disconnected; killing request %s", ctx.id)
                ctx.kill()
                raise
            with contextlib.suppress(ConnectionResetError):
                await resp.write_eof()
            return resp

        # relay path: the generation pump is decoupled from the socket —
        # frames land in the bounded replay window (with backpressure
        # while this client keeps up), and a client drop PARKS the
        # stream for Last-Event-ID resume instead of killing it
        entry.pump = asyncio.create_task(
            self._relay_pump(ctx, entry, _chained())
        )
        try:
            async for _eid, frame in entry.subscribe(after=0):
                await resp.write(frame)
            if entry.ok:
                guard.mark_ok()
            # the client saw the stream end: nothing left to resume
            self.sse_relay.discard(ctx.id)
        except RelayGapError:
            # this live subscriber fell behind its own window (slow
            # reader after a takeover): it cannot continue gapless
            self.sse_relay.discard(ctx.id)
            ctx.kill()
        except RelayTakenOverError:
            # a reconnect won the race against our dead-socket notice:
            # just end this response, the window lives on — and this
            # exchange's verdict is "detached" (the resume records the
            # final one), not the guard's default "error"
            guard.status = "detached"
        except (ConnectionResetError, asyncio.CancelledError):
            log.info(
                "client dropped mid-stream; parking %s for reconnect "
                "(%.0fs window)", ctx.id, self.sse_relay.grace_s,
            )
            self.sse_relay.detach(entry)
            # the generation lives on, parked: _handle_llm's outer
            # cancel handler must NOT kill it, and this exchange's
            # accounting verdict is "detached", not "error" (a resume
            # exchange records the final success/error)
            ctx.metadata["sse_parked"] = True
            guard.status = "detached"
            raise
        except Exception:
            self.sse_relay.discard(ctx.id)
            ctx.kill()
            raise
        with contextlib.suppress(ConnectionResetError):
            await resp.write_eof()
        return resp

    async def _sse_frames(self, ctx, items):
        """Encode the engine stream as SSE frames: yields
        (kind, frame_bytes) with kind in comment/event/data/done/error.
        Engine faults become an `error` event + kill (the 200 is
        already on the wire); transport faults raise to the caller."""
        try:
            async for item in items:
                if "__annotation__" in item:
                    # reference: SSE `event:` lines for annotations; the
                    # internal "ready" frame becomes an SSE comment
                    # (spec: lines starting with ':' are ignored)
                    name, data = item["__annotation__"], item["data"]
                    if name == "ready":
                        yield "comment", b": ready\n\n"
                        continue
                    yield (
                        "event",
                        f"event: {name}\ndata: {json.dumps(data)}\n\n".encode(),
                    )
                    continue
                with tracing.phase("fe.stream"):  # serializing one chunk
                    frame = f"data: {json.dumps(item)}\n\n".encode()
                yield "data", frame
            yield "done", b"data: [DONE]\n\n"
        except (ConnectionResetError, asyncio.CancelledError):
            raise
        except Exception as exc:  # noqa: BLE001 — any mid-stream fault
            # (engine, data-plane drop past failover, codec) becomes an
            # SSE error event + kill rather than a truncation
            log.error("stream error for request %s: %s", ctx.id, exc)
            ctx.kill()
            yield (
                "error",
                f'event: error\ndata: {json.dumps({"message": str(exc)})}\n\n'.encode(),
            )

    async def _relay_pump(self, ctx, entry, items) -> None:
        """Drain the engine stream into the relay window (detached from
        the client socket — a parked stream keeps generating until the
        window fills or the reconnect grace expires)."""
        ok = False
        try:
            async for fkind, frame in self._sse_frames(ctx, items):
                await entry.append(frame)
                if fkind == "done":
                    ok = True
        except asyncio.CancelledError:
            raise
        except Exception:  # noqa: BLE001 — the window just ends early
            log.exception("sse relay pump failed for %s", ctx.id)
        finally:
            await entry.finish(ok)

    async def _resume_sse(
        self, request: web.Request, rid: str, last_eid: str
    ) -> web.StreamResponse:
        """Serve a Last-Event-ID reconnect from the parked window —
        events strictly after the client's last id, then the live tail
        of the same generation. No repeats, no gaps: a resume point
        already evicted answers 410 (the client must retry in full)."""
        try:
            after = int(last_eid)
        except ValueError:
            return _error_response(
                400, f"invalid Last-Event-ID {last_eid!r} (want an int)"
            )
        relay = self.sse_relay
        entry = relay.get(rid)
        if entry is None or after < entry.floor:
            counters.inc("failover_sse_expired_total")
            return _error_response(
                410, f"reconnect window expired for request {rid}"
            )
        # the server-minted credential from the original exchange's
        # X-Resume-Token header: without it, any caller presenting a
        # guessed x-request-id could hijack-read this stream. Answered
        # as the same 410 — an unauthorized prober learns nothing about
        # whether the window exists.
        if request.headers.get("X-Resume-Token") != entry.token:
            counters.inc("failover_sse_expired_total")
            return _error_response(
                410, f"reconnect window expired for request {rid}"
            )
        epoch = relay.attach(entry, after=after)
        counters.inc("failover_sse_resumes_total")
        # the resume exchange carries the request's FINAL accounting
        # verdict (the original handler's guard closed "detached" when
        # the client dropped)
        guard = self.metrics.inflight_guard(
            entry.model, entry.endpoint or "completions"
        )
        resp = web.StreamResponse(
            headers={
                "Content-Type": "text/event-stream",
                "Cache-Control": "no-cache",
                "Connection": "keep-alive",
                "X-Request-Id": rid,
            }
        )
        await resp.prepare(request)
        try:
            async for _eid, frame in entry.subscribe(after=after, epoch=epoch):
                await resp.write(frame)
            if entry.ok:
                guard.mark_ok()
            relay.discard(rid)
        except RelayGapError:
            counters.inc("failover_sse_expired_total")
            relay.discard(rid)
            entry.ctx.kill()
        except RelayTakenOverError:
            guard.status = "detached"  # an even newer reconnect owns it
        except (ConnectionResetError, asyncio.CancelledError):
            relay.detach(entry)
            guard.status = "detached"
            raise
        finally:
            guard.close()
        with contextlib.suppress(ConnectionResetError):
            await resp.write_eof()
        return resp

    async def _respond_full(self, ctx, stream, guard, kind) -> web.Response:
        async def _data_only():
            async for item in stream:
                if "__annotation__" not in item:
                    yield item

        try:
            if kind == "chat":
                full = await aggregate_chat_stream(_data_only())
            else:
                full = await aggregate_completion_stream(_data_only())
        except Exception as exc:  # noqa: BLE001 — mapped to a status code
            ctx.kill()
            return _classify_error(exc)
        if _timed_out_empty(full):
            # deadline expired in the admission queue: zero tokens were
            # produced and the response had not started streaming, so
            # the caller gets a REAL 429 instead of a 200 with an empty
            # "timeout" choice (docs/robustness.md "Deadlines")
            return _error_response(
                429, "request deadline expired in the admission queue",
                headers={"Retry-After": "1"},
            )
        guard.mark_ok()
        return web.json_response(full)


def _error_response(
    status: int, message: str, headers: Optional[dict] = None
) -> web.Response:
    kind = (
        "invalid_request_error" if status < 500 and status != 429
        else "rate_limit_error" if status == 429
        else "server_error"
    )
    return web.json_response(
        {"error": {"message": message, "type": kind}},
        status=status, headers=headers,
    )


def _timed_out_empty(full: dict) -> bool:
    """Did every choice of an aggregated response end `timeout` with no
    content? (= the deadline expired before the first token; eligible
    for conversion to a real 429 since nothing has streamed yet)."""
    choices = full.get("choices") or []
    if not choices:
        return False
    for c in choices:
        if c.get("finish_reason") != FINISH_REASON_TIMEOUT:
            return False
        text = c.get("text") or (c.get("message") or {}).get("content")
        if text:
            return False
    return True


def _classify_error(exc: Exception) -> web.Response:
    """One policy for mapping stream/admission exceptions to HTTP status:
    DeadlineExceeded = the caller's budget expired before device work ->
    429 + Retry-After; PoolExhausted = a capacity condition -> 503 +
    Retry-After; ValueError (incl. RequestError) = the request was
    invalid -> 400; anything else = server fault -> 502. Post-admission
    stream faults are normalized to RuntimeError by the preprocessor, so
    they land in 502."""
    if isinstance(exc, DeadlineExceededError):
        return _error_response(
            429, str(exc),
            headers={"Retry-After": str(max(1, int(exc.retry_after_s)))},
        )
    if isinstance(exc, PoolExhaustedError):
        return _error_response(
            503, str(exc),
            headers={"Retry-After": str(max(1, int(exc.retry_after_s)))},
        )
    if isinstance(exc, ValueError):
        return _error_response(400, str(exc))
    return _error_response(502, f"engine error: {exc}")

