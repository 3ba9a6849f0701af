"""Request-level failover plane (llm/http/failover.py): journaled
replay across worker death, typed mid-stream breaks, the replay storm
cap, lease-expiry/breaker failure detection, and the SSE Last-Event-ID
reconnect window. The e2e chaos proof (DYN_FAULTS worker death under a
real two-worker fleet, byte-identical greedy stream) lives in
tests/test_chaos.py; this file covers the mechanism.
"""

import asyncio

import pytest

from dynamo_tpu.llm.http.failover import (
    FailoverConfig,
    FailoverEngine,
    JournalEntry,
    SseRelay,
)
from dynamo_tpu.llm.http.failover import recent_replays, reset_stats
from dynamo_tpu.llm.protocols.common import PoolExhaustedError
from dynamo_tpu.runtime.pipeline.context import Context
from dynamo_tpu.runtime.resilience import StreamBrokenError
from dynamo_tpu.utils import counters


@pytest.fixture(autouse=True)
def _clean():
    counters.reset()
    reset_stats()
    yield
    counters.reset()
    reset_stats()


def _payload(prompt, max_tokens=12, min_tokens=None, seed=None):
    return {
        "token_ids": list(prompt),
        "stop_conditions": {"max_tokens": max_tokens,
                            "min_tokens": min_tokens},
        "sampling_options": {"greedy": seed is None, "seed": seed},
    }


def _arith_next(t: int) -> int:
    return (t * 31 + 7) % 997


def arith_ref(prompt, n):
    """The deterministic continuation any healthy engine produces."""
    toks, last = [], prompt[-1]
    for _ in range(n):
        last = _arith_next(last)
        toks.append(last)
    return toks


class ArithEngine:
    """Continuation-safe fake engine: output depends only on the prompt
    tail, like a greedy model — serving prompt+emitted resumes the
    exact sequence. `die_after` breaks the stream (typed) after that
    many tokens; `hang_after` stalls it without an error (the wedged-
    worker-with-live-socket shape); `gate` delays the first frame."""

    def __init__(self, instance, die_after=None, hang_after=None,
                 cached_tokens=0, gate=None):
        self.instance = instance
        self.die_after = die_after
        self.hang_after = hang_after
        self.cached_tokens = cached_tokens
        self.gate = gate
        self.serves = 0

    async def generate(self, ctx):
        pre = ctx.payload
        self.serves += 1
        ctx.metadata["served_by"] = self.instance

        async def _gen():
            if self.gate is not None:
                await self.gate.wait()
            last = pre["token_ids"][-1]
            budget = pre["stop_conditions"]["max_tokens"]
            emitted = 0
            first = True
            while emitted < budget:
                if self.hang_after is not None and emitted >= self.hang_after:
                    await asyncio.Event().wait()  # wedged, socket alive
                last = _arith_next(last)
                emitted += 1
                frame = {"token_ids": [last]}
                if first:
                    frame["meta"] = {
                        "prefix_cached_tokens": self.cached_tokens,
                        "prompt_tokens": len(pre["token_ids"]),
                    }
                    first = False
                yield frame
                if self.die_after is not None and emitted >= self.die_after:
                    raise StreamBrokenError(
                        "injected mid-stream break",
                        instance_id=self.instance,
                    )
            yield {"token_ids": [], "finish_reason": "length"}

        return _gen()


class SwitchInner:
    """Routes to the first engine whose instance is not excluded —
    the two-line stand-in for the router stack."""

    def __init__(self, engines):
        self.engines = engines

    async def generate(self, ctx):
        excluded = set(ctx.metadata.get("failover_exclude") or ())
        for eng in self.engines:
            if eng.instance not in excluded:
                return await eng.generate(ctx)
        raise ConnectionError("no healthy instances")


async def _collect(stream):
    toks, finish = [], None
    async for f in stream:
        toks.extend(f.get("token_ids") or [])
        if f.get("finish_reason"):
            finish = f["finish_reason"]
    return toks, finish


# ------------------------------------------------------------- journal


def test_replay_payload_is_prompt_continuation():
    e = JournalEntry("r", _payload([5, 9], max_tokens=10, min_tokens=6))
    e.emitted = [101, 102, 103]
    d = e.replay_payload()
    assert d["token_ids"] == [5, 9, 101, 102, 103]
    assert d["stop_conditions"]["max_tokens"] == 7
    assert d["stop_conditions"]["min_tokens"] == 3
    # sampling params (incl. seed) ride unchanged
    assert d["sampling_options"] == e.payload["sampling_options"]
    # the original payload was not mutated
    assert e.payload["token_ids"] == [5, 9]
    assert e.payload["stop_conditions"]["max_tokens"] == 10


def test_journal_accept_clamps_over_budget_tail():
    # frames carry finish_reason=None mid-stream like real
    # EngineOutput.to_dict() frames do — the clamp must REPLACE the
    # None, not setdefault around it (regression: the clamped frame
    # went downstream without a finish and the stream never closed)
    e = JournalEntry("r", _payload([5], max_tokens=3))
    e.accept({"token_ids": [1, 2], "finish_reason": None})
    out = e.accept({"token_ids": [3, 4, 5], "finish_reason": None,
                    "log_probs": [0.1, 0.2, 0.3]})
    assert out["token_ids"] == [3]
    assert out["log_probs"] == [0.1]
    assert out["finish_reason"] == "length"
    assert e.emitted == [1, 2, 3]
    assert e.remaining_tokens() == 0


# ----------------------------------------------------------- replay path


async def test_failover_resumes_exact_stream():
    prompt = [5, 17, 42]
    ref = arith_ref(prompt, 12)
    dead = ArithEngine(0, die_after=4)
    healthy = ArithEngine(1)
    eng = FailoverEngine(SwitchInner([dead, healthy]),
                         cfg=FailoverConfig())
    ctx = Context(_payload(prompt, max_tokens=12))
    toks, finish = await _collect(await eng.generate(ctx))
    assert toks == ref, "resume must neither repeat nor gap a token"
    assert finish == "length"
    assert healthy.serves == 1
    # the replay prompt was the continuation, not a fresh start
    assert counters.get("failover_replays_total") == 1.0
    assert counters.get("failover_recovered_total") == 1.0
    rec = recent_replays()[-1]
    assert rec["emitted_at_break"] == 4
    assert rec["replay_prompt_tokens"] == len(prompt) + 4
    assert rec["recompute_tokens"] == len(prompt) + 4
    assert rec["gap_s"] is not None


async def test_failover_seeded_payload_keeps_seed():
    prompt = [5, 17]
    dead = ArithEngine(0, die_after=2)
    healthy = ArithEngine(1)
    eng = FailoverEngine(SwitchInner([dead, healthy]))
    ctx = Context(_payload(prompt, max_tokens=6, seed=1234))
    toks, _ = await _collect(await eng.generate(ctx))
    assert toks == arith_ref(prompt, 6)
    # the continuation payload still carried the seed (the engine keys
    # sampling on (seed, absolute position) so the draw is identical)
    assert healthy.serves == 1


async def test_failover_retry_budget_exhausts_typed():
    prompt = [3, 4]
    engines = [ArithEngine(i, die_after=1) for i in range(4)]
    eng = FailoverEngine(SwitchInner(engines),
                         cfg=FailoverConfig(max_retries=2))
    ctx = Context(_payload(prompt))
    with pytest.raises(StreamBrokenError):
        await _collect(await eng.generate(ctx))
    assert counters.get("failover_replays_total") == 2.0
    assert counters.get("failover_giveup_total") == 1.0


async def test_failover_storm_cap_sheds_typed_503():
    """Over the replay concurrency cap, a broken stream sheds with the
    typed PoolExhaustedError (503 + Retry-After ladder) instead of
    queueing a replay storm."""
    prompt = [7, 8]
    gate = asyncio.Event()  # replacement streams stall pre-first-frame,
    #                         so the first replay HOLDS its storm slot
    dead0 = ArithEngine(0, die_after=2)
    dead1 = ArithEngine(1, die_after=2)
    slow2 = ArithEngine(2, gate=gate)
    eng = FailoverEngine(
        SwitchInner([dead0, dead1, slow2]),
        cfg=FailoverConfig(max_concurrent=1, max_retries=3),
    )

    async def run(payload_prompt):
        ctx = Context(_payload(payload_prompt, max_tokens=6))
        return await _collect(await eng.generate(ctx))

    t0 = asyncio.ensure_future(run([7, 8]))
    # wait until stream 0's SECOND replay is parked on the gated engine
    # — that attempt holds the single slot until its first frame (the
    # first replay's slot releases at dead1's first frame, so waiting
    # for replay #1 alone would race t1 into the freed slot)
    for _ in range(200):
        if counters.get("failover_replays_total") >= 2.0:
            break
        await asyncio.sleep(0.01)
    assert counters.get("failover_replays_total") == 2.0
    t1 = asyncio.ensure_future(run([9, 10]))
    with pytest.raises(PoolExhaustedError) as ei:
        await t1
    assert ei.value.retry_after_s >= 1.0
    assert counters.get("failover_storm_shed_total") == 1.0
    gate.set()
    toks, _ = await t0
    assert toks == arith_ref([7, 8], 6)


async def test_failover_lease_expiry_breaks_live_socket():
    """An expired lease with a live socket still counts as a failed
    worker: the instance-down hook condemns the wedged stream and the
    request fails over (ISSUE satellite: lease-expiry detection)."""
    from dynamo_tpu.runtime.component import EndpointId

    class _Drt:
        def __init__(self):
            self.hooks = []

        def on_instance_down(self, fn):
            self.hooks.append(fn)

    class _Client:
        endpoint_id = EndpointId("ns", "comp", "ep")

        def add_breaker_listener(self, fn):
            pass

    drt = _Drt()
    wedged = ArithEngine(0, hang_after=3)
    healthy = ArithEngine(1)
    eng = FailoverEngine(SwitchInner([wedged, healthy]),
                         client=_Client(), drt=drt)
    assert drt.hooks, "failover must subscribe to instance-down"
    ctx = Context(_payload([2, 44, 8], max_tokens=9))
    task = asyncio.ensure_future(_collect(await eng.generate(ctx)))
    # wait until the wedge: 3 tokens delivered, socket still "alive"
    for _ in range(200):
        if counters.get("failover_replays_total") or len(
            recent_replays()
        ) or _journal_emitted(eng) >= 3:
            break
        await asyncio.sleep(0.01)
    assert _journal_emitted(eng) == 3
    # lease expiry: discovery pops the instance -> hook fires
    drt.hooks[0](_Client.endpoint_id, 0)
    toks, finish = await asyncio.wait_for(task, 30)
    assert toks == arith_ref([2, 44, 8], 9)
    assert finish == "length"
    assert recent_replays()[-1]["reason"] == "lease_expired"


def _journal_emitted(eng: FailoverEngine) -> int:
    entries = list(eng._live.values())
    return len(entries[0].emitted) if entries else -1


async def test_failover_ignores_other_endpoints_instance_down():
    from dynamo_tpu.runtime.component import EndpointId

    class _Drt:
        def __init__(self):
            self.hooks = []

        def on_instance_down(self, fn):
            self.hooks.append(fn)

    class _Client:
        endpoint_id = EndpointId("ns", "comp", "ep")

        def add_breaker_listener(self, fn):
            pass

    drt = _Drt()
    eng = FailoverEngine(SwitchInner([ArithEngine(0)]),
                         client=_Client(), drt=drt)
    ctx = Context(_payload([1, 2], max_tokens=4))
    stream = await eng.generate(ctx)
    it = stream.__aiter__()
    first = await it.__anext__()
    assert first["token_ids"]
    # an unrelated component's worker 0 dying must NOT condemn ours
    drt.hooks[0](EndpointId("ns", "other", "ep"), 0)
    toks, _ = await _collect(it)
    assert len(toks) == 3  # the remaining tokens, uninterrupted
    assert counters.get("failover_replays_total") == 0.0


async def test_failover_breaker_open_condemns_stream():
    listeners = []

    class _Client:
        endpoint_id = None

        def add_breaker_listener(self, fn):
            listeners.append(fn)

    wedged = ArithEngine(0, hang_after=2)
    healthy = ArithEngine(1)
    eng = FailoverEngine(SwitchInner([wedged, healthy]), client=_Client())
    assert listeners
    ctx = Context(_payload([11, 3], max_tokens=8))
    task = asyncio.ensure_future(_collect(await eng.generate(ctx)))
    for _ in range(200):
        if _journal_emitted(eng) >= 2:
            break
        await asyncio.sleep(0.01)
    listeners[0](0)  # this instance's breaker tripped open
    toks, _ = await asyncio.wait_for(task, 30)
    assert toks == arith_ref([11, 3], 8)
    assert recent_replays()[-1]["reason"] == "breaker_open"


async def test_failover_break_after_final_token_closes_clean():
    """A break after the last budgeted token (finish frame lost) closes
    the stream with the length finish — no replay, no duplicate."""
    dead = ArithEngine(0, die_after=4)
    eng = FailoverEngine(SwitchInner([dead]))
    ctx = Context(_payload([5, 6], max_tokens=4))
    toks, finish = await _collect(await eng.generate(ctx))
    assert toks == arith_ref([5, 6], 4)
    assert finish == "length"
    assert counters.get("failover_replays_total") == 0.0
    assert counters.get("failover_recovered_total") == 1.0


async def test_failover_passthrough_non_token_payload():
    class _Inner:
        called = 0

        async def generate(self, ctx):
            self.called += 1

            async def g():
                yield {"x": 1}

            return g()

    inner = _Inner()
    eng = FailoverEngine(inner)
    out = [f async for f in await eng.generate(Context(object()))]
    assert out == [{"x": 1}] and inner.called == 1
    assert not eng._live


async def test_failover_disabled_passthrough():
    dead = ArithEngine(0, die_after=2)
    eng = FailoverEngine(SwitchInner([dead, ArithEngine(1)]),
                         cfg=FailoverConfig(enabled=False))
    with pytest.raises(StreamBrokenError):
        await _collect(await eng.generate(Context(_payload([1, 2]))))


# ------------------------------------------------------------ SSE relay


def _frame_text(data: str) -> str:
    """Stream-identity view of one SSE data payload: the delta text
    ([DONE] stays itself; the per-request cmpl id is not identity)."""
    import json as _json

    if data == "[DONE]":
        return data
    item = _json.loads(data)
    return "".join(c.get("text") or "" for c in item.get("choices") or [])


async def _sse_events(resp):
    """Parse an aiohttp SSE response into (last_id, [frame texts])."""
    last_id, datas = None, []
    async for raw in resp.content:
        line = raw.decode().rstrip("\n")
        if line.startswith("id: "):
            last_id = int(line[4:])
        elif line.startswith("data: "):
            datas.append(_frame_text(line[6:]))
    return last_id, datas


async def test_sse_event_ids_and_reconnect_resume():
    """Monotonic SSE ids + Last-Event-ID resume: drop the client
    mid-stream, reconnect, and the joined stream is exactly the
    uninterrupted one — no repeats, no gaps."""
    import aiohttp

    from dynamo_tpu.llm.http.engine_service import engine_http_service

    class SlowArith(ArithEngine):
        async def generate(self, ctx):
            stream = await super().generate(ctx)

            async def paced():
                async for f in stream:
                    yield f
                    await asyncio.sleep(0.02)

            return paced()

    engine = SlowArith(0)
    async with engine_http_service(engine) as svc:
        svc.sse_relay = SseRelay(grace_s=30.0, window_events=64)
        base = f"http://127.0.0.1:{svc.port}"
        body = {
            "model": "loadgen", "prompt": [5, 17, 42], "stream": True,
            "max_tokens": 16, "dyn_ext": {"ignore_eos": True},
        }

        async with aiohttp.ClientSession(base) as session:
            # reference: uninterrupted stream
            async with session.post(
                "/v1/completions", json=body,
                headers={"x-request-id": "ref-1"},
            ) as resp:
                assert resp.status == 200
                _, ref = await _sse_events(resp)

            # interrupted: read a few events, then drop the connection
            got_head = []
            last_id = None
            async with session.post(
                "/v1/completions", json=body,
                headers={"x-request-id": "cut-1"},
            ) as resp:
                assert resp.status == 200
                # the resume credential rides the ORIGINAL response
                token = resp.headers["X-Resume-Token"]
                n_data = 0
                async for raw in resp.content:
                    line = raw.decode().rstrip("\n")
                    if line.startswith("id: "):
                        last_id = int(line[4:])
                    elif line.startswith("data: "):
                        got_head.append(_frame_text(line[6:]))
                        n_data += 1
                        if n_data >= 4:
                            break
                resp.close()  # client vanishes mid-stream

            assert last_id is not None
            # a hijacker guessing the request id but lacking the token
            # learns nothing (same 410 as a missing window)
            async with session.post(
                "/v1/completions", json=body,
                headers={"x-request-id": "cut-1",
                         "Last-Event-ID": str(last_id)},
            ) as resp:
                assert resp.status == 410
            # reconnect with Last-Event-ID + the minted token: the SAME
            # generation resumes
            async with session.post(
                "/v1/completions", json=body,
                headers={"x-request-id": "cut-1",
                         "Last-Event-ID": str(last_id),
                         "X-Resume-Token": token},
            ) as resp:
                assert resp.status == 200
                _, tail = await _sse_events(resp)

        joined = got_head + tail
        assert joined == ref, "resume repeated or gapped an event"
        assert counters.get("failover_sse_resumes_total") == 1.0


async def test_sse_reconnect_expired_window_410():
    import aiohttp

    from dynamo_tpu.llm.http.engine_service import engine_http_service

    async with engine_http_service(ArithEngine(0)) as svc:
        svc.sse_relay = SseRelay(grace_s=30.0)
        base = f"http://127.0.0.1:{svc.port}"
        async with aiohttp.ClientSession(base) as session:
            async with session.post(
                "/v1/completions",
                json={"model": "loadgen", "prompt": [1, 2], "stream": True,
                      "max_tokens": 4, "dyn_ext": {"ignore_eos": True}},
                headers={"x-request-id": "gone-1",
                         "Last-Event-ID": "3"},
            ) as resp:
                # never-seen request id: the window does not exist
                assert resp.status == 410
        assert counters.get("failover_sse_expired_total") == 1.0


async def test_sse_relay_grace_expiry_kills_request():
    """A parked stream whose client never returns is killed at the
    grace deadline (the engine must not generate forever)."""
    relay = SseRelay(grace_s=0.05)
    ctx = Context({"token_ids": [1]})
    entry = relay.open(ctx)
    assert entry is not None
    relay.detach(entry)
    await asyncio.sleep(0.2)
    assert relay.get(ctx.id) is None
    assert ctx.is_killed()


async def test_sse_relay_bounded_entries():
    relay = SseRelay(grace_s=1.0, max_entries=2)
    a = relay.open(Context({}))
    b = relay.open(Context({}))
    assert a is not None and b is not None
    assert relay.open(Context({})) is None, "over cap: no reconnect cover"


async def test_failover_stale_breaker_event_cannot_condemn_replay():
    """The dead worker's breaker keeps tripping after the replay
    launched (stats scrapes, sibling streams). A breaker-open event for
    the PREVIOUS attempt's instance must not condemn the fresh attempt
    — the stale id is cleared before the replay routes (regression:
    the replay was condemned and a second replay lost the pull)."""
    listeners = []

    class _Client:
        endpoint_id = None

        def add_breaker_listener(self, fn):
            listeners.append(fn)

    dead = ArithEngine(0, die_after=3)
    slow_gate = asyncio.Event()
    healthy = ArithEngine(1, gate=slow_gate)
    eng = FailoverEngine(SwitchInner([dead, healthy]), client=_Client())
    ctx = Context(_payload([7, 21], max_tokens=8))
    task = asyncio.ensure_future(_collect(await eng.generate(ctx)))
    # wait for the break + replay to be in flight (healthy is gated
    # pre-first-frame, exactly the establishment window of the race)
    for _ in range(200):
        if counters.get("failover_replays_total") >= 1.0:
            break
        await asyncio.sleep(0.01)
    # the dead instance's breaker trips NOW — late, after the replay
    listeners[0](0)
    slow_gate.set()
    toks, finish = await asyncio.wait_for(task, 30)
    assert toks == arith_ref([7, 21], 8)
    assert finish == "length"
    assert counters.get("failover_replays_total") == 1.0, (
        "the stale breaker event forced a second replay"
    )


async def test_sse_relay_attach_rewinds_consumed():
    """A resume from an earlier event than the old subscriber's
    progress must rewind the eviction guard: the old subscriber was
    YIELDED frames its client never persisted, and the pump must not
    evict what the resuming client still needs (regression: spurious
    RelayGapError on resume under continued production)."""
    from dynamo_tpu.llm.http.failover import RelayEntry

    relay = SseRelay(grace_s=30.0, window_events=4)
    ctx = Context({"token_ids": [1]})
    entry = relay.open(ctx)
    assert entry is not None
    # the (doomed) original subscriber keeps up through eid 6 — its
    # consumed watermark advances past each append like subscribe()'s
    # yield loop would, so the window free-runs to [3..6]
    for i in range(6):
        await entry.append(b"data: %d\n\n" % i)
        entry.consumed = entry.last_eid
    # ...but its CLIENT only persisted eid 2 before the socket died
    relay.detach(entry)
    epoch = relay.attach(entry, after=2)
    assert entry.consumed == 2

    got = []

    async def consume():
        async for eid, _frame in entry.subscribe(after=2, epoch=epoch):
            got.append(eid)
            await asyncio.sleep(0.01)  # slow client

    task = asyncio.ensure_future(consume())
    # the pump keeps producing: with consumed rewound these appends
    # BACKPRESSURE instead of evicting 3..6 out from under the resume
    for i in range(6, 8):
        await entry.append(b"data: %d\n\n" % i)
    await entry.finish(ok=True)
    await asyncio.wait_for(task, 10)
    assert got == [3, 4, 5, 6, 7, 8], got
