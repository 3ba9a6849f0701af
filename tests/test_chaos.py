"""Deterministic chaos scenarios (DYN_FAULTS registry, utils/faults.py).

Each test injects one fault class and asserts the acceptance contract
from the fault-tolerance spine: every in-flight request RESOLVES
(tokens, a typed error, or a timeout/429-class finish) within its
budget, nothing hangs, and after the fault clears the engine serves
byte-identical greedy streams. The CI chaos job runs this file (plus
tests/test_robustness.py, which covers the slow-dispatch/watchdog and
client-disconnect scenarios) — see .github/workflows/pre-merge.yml.
"""

import asyncio
import contextlib
import time

import pytest

from dynamo_tpu.engine import EngineConfig, JaxEngine
from dynamo_tpu.llm.protocols.common import (
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)
from dynamo_tpu.models import config as cfgmod
from dynamo_tpu.runtime.pipeline.context import Context
from dynamo_tpu.utils import counters, faults

from .helpers import hub_pair

CFG = cfgmod.get_config("tiny")


@pytest.fixture(autouse=True)
def _clean():
    faults.reset()
    counters.reset()
    yield
    faults.reset()
    counters.reset()


def make_engine(**kw) -> JaxEngine:
    defaults = dict(
        model=CFG,
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


def greedy_request(prompt, max_tokens=8) -> PreprocessedRequest:
    return PreprocessedRequest(
        token_ids=list(prompt),
        stop_conditions=StopConditions(max_tokens=max_tokens),
        sampling_options=SamplingOptions(greedy=True),
    )


async def collect(engine, pre, deadline=None):
    ctx = Context(pre.to_dict())
    if deadline is not None:
        ctx.metadata["deadline"] = deadline
    frames = [f async for f in await engine.generate(ctx)]
    tokens = [t for f in frames for t in f.get("token_ids") or []]
    return tokens, frames[-1].get("finish_reason")


PROMPTS = ([5, 17, 42, 9], [11, 3, 7, 29, 31], [2, 44, 8])


async def _serve_wave(engine, max_tokens=8):
    outs = await asyncio.gather(
        *(collect(engine, greedy_request(p, max_tokens)) for p in PROMPTS)
    )
    return outs


async def _baseline(max_tokens=8, **kw):
    plain = make_engine(**kw)
    want = await _serve_wave(plain, max_tokens)
    await plain.close()
    assert all(f == "length" for _, f in want)
    return want


# ---------------------------------------------------------------------
# scenario: dispatch failure mid-wave (prefill group dispatch dies once)


async def test_chaos_prefill_dispatch_failure_mid_wave():
    want = await _baseline()
    engine = make_engine()
    # the FIRST prefill group dispatch fails; the engine must contain it
    # (retry-singly path), finish every request, and match byte-for-byte
    faults.configure("engine.prefill.fail@1x1")
    got = await asyncio.wait_for(_serve_wave(engine), 120)
    assert got == want, "recovery must be byte-identical"
    assert faults.stats()["engine.prefill"]["fired"] == 1
    # fault cleared: a fresh wave serves clean
    got2 = await asyncio.wait_for(_serve_wave(engine), 120)
    assert got2 == want
    await engine.close()


# ---------------------------------------------------------------------
# scenario: mixed-step dispatch failure -> degrade ladder -> normal paths


async def test_chaos_mixed_dispatch_failure_degrades_cleanly():
    want = await _baseline(max_tokens=24, mixed_batching=True)

    engine = make_engine(mixed_batching=True)
    faults.configure("engine.mixed.fail")
    # stagger arrivals so decode-ready rows and prefill chunks coexist
    # (the mixed-step precondition); any mixed step then fails and the
    # engine must degrade to the contained normal paths mid-serve

    async def late(delay, p):
        await asyncio.sleep(delay)
        return await collect(engine, greedy_request(p, 24))

    got = await asyncio.wait_for(
        asyncio.gather(
            *(late(0.4 * i, p) for i, p in enumerate(PROMPTS))
        ),
        180,
    )
    assert got == want, "degraded serve must stay byte-identical"
    fired = faults.stats()["engine.mixed"]["fired"]
    if fired:
        # the one-way trip is loud on /metrics
        assert engine.metrics()["mixed_disabled"] == 1
        assert engine.phase_stats["mixed_disabled"] == 1
    await engine.close()


# ---------------------------------------------------------------------
# scenario: KV-pool exhaustion (transient, then permanent + deadline)


async def test_chaos_transient_pool_exhaustion_recovers():
    want = await _baseline()
    engine = make_engine()
    # the first two page reservations fail as if the pool were empty;
    # admission must retry and serve everything once pages "free up"
    faults.configure("engine.reserve.failx2")
    got = await asyncio.wait_for(_serve_wave(engine), 120)
    assert got == want
    assert faults.stats()["engine.reserve"]["fired"] == 2
    await engine.close()


async def test_chaos_sustained_pool_exhaustion_sheds_within_deadline():
    engine = make_engine()
    faults.configure("engine.reserve.fail")  # pool never recovers
    t0 = time.perf_counter()
    tokens, finish = await asyncio.wait_for(
        collect(
            engine, greedy_request([5, 17, 42]),
            deadline=time.time() + 0.4,
        ),
        60,
    )
    assert finish == "timeout" and tokens == []
    # resolved promptly once the deadline passed — not a hang
    assert time.perf_counter() - t0 < 30
    assert engine.phase_stats["deadline_shed"] == 1
    await engine.close()


# ---------------------------------------------------------------------
# scenario: hub connection drop mid-lease (keepalive thread reconnects)


async def test_chaos_hub_drop_mid_lease_keepalive_reconnects():
    async with hub_pair() as (server, client):
        lease = await client.lease_grant(ttl=1.5, keepalive="thread")
        await client.kv_put("/chaos/worker", b"alive", lease=lease)
        # let the first threaded keepalive land before arming the fault
        await asyncio.sleep(0.3)
        # ONE dropped hub round trip mid-lease: the keepalive thread
        # must treat it as a dead connection, reconnect (jittered), and
        # keep the lease alive — a silently-expired lease is the
        # "worker vanishes while healthy" failure this exists to stop
        faults.configure("hub.send.dropx1")
        await asyncio.sleep(2.0)  # several keepalive periods of chaos
        faults.reset()
        assert await lease.is_valid(), "lease must survive the drop"
        assert (await client.kv_get("/chaos/worker")) is not None
        assert counters.get("hub_reconnects_total") >= 1.0
        assert counters.get("lease_expired_total") == 0.0
        assert faults.stats() == {}  # registry cleanly cleared
        lease.client.keepalive_thread().stop()


async def test_chaos_hub_recv_drop_fails_pending_cleanly():
    """A severed recv loop must fail every pending request with
    ConnectionError (the retryable class) — never hang a caller."""
    async with hub_pair() as (server, client):
        assert await client.ping() == "pong"
        faults.configure("hub.recv.dropx1")
        with pytest.raises(ConnectionError):
            await asyncio.wait_for(client.ping(), 10)


# ---------------------------------------------------------------------
# scenario: forced SLO breach -> ONE forensic flight-recorder artifact
# (docs/observability.md "Forensics plane"): a DYN_FAULTS dispatch delay
# blows every TTFT target; the breach storm must write exactly one
# artifact (rate limit), carrying the breaching request's trace slice
# and a deep step-digest window.


async def test_chaos_slo_breach_dumps_one_forensic_artifact(tmp_path):
    import json

    from dynamo_tpu.engine import flight_recorder as flightmod
    from dynamo_tpu.llm.http.metrics import SloTracker
    from dynamo_tpu.utils import tracing

    tracing.clear()
    tracing.enable()
    try:
        engine = make_engine(decode_steps=1)
        # swap in a recorder aimed at the test dir with a cooldown far
        # longer than the wave — the storm must collapse to ONE dump
        engine.flight = flightmod.FlightRecorder(
            capacity=256, cooldown_s=600.0,
            context_fn=engine._flight_context, directory=str(tmp_path),
        )
        slo = SloTracker({"default": {"ttft_s": 1e-06}})  # all breach
        slo.on_breach = engine.flight.on_slo_breach
        engine.subscribe_requests(slo.observe)
        faults.configure("engine.dispatch.delay=0.02")
        outs = await asyncio.wait_for(
            asyncio.gather(
                *(collect(engine, greedy_request(p, 24))
                  for p in PROMPTS * 2)
            ),
            120,
        )
        assert all(f == "length" for _, f in outs)  # chaos, not loss
        arts = sorted(tmp_path.glob("flight_recorder_*.json"))
        assert len(arts) == 1, [a.name for a in arts]
        assert engine.flight.suppressed_total >= 1  # the storm was real
        with open(arts[0]) as f:
            art = json.load(f)
        assert art["trigger"] == "slo_breach"
        rid = art["request_id"]
        assert rid
        # the digest window is deep enough to read the incident's past
        assert len(art["digests"]) >= 32
        kinds = {
            flightmod.digest_to_dict(r)["kind"] for r in art["digests"]
        }
        assert {"prefill", "decode"} <= kinds
        # the merged trace slice is the BREACHING request's story
        evs = [e for e in art["trace"]["traceEvents"] if e["ph"] != "M"]
        assert evs and all(
            e["args"].get("request_id") == rid for e in evs
        )
        assert any(e["name"] == "request" for e in evs)
        # engine-side gauges agree with the artifact
        m = engine.metrics()
        assert m["flight_dumps"] == 1
        assert m["flight_digests"] >= 32
        await engine.close()
    finally:
        tracing.disable()
        tracing.clear()


# ---------------------------------------------------------------------
# scenario: worker death mid-stream -> request-level journaled failover
# (llm/http/failover.py over the REAL data plane). The `dataplane.die`
# fault point (runtime/network.py) severs every connection of the
# serving worker's data plane WITHOUT end/err frames — on the wire
# indistinguishable from a SIGKILLed process — and the frontend must
# resume the stream on the healthy worker with zero duplicated or
# skipped tokens. The real-JaxEngine SSE variant of this proof is
# scripts/failover_chaos.py.


def _arith_next(t: int) -> int:
    return (t * 31 + 7) % 997


def _arith_ref(prompt, n):
    toks, last = [], prompt[-1]
    for _ in range(n):
        last = _arith_next(last)
        toks.append(last)
    return toks


class _DetWorkerEngine:
    """Deterministic continuation-safe stand-in engine served over the
    real data plane: output depends only on the prompt tail (a greedy
    model's contract), so serving prompt+emitted resumes the exact
    sequence. Paced so a kill lands while frames are in flight."""

    def __init__(self, pace_s: float = 0.01):
        self.pace_s = pace_s

    async def generate(self, ctx):
        pre = ctx.payload

        async def stream():
            last = pre["token_ids"][-1]
            for _ in range(pre["stop_conditions"]["max_tokens"]):
                if self.pace_s:
                    await asyncio.sleep(self.pace_s)
                last = _arith_next(last)
                yield {"token_ids": [last]}
            yield {"token_ids": [], "finish_reason": "length"}

        return stream()


@contextlib.asynccontextmanager
async def _failover_fleet(n_workers=2, pace_s=0.01, cfg=None):
    """Hub + n real workers on the data plane + a frontend FailoverEngine
    over the discovery client (the exact ModelWatcher wiring)."""
    from dynamo_tpu.llm.http.discovery import RouterEngine
    from dynamo_tpu.llm.http.failover import FailoverEngine
    from dynamo_tpu.runtime.distributed import DistributedRuntime

    from .helpers import hub_server

    async with hub_server() as hub:
        addr = f"127.0.0.1:{hub.port}"
        drts = []
        try:
            for _ in range(n_workers):
                drt = await DistributedRuntime.from_settings(hub_addr=addr)
                drts.append(drt)
                ep = drt.namespace("cf").component("be").endpoint("generate")
                await ep.serve_engine(_DetWorkerEngine(pace_s))
            fe = await DistributedRuntime.from_settings(hub_addr=addr)
            drts.append(fe)
            client = await (
                fe.namespace("cf").component("be").endpoint("generate").client()
            )
            for _ in range(200):
                if len(client.instance_ids()) >= n_workers:
                    break
                await asyncio.sleep(0.05)
            assert len(client.instance_ids()) >= n_workers
            yield FailoverEngine(
                RouterEngine(client, "round_robin"),
                client=client, drt=fe, cfg=cfg,
            )
        finally:
            for drt in drts:
                try:
                    await drt.shutdown()
                except Exception:  # noqa: BLE001 — teardown best-effort
                    pass


async def _collect_failover(eng, prompt, osl):
    pre = greedy_request(prompt, max_tokens=osl)
    pre.stop_conditions.ignore_eos = True
    ctx = Context(pre.to_dict())
    toks, finish = [], None
    async for f in await eng.generate(ctx):
        toks.extend(f.get("token_ids") or [])
        if f.get("finish_reason"):
            finish = f["finish_reason"]
    return toks, finish


async def test_chaos_worker_death_midstream_failover_byte_identical():
    """DYN_FAULTS-style worker.die mid-stream: the greedy stream
    completes byte-identical to the no-fault run — the journal replay
    neither repeats nor gaps a token (ISSUE 15 acceptance)."""
    from dynamo_tpu.llm.http import failover as fomod

    fomod.reset_stats()
    prompt, osl = [5, 17, 42, 9], 12
    want = _arith_ref(prompt, osl)
    async with _failover_fleet(n_workers=2) as eng:
        # no-fault reference over the very same fleet
        ref, finish = await asyncio.wait_for(
            _collect_failover(eng, prompt, osl), 30
        )
        assert ref == want and finish == "length"
        # arm the kill: the 5th streamed frame severs the serving
        # worker's whole data plane (all conns aborted, no err frames)
        faults.configure("dataplane.die.fail@5x1")
        toks, finish = await asyncio.wait_for(
            _collect_failover(eng, prompt, osl), 60
        )
    assert toks == want, "failover resume repeated or gapped a token"
    assert finish == "length"
    assert counters.get("failover_replays_total") == 1.0
    assert counters.get("failover_recovered_total") == 1.0
    rec = fomod.recent_replays()[-1]
    assert rec["reason"] == "transport"
    assert 0 < rec["emitted_at_break"] < osl
    assert rec["replay_prompt_tokens"] == len(prompt) + rec["emitted_at_break"]
    assert rec["gap_s"] is not None


async def test_chaos_mass_worker_death_sheds_typed_not_replay_storm():
    """Mass worker death: every worker's data plane dies under a wave of
    live streams. The failover plane must degrade into the PR-6 typed
    shed ladder — over-cap replays shed with PoolExhaustedError
    (503 + Retry-After), the rest surface typed transport errors —
    and every request RESOLVES; nothing hangs, no unbounded replays."""
    from dynamo_tpu.llm.http.failover import FailoverConfig
    from dynamo_tpu.llm.protocols.common import PoolExhaustedError

    n_req = 6
    cfg = FailoverConfig(
        max_retries=1, max_concurrent=1, shed_retry_after_s=1.0
    )
    async with _failover_fleet(n_workers=2, pace_s=0.02, cfg=cfg) as eng:
        # unlimited count from the 8th frame on: the first fire kills
        # one worker's plane, the next frame on the survivor kills the
        # other — total fleet death while all streams are mid-flight
        faults.configure("dataplane.die.fail@8")

        async def one(i):
            try:
                toks, fin = await _collect_failover(eng, [3 + i, 9], 10)
                return "ok"
            except PoolExhaustedError as exc:
                assert exc.retry_after_s > 0  # the 503 ladder's hint
                return "shed"
            except (ConnectionError, RuntimeError):
                return "error"  # typed transport surface, not a hang

        outs = await asyncio.wait_for(
            asyncio.gather(*(one(i) for i in range(n_req))), 60
        )
    assert len(outs) == n_req  # every stream resolved
    assert "ok" not in outs, outs  # the whole fleet was dead
    assert outs.count("shed") >= 1, (
        f"no typed storm shed: {outs}, "
        f"shed={counters.get('failover_storm_shed_total')}"
    )
    assert counters.get("failover_storm_shed_total") >= 1.0
    # the retry budget bounds replays per request; the concurrency cap
    # (proven in tests/test_failover.py) bounds them in flight
    assert counters.get("failover_replays_total") <= float(n_req)
