"""Fault-tolerance spine tests (CPU, tiny model): end-to-end deadlines,
the engine watchdog + degrade ladder, typed capacity errors, and the
client-disconnect kill path all the way into the engine's cancellation
sweep (slot + KV pages freed).

Companion suites: tests/test_faults.py (the injection registry itself),
tests/test_chaos.py (DYN_FAULTS scenario runs the CI chaos job drives),
tests/test_resilience.py (breakers/retries). See docs/robustness.md.
"""

import asyncio
import json
import os
import time

import pytest

from dynamo_tpu.engine import EngineConfig, JaxEngine
from dynamo_tpu.engine.degrade import RUNGS, DegradeLadder
from dynamo_tpu.llm.protocols.common import (
    DeadlineExceededError,
    PoolExhaustedError,
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)
from dynamo_tpu.models import config as cfgmod
from dynamo_tpu.runtime.pipeline.context import Context
from dynamo_tpu.utils import counters, faults

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


def greedy_request(prompt, max_tokens=8, **stop_kw) -> PreprocessedRequest:
    return PreprocessedRequest(
        token_ids=list(prompt),
        stop_conditions=StopConditions(max_tokens=max_tokens, **stop_kw),
        sampling_options=SamplingOptions(greedy=True),
    )


async def collect(engine, pre, deadline=None):
    ctx = Context(pre.to_dict())
    if deadline is not None:
        ctx.metadata["deadline"] = deadline
    frames = [f async for f in await engine.generate(ctx)]
    tokens = [t for f in frames for t in f.get("token_ids") or []]
    return tokens, frames[-1].get("finish_reason"), frames


# ------------------------------------------------------- degrade ladder


def test_degrade_ladder_walk_reprobe_and_permanent():
    t = [0.0]
    lad = DegradeLadder(reprobe_s=10.0, clock=lambda: t[0])
    assert not lad.any_tripped()
    # walk trips in documented order: most speculative machinery first
    assert lad.trip_next("wd") == "step_pipeline"
    assert lad.trip_next("wd") == "spec"
    assert lad.trip_next("wd") == "mixed"
    assert lad.trip_next("wd") == "decode_scan"
    assert lad.trip_next("wd") is None, "fully shed: nothing left"
    assert lad.degrades_total == 4
    assert all(lad.state()[f"degraded_{r}"] == 1 for r in RUNGS)

    # re-probe: rungs recover lazily at their gate checks
    t[0] = 10.0
    assert not lad.disabled("step_pipeline")
    assert lad.recoveries_total == 1
    assert lad.state()["degraded_step_pipeline"] == 0

    # permanent trips never re-probe
    lad.trip("mixed", "dispatch failed", permanent=True)
    t[0] = 1000.0
    assert lad.disabled("mixed")
    lad.recover_all()
    assert lad.disabled("mixed"), "recover_all spares permanent trips"
    assert not lad.disabled("spec")


def test_degrade_ladder_retrip_extends_timer_not_counter():
    t = [0.0]
    lad = DegradeLadder(reprobe_s=5.0, clock=lambda: t[0])
    lad.trip("spec", "a")
    t[0] = 4.0
    lad.trip("spec", "b")  # extends to t=9
    assert lad.degrades_total == 1, "re-trip is not a new degrade"
    t[0] = 6.0
    assert lad.disabled("spec"), "timer was extended"
    t[0] = 9.0
    assert not lad.disabled("spec")


# ------------------------------------------------------------ deadlines


async def test_deadline_expired_at_submit_sheds_with_429_type():
    engine = make_engine()
    with pytest.raises(DeadlineExceededError):
        await collect(
            engine, greedy_request([5, 17, 42]), deadline=time.time() - 1.0
        )
    assert engine.phase_stats["deadline_shed"] == 1
    assert engine.metrics()["deadline_shed"] == 1
    await engine.close()


async def test_deadline_expires_in_admission_queue_resolves_timeout():
    """A queued request whose budget dies waiting leaves with a
    zero-token `timeout` finish BEFORE touching the device."""
    engine = make_engine(max_batch_size=1)
    long_ctx = Context(greedy_request([5, 17, 42], max_tokens=100).to_dict())
    long_stream = await engine.generate(long_ctx)
    # the slot is taken and its programs are compiled (a compile beside
    # five other workers can hold the loop longer than the budget below,
    # which then dies at submission, not in the queue)
    await long_stream.__anext__()
    # this one queues and its 0.2s budget dies there
    waiter = asyncio.create_task(
        collect(engine, greedy_request([9, 8, 7]), deadline=time.time() + 0.2)
    )
    tokens, finish, _ = await asyncio.wait_for(waiter, 60)
    assert finish == "timeout"
    assert tokens == [], "shed before any device work"
    assert engine.phase_stats["deadline_shed"] == 1
    long_ctx.stop_generating()
    async for f in long_stream:
        if f.get("finish_reason"):
            break
    await engine.close()


async def test_deadline_mid_flight_resolves_timeout():
    """An admitted request past deadline is cancelled by the sweep."""
    engine = make_engine()
    tokens, finish, _ = await collect(
        engine, greedy_request([5, 17, 42], max_tokens=5000),
        deadline=time.time() + 0.25,
    )
    # tiny-model CPU compile alone exceeds the budget, so the sweep
    # fires during the serve; whatever emitted before stays delivered
    assert finish == "timeout"
    assert engine.phase_stats["deadline_timeouts"] == 1
    await engine.close()


async def test_config_default_timeout_applies_without_header():
    engine = make_engine(request_timeout_s=0.25)
    tokens, finish, _ = await collect(
        engine, greedy_request([5, 17, 42], max_tokens=5000)
    )
    assert finish == "timeout"
    await engine.close()


async def test_prefill_only_pool_exhaustion_typed_503():
    """The (formerly hardcoded-60s) page-wait budget is a config knob
    and exhaustion surfaces as PoolExhaustedError (HTTP 503)."""
    engine = make_engine(prefill_wait_s=0.2)
    faults.configure("engine.reserve.fail")  # allocator never yields
    t0 = time.perf_counter()
    with pytest.raises(PoolExhaustedError):
        await engine.prefill_only(greedy_request([5, 17, 42, 9]))
    assert time.perf_counter() - t0 < 30, "must honor the budget, not 60s"
    await engine.close()


async def test_prefill_only_wait_shrinks_to_request_deadline():
    engine = make_engine(prefill_wait_s=60.0)
    faults.configure("engine.reserve.fail")
    ctx = Context({})
    ctx.metadata["deadline"] = time.time() + 0.2
    t0 = time.perf_counter()
    with pytest.raises(PoolExhaustedError):
        await engine.prefill_only(greedy_request([5, 17, 42, 9]), ctx=ctx)
    assert time.perf_counter() - t0 < 30
    await engine.close()


# ----------------------------------------------- watchdog + recovery


async def test_watchdog_fires_dumps_artifact_degrades_and_recovers(tmp_path):
    """Acceptance: watchdog demonstrably fires on an injected slow
    dispatch — trace artifact written, degrade rung applied, recovery
    observed, all visible in metrics — and the engine serves
    byte-identical greedy streams after the ladder re-probes."""
    plain = make_engine()
    prompt = [5, 17, 42, 9, 88]
    want, want_finish, _ = await collect(plain, greedy_request(prompt))
    await plain.close()

    # the watchdog cannot tell a compile from a hang (config.py says so),
    # and every step variant of a new engine compiles on first use: the
    # budget sits above a tiny-model CPU compile, so the ONLY stall past
    # it is the injected one and the ladder sheds exactly one rung. The
    # re-probe window outlasts the rest of the faulted request, so the
    # rung is seen held first and recovers only in the second request.
    engine = make_engine(
        watchdog_dispatch_s=2.0,
        degrade_reprobe_s=6.0,
        crash_dir=str(tmp_path),
    )
    # slow the FIRST decode dispatch well past the watchdog budget
    faults.configure("engine.dispatch.delay=3.5@1x1")
    got, finish, _ = await asyncio.wait_for(
        collect(engine, greedy_request(prompt)), 120
    )
    assert got == want and finish == want_finish, (
        "a degraded engine must stay byte-identical on greedy streams"
    )
    m = engine.metrics()
    assert m["watchdog_fired"] == 1
    assert m["degrades_total"] == 1
    assert m["degraded_step_pipeline"] == 1 and m["recoveries_total"] == 0
    assert engine.last_crash_artifact and os.path.exists(
        engine.last_crash_artifact
    )
    art = json.load(open(engine.last_crash_artifact))
    assert art["op"] == "decode.dispatch"
    assert art["rung_tripped"] == "step_pipeline"
    assert "phase_stats" in art and "trace" in art
    assert art["stalled_s"] >= 2.0

    # recovery: wait out the re-probe window, run again — the engine's
    # own gate check (`_pipe_on`) re-opens the rung during this request
    await asyncio.sleep(6.0)
    got2, finish2, _ = await collect(engine, greedy_request(prompt))
    assert got2 == want and finish2 == want_finish
    m2 = engine.metrics()
    assert m2["watchdog_fired"] == 1, "the re-probed variant's compile re-tripped"
    assert m2["recoveries_total"] == 1
    assert all(m2[f"degraded_{r}"] == 0 for r in RUNGS), m2
    await engine.close()


async def test_watchdog_off_by_default_no_ops_registered():
    engine = make_engine()
    await collect(engine, greedy_request([5, 17, 42]))
    assert engine._watchdog_task is None
    assert engine._ops == {}
    await engine.close()


# -------------------------------------------- metrics surface contract


async def test_metrics_surface_spine_keys():
    engine = make_engine()
    m = engine.metrics()
    for key in (
        "watchdog_fired", "deadline_shed", "deadline_timeouts",
        "degrades_total", "recoveries_total", "faults_injected",
        *(f"degraded_{r}" for r in RUNGS),
    ):
        assert key in m, key
        assert m[key] == 0
    await engine.close()


# -------------------------- client-disconnect kill path, end to end


async def test_sse_disconnect_reaches_engine_sweep_frees_slot_and_pages():
    """Satellite: a mid-stream SSE drop must reach the engine's
    cancellation sweep and free the sequence's slot and KV pages (until
    now only the HTTP-side kill was tested)."""
    import aiohttp

    from dynamo_tpu.llm.backend import Backend
    from dynamo_tpu.llm.http.service import HttpService
    from dynamo_tpu.llm.model_card import ModelDeploymentCard
    from dynamo_tpu.llm.preprocessor import OpenAIPreprocessor
    from dynamo_tpu.runtime.pipeline.engine import link

    from .fixtures import tiny_model_dir

    card = ModelDeploymentCard.from_local_path(tiny_model_dir(), name="tiny")
    engine = make_engine(model=CFG.with_(vocab_size=512), max_model_len=256)
    svc = HttpService()
    svc.manager.add_chat_model(
        "tiny", link(OpenAIPreprocessor(card), Backend.from_card(card), engine)
    )
    await svc.start("127.0.0.1", 0)
    try:
        async with aiohttp.ClientSession(
            f"http://127.0.0.1:{svc.port}"
        ) as session:
            resp = await session.post(
                "/v1/chat/completions",
                json={
                    "model": "tiny",
                    "messages": [{"role": "user", "content": "the quick brown fox"}],
                    "max_tokens": 4000,
                    "stream": True,
                },
            )
            assert resp.status == 200
            # read a few frames to prove generation is live, then DROP
            # the connection mid-stream (no graceful close)
            got = 0
            async for _line in resp.content:
                got += 1
                if got >= 5:
                    break
            resp.close()
        # the aiohttp handler cancels -> ctx.kill() -> engine sweep must
        # free the slot and release every page ref. Released pages whose
        # blocks are hashed stay CACHED (refs==0, evictable — that's the
        # prefix cache working as designed), so "freed" means every
        # usable page is on the free list or evictable, none pinned.
        usable = engine.num_pages - 1
        for _ in range(200):
            if (
                all(s is None for s in engine.slots)
                and not engine.waiting
                and engine.allocator.num_free == usable
            ):
                break
            await asyncio.sleep(0.05)
        assert all(s is None for s in engine.slots), "slot not freed"
        assert engine.allocator.num_free == usable, "KV pages leaked refs"
        # the freed capacity is genuinely reusable
        tokens, finish, _ = await collect(
            engine, greedy_request([5, 17, 42], max_tokens=4)
        )
        assert finish == "length" and len(tokens) == 4
    finally:
        await svc.stop()
        await engine.close()
