"""Engine e2e tests (CPU, tiny model): continuous batching, prefix cache,
preemption, KV events, and the full HTTP-shaped pipeline.

Oracle: the jitted engine under concurrency must reproduce the single-step
manual forward loop (greedy), mirroring the reference's strategy of testing
distributed graphs against echo/counting engines (SURVEY.md §4) — except our
engine is real, so the oracle is the model itself.
"""

import asyncio

import jax
import jax.numpy as jnp
import numpy as np

from dynamo_tpu.engine import EngineConfig, JaxEngine
from dynamo_tpu.llm.protocols.common import (
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)
from dynamo_tpu.models import config as cfgmod, llama
from dynamo_tpu.runtime.pipeline.context import Context

CFG = cfgmod.get_config("tiny")


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


async def collect(engine, pre):
    frames = [f async for f in await engine.generate(Context(pre.to_dict()))]
    tokens = [t for f in frames for t in f.get("token_ids") or []]
    finish = frames[-1].get("finish_reason")
    return tokens, finish, frames


def manual_greedy(prompt, n):
    """Reference loop: direct forward calls, one token at a time."""
    params = llama.init_params(CFG, jax.random.PRNGKey(0), dtype=jnp.float32)
    kv = llama.init_kv_cache(CFG, 512, dtype=jnp.float32)
    toks = list(prompt)
    out = []
    for step in range(n):
        t = len(toks)
        if step == 0:
            tok_in = np.asarray([toks], np.int32)
            pos = np.arange(t)[None]
            wslots = np.arange(8, 8 + t)
        else:
            tok_in = np.asarray([[toks[-1]]], np.int32)
            pos = np.asarray([[t - 1]])
            wslots = np.asarray([8 + t - 1])
        smat = np.arange(8, 8 + t)[None]
        hidden, kv = llama.forward(
            params, CFG.with_(dtype="float32"), jnp.asarray(tok_in),
            jnp.asarray(pos, jnp.int32), kv,
            jnp.asarray(wslots, jnp.int32), jnp.asarray(smat, jnp.int32),
        )
        lg = llama.logits(params, CFG, hidden[:, -1])
        nxt = int(jnp.argmax(lg[0]))
        toks.append(nxt)
        out.append(nxt)
    return out


async def test_single_request_matches_manual_loop():
    engine = make_engine()
    prompt = [5, 17, 42, 9, 88]
    tokens, finish, _ = await collect(engine, greedy_request(prompt, max_tokens=6))
    assert finish == "length"
    assert tokens == manual_greedy(prompt, 6)
    await engine.close()


async def test_concurrent_requests_batch_and_isolate():
    engine = make_engine()
    prompts = [[5, 17, 42], [9, 88, 3, 21], [60, 14], [7, 7, 7, 7, 7]]
    expected = [manual_greedy(p, 5) for p in prompts]
    results = await asyncio.gather(
        *(collect(engine, greedy_request(p, max_tokens=5)) for p in prompts)
    )
    for (tokens, finish, _), exp in zip(results, expected):
        assert finish == "length"
        assert tokens == exp
    await engine.close()


async def test_prefix_cache_hit_and_events():
    events = []
    engine = make_engine()
    engine.subscribe_events(events.append)
    prompt = list(range(10, 30))  # 20 tokens = 2 full pages + tail
    t1, _, frames1 = await collect(engine, greedy_request(prompt, max_tokens=4))
    assert frames1[0]["meta"]["prefix_cached_tokens"] == 0
    stored = [e for e in events if e["type"] == "stored"]
    assert stored and all("block_hash" in b for e in stored for b in e["blocks"])

    # same prompt again: the two full prompt pages must be reused
    t2, _, frames2 = await collect(engine, greedy_request(prompt, max_tokens=4))
    assert frames2[0]["meta"]["prefix_cached_tokens"] == 16
    assert t2 == t1
    m = engine.metrics()
    assert m["prefix_cache_hit_rate"] > 0
    await engine.close()


async def test_eos_stop():
    engine = make_engine()
    prompt = [5, 17, 42, 9, 88]
    first = manual_greedy(prompt, 1)[0]
    pre = greedy_request(prompt, max_tokens=16, stop_token_ids=[first])
    tokens, finish, _ = await collect(engine, pre)
    assert finish == "stop"
    assert tokens == [first]  # eos emitted then stop
    await engine.close()


async def test_preemption_under_page_pressure():
    # 15 usable pages, two long-running sequences => someone gets preempted
    engine = make_engine(num_pages=16, max_model_len=96, max_batch_size=2)
    prompts = [list(range(20, 52)), list(range(60, 92))]  # 32 tokens each
    expected = [manual_greedy(p, 24) for p in prompts]
    results = await asyncio.gather(
        *(collect(engine, greedy_request(p, max_tokens=24)) for p in prompts)
    )
    for (tokens, finish, _), exp in zip(results, expected):
        assert finish == "length"
        assert tokens == exp
    await engine.close()


async def test_cancellation_mid_stream():
    engine = make_engine()
    ctx = Context(greedy_request([5, 17, 42], max_tokens=100).to_dict())
    stream = await engine.generate(ctx)
    got = 0
    async for frame in stream:
        got += 1
        if got == 3:
            ctx.stop_generating()
        if frame.get("finish_reason"):
            assert frame["finish_reason"] == "cancelled"
            break
    assert got >= 3
    await engine.close()


async def test_waiting_queue_when_slots_full():
    engine = make_engine(max_batch_size=2)
    prompts = [[i, i + 1, i + 2] for i in range(5, 45, 8)]  # 5 requests, 2 slots
    results = await asyncio.gather(
        *(collect(engine, greedy_request(p, max_tokens=4)) for p in prompts)
    )
    for p, (tokens, finish, _) in zip(prompts, results):
        assert finish == "length"
        assert tokens == manual_greedy(p, 4)
    await engine.close()


async def test_prompt_too_long_rejected():
    engine = make_engine(max_model_len=32)
    try:
        await engine.generate(Context(greedy_request(list(range(40))).to_dict()))
        raise AssertionError("expected ValueError")
    except ValueError:
        pass
    await engine.close()


async def test_full_pipeline_http_shape():
    """preprocessor -> backend -> JaxEngine, chat-completion shaped."""
    from dynamo_tpu.llm.backend import Backend
    from dynamo_tpu.llm.model_card import ModelDeploymentCard
    from dynamo_tpu.llm.preprocessor import OpenAIPreprocessor
    from dynamo_tpu.llm.protocols.openai import ChatCompletionRequest
    from dynamo_tpu.runtime.pipeline.engine import link

    from .fixtures import tiny_model_dir

    card = ModelDeploymentCard.from_local_path(tiny_model_dir(), name="tiny")
    engine = make_engine(model=CFG.with_(vocab_size=512), max_model_len=256)
    pipeline = link(OpenAIPreprocessor(card), Backend.from_card(card), engine)
    req = ChatCompletionRequest.from_body(
        {
            "model": "tiny",
            "messages": [{"role": "user", "content": "the quick brown fox"}],
            "max_tokens": 8,
        }
    )
    chunks = [c async for c in await pipeline.generate(Context(req))]
    assert chunks, "no output"
    finishes = [
        c["choices"][0].get("finish_reason")
        for c in chunks
        if c.get("choices")
    ]
    assert any(f in ("length", "stop") for f in finishes)
    await engine.close()


async def test_prompt_exceeding_kv_pool_rejected():
    """A prompt that could never be paged must be rejected, not hang."""
    engine = make_engine(num_pages=8, max_model_len=2000)
    try:
        await engine.generate(Context(greedy_request(list(range(2, 80))).to_dict()))
        raise AssertionError("expected ValueError")
    except ValueError as e:
        assert "KV pool" in str(e)
    await engine.close()


async def test_tp2_pallas_matches_gather():
    """The shard_map'd pallas decode kernel under tp=2 (interpret mode on
    the virtual CPU mesh) must reproduce the gather oracle bit-exactly in
    f32 — the flagship multi-chip path must not change results."""
    from dynamo_tpu.parallel.mesh import MeshConfig

    prompt = [5, 17, 42, 9, 88, 3, 14]
    outs = {}
    for backend in ("gather", "pallas"):
        engine = make_engine(
            mesh=MeshConfig(tp=2), attn_backend=backend, decode_steps=4
        )
        tokens, finish, _ = await collect(
            engine, greedy_request(prompt, max_tokens=8)
        )
        outs[backend] = tokens
        assert finish == "length"
        await engine.close()
    assert outs["pallas"] == outs["gather"], outs


def test_auto_backend_warns_on_tpu_gather_fallback(monkeypatch, caplog):
    """attn_backend='auto' must WARN loudly when a TPU mesh silently
    gets gather attention: dp>1 in one engine
    cannot run the fused write kernel soundly."""
    import logging

    import jax

    from dynamo_tpu.engine import EngineConfig, JaxEngine
    from dynamo_tpu.parallel.mesh import MeshConfig

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    if len(jax.devices()) < 2:
        import pytest

        pytest.skip("needs 2 devices")
    with caplog.at_level(logging.WARNING, logger="dynamo_tpu.engine"):
        engine = JaxEngine(
            EngineConfig(
                model="tiny", dtype="float32", mesh=MeshConfig(dp=2),
                page_size=8, num_pages=32, max_batch_size=2,
                max_model_len=64, prefill_chunk=16,
            ),
            devices=jax.devices()[:2],
        )
    assert not engine._attn_pallas
    assert any(
        "falls back to GATHER" in r.message for r in caplog.records
    ), "no gather-fallback warning emitted"


async def test_bucketed_decode_dispatch_small_load():
    """With few live streams in a big-slot engine, decode dispatches at
    a power-of-two bucket (not max_batch); outputs match the full-width
    oracle exactly (burst TTFT/ITL fix for paced arrivals)."""
    import asyncio

    ref = make_engine(max_batch_size=4)
    prompts = [[5, 17, 42, 9], [30, 31, 32], [7, 7, 7, 7, 7]]
    refs = []
    for p in prompts:
        toks, _, _ = await collect(ref, greedy_request(p, max_tokens=6))
        refs.append(toks)
    await ref.close()

    engine = make_engine(max_batch_size=32)
    # 1 then 3 concurrent: dispatch widths 8 (never 32)
    a, _, _ = await collect(engine, greedy_request(prompts[0], max_tokens=6))
    assert a == refs[0]
    outs = await asyncio.gather(*(
        collect(engine, greedy_request(p, max_tokens=6)) for p in prompts
    ))
    for (toks, _, _), want in zip(outs, refs):
        assert toks == want
    # seeded path (ext decode family) through a partial bucket
    def seeded():
        return PreprocessedRequest(
            token_ids=list(prompts[0]),
            stop_conditions=StopConditions(max_tokens=6, ignore_eos=True),
            sampling_options=SamplingOptions(temperature=1.0, seed=77),
        )

    s1, _, _ = await collect(engine, seeded())
    s2, _, _ = await collect(engine, seeded())
    assert len(s1) == 6 and s1 == s2
    await engine.close()


async def test_engine_phase_stats_and_first_meta_timing():
    """Engine-side accounting: phase counters advance with dispatches and
    the first frame's meta carries the submit->dispatch latency split
    (the bench's engine-side TTFT/phase source)."""
    engine = make_engine()
    ps0 = engine.phase_stats
    pre = greedy_request([3, 14, 15, 92, 65], max_tokens=6)
    frames = [f async for f in await engine.generate(Context(pre.to_dict()))]
    metas = [f.get("meta") for f in frames if f.get("meta")]
    assert metas, "first frame meta missing"
    m = metas[0]
    assert m.get("engine_ttft_s") is not None and m["engine_ttft_s"] >= 0
    assert m.get("queue_wait_s") is not None and m["queue_wait_s"] >= 0
    assert m["engine_ttft_s"] >= m["queue_wait_s"]
    ps1 = engine.phase_stats
    assert ps1["prefill_tokens"] - ps0["prefill_tokens"] >= 5
    assert ps1["prefill_dispatch_s"] > ps0["prefill_dispatch_s"]
    assert ps1["decode_tokens"] > ps0["decode_tokens"]
    assert ps1["decode_dispatch_s"] > ps0["decode_dispatch_s"]
    # the step pipeline books an overlapped fetch (another dispatch was
    # already queued while it ran) under pipeline_overlap_s INSTEAD of
    # decode_sync_s — the sync wall must land in exactly one of the two
    assert (
        ps1["decode_sync_s"] + ps1["pipeline_overlap_s"]
        > ps0["decode_sync_s"] + ps0["pipeline_overlap_s"]
    )
    await engine.close()


def _spy_decode_builds(engine) -> list:
    """Every `_build_decode` call as (prompts still prefilling, rows
    decode-ready, the most tokens a ready row has, did it build)."""
    seen, build = [], engine._build_decode

    def spy():
        ready = [s for s in engine.slots if s is not None and not s.prefilling]
        state = (len(engine._prefilling), len(ready),
                 max((s.generated for s in ready), default=0))
        out = build()
        seen.append(state + (out is not None,))
        return out

    engine._build_decode = spy
    return seen


async def test_decode_ready_gate_holds_a_pure_admission_wave():
    """A short prompt beside one of four chunks, nothing decoding yet:
    the short one's first token leaves with its prefill, and no decode
    is dispatched until the long one is ready too, so the first decode
    dispatch carries the whole wave."""
    engine = make_engine()
    seen = _spy_decode_builds(engine)
    outs = await asyncio.gather(
        collect(engine, greedy_request([5, 6, 7], max_tokens=6)),
        collect(engine, greedy_request(list(range(1, 101)), max_tokens=6)),
    )
    await engine.close()
    assert all(len(t) == 6 and fin == "length" for t, fin, _ in outs)
    held = [s for s in seen if s[0] and s[1]]
    assert held and all(s[2] == 1 and not s[3] for s in held), seen
    assert next(s for s in seen if s[3])[:2] == (0, 2), seen


async def test_decode_ready_gate_never_holds_a_stream_mid_decode():
    """A prompt of four chunks arriving beside a stream that is past its
    first token: every decode build asked for while it prefills is
    dispatched, one between chunks, so the stream keeps its cadence."""
    engine = make_engine()
    running = asyncio.create_task(
        collect(engine, greedy_request([5, 6, 7], max_tokens=80)))
    while not any(s is not None and s.generated > 1 for s in engine.slots):
        await asyncio.sleep(0.01)
    seen = _spy_decode_builds(engine)
    late, fin, _ = await collect(
        engine, greedy_request(list(range(1, 101)), max_tokens=4))
    tokens, _, _ = await running
    await engine.close()
    assert len(tokens) == 80 and len(late) == 4 and fin == "length"
    during = [s for s in seen if s[0] and s[1]]
    assert len(during) >= 2 and all(s[2] > 1 and s[3] for s in during), seen
