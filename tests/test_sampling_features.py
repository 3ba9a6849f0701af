"""Sampling feature depth: logprobs, frequency/presence/repetition
penalties, per-request seeds — the SamplingOptions surface the reference
forwards into vLLM (reference: lib/llm/src/protocols/common.rs:248),
implemented natively in the jitted sampler (ops/sampling.py) and the
engine's decode scan."""

import jax
import jax.numpy as jnp
import numpy as np

from dynamo_tpu.engine import EngineConfig, JaxEngine
from dynamo_tpu.llm.protocols.common import (
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)
from dynamo_tpu.models import config as cfgmod
from dynamo_tpu.ops.sampling import apply_penalties, sample_tokens
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


async def collect(engine, pre):
    frames = [f async for f in await engine.generate(Context(pre.to_dict()))]
    tokens = [t for f in frames for t in f.get("token_ids") or []]
    return tokens, frames


def request(prompt, max_tokens=8, **so_kw):
    return PreprocessedRequest(
        token_ids=list(prompt),
        stop_conditions=StopConditions(max_tokens=max_tokens, ignore_eos=True),
        sampling_options=SamplingOptions(**so_kw),
    )


# ------------------------------------------------------------- unit level


def test_apply_penalties_math():
    logits = jnp.asarray([[2.0, -1.0, 0.5, 3.0]])
    counts = jnp.asarray([[2, 1, 0, 0]], jnp.int8)
    out = apply_penalties(
        logits, counts,
        freq_pen=jnp.asarray([0.5]),
        pres_pen=jnp.asarray([0.25]),
        rep_pen=jnp.asarray([2.0]),
    )
    # token 0: 2.0 - 0.5*2 - 0.25 = 0.75, seen & positive -> /2 = 0.375
    # token 1: -1.0 - 0.5 - 0.25 = -1.75, seen & negative -> *2 = -3.5
    # tokens 2,3: unseen, untouched
    np.testing.assert_allclose(
        np.asarray(out[0]), [0.375, -3.5, 0.5, 3.0], rtol=1e-6
    )


def test_sample_tokens_logprobs_greedy():
    logits = jnp.asarray([[0.0, 2.0, 1.0], [5.0, 0.0, 0.0]])
    ids, lps = sample_tokens(
        logits, jax.random.PRNGKey(0),
        jnp.zeros(2), jnp.zeros(2, jnp.int32), jnp.ones(2),
        all_greedy=True, return_logprobs=True,
    )
    assert list(np.asarray(ids)) == [1, 0]
    expect = jax.nn.log_softmax(logits, axis=-1)
    np.testing.assert_allclose(
        np.asarray(lps), [expect[0, 1], expect[1, 0]], rtol=1e-5
    )


def test_penalties_are_pre_logprob_only():
    """Reported logprobs come from the RAW distribution even when
    penalties reshape the sampling distribution."""
    logits = jnp.asarray([[3.0, 2.9, 0.0]])
    counts = jnp.zeros((1, 3), jnp.int8).at[0, 0].set(5)
    ids, lps = sample_tokens(
        logits, jax.random.PRNGKey(0),
        jnp.zeros(1), jnp.zeros(1, jnp.int32), jnp.ones(1),
        all_greedy=True, return_logprobs=True,
        counts=counts,
        freq_pen=jnp.asarray([10.0]), pres_pen=jnp.asarray([0.0]),
        rep_pen=jnp.asarray([1.0]),
    )
    assert int(ids[0]) == 1  # token 0 penalized away
    expect = float(jax.nn.log_softmax(logits, axis=-1)[0, 1])
    np.testing.assert_allclose(float(lps[0]), expect, rtol=1e-5)


# ----------------------------------------------------------- engine level


async def test_engine_logprobs_stream():
    engine = make_engine()
    tokens, frames = await collect(
        engine, request([5, 6, 7], max_tokens=5, greedy=True, logprobs=True)
    )
    assert len(tokens) == 5
    token_frames = [f for f in frames if f.get("token_ids")]
    lps = [lp for f in token_frames for lp in f["log_probs"]]
    assert len(lps) == 5
    assert all(isinstance(lp, float) and lp <= 0.0 for lp in lps)
    np.testing.assert_allclose(
        token_frames[-1]["cum_log_probs"], sum(lps), rtol=1e-5
    )
    # without the flag, frames stay lean
    _, frames2 = await collect(
        engine, request([5, 6, 7], max_tokens=3, greedy=True)
    )
    assert all(f.get("log_probs") is None for f in frames2)
    await engine.close()


async def test_engine_logprobs_match_manual_forward():
    from dynamo_tpu.models import llama

    engine = make_engine()
    prompt = [9, 10, 11, 12]
    tokens, frames = await collect(
        engine, request(prompt, max_tokens=3, greedy=True, logprobs=True)
    )
    lps = [lp for f in frames for lp in f.get("log_probs") or []]
    assert len(lps) == len(tokens) == 3

    # manual: same params, full-context forward per step
    params = llama.init_params(CFG, jax.random.PRNGKey(0), dtype=jnp.float32)
    ctx = list(prompt)
    for tok, lp in zip(tokens, lps):
        kv = llama.init_kv_cache(CFG, 256, dtype=jnp.float32)
        t = len(ctx)
        smat = jnp.arange(8, 8 + t, dtype=jnp.int32)[None, :]
        hidden, _ = llama.forward(
            params, CFG,
            jnp.asarray([ctx], jnp.int32),
            jnp.arange(t, dtype=jnp.int32)[None, :],
            kv, smat.reshape(-1), smat,
        )
        lg = llama.logits(params, CFG, hidden[0, -1])
        want_tok = int(jnp.argmax(lg))
        want_lp = float(jax.nn.log_softmax(lg)[want_tok])
        assert tok == want_tok
        np.testing.assert_allclose(lp, want_lp, rtol=2e-2, atol=1e-3)
        ctx.append(tok)
    await engine.close()


async def test_engine_frequency_penalty_blocks_repeats():
    """A huge frequency penalty under greedy decoding makes every
    generated token distinct from the prompt and from each other."""
    engine = make_engine()
    prompt = [20, 21, 22, 23]
    tokens, _ = await collect(
        engine,
        request(prompt, max_tokens=10, greedy=True, frequency_penalty=100.0),
    )
    assert len(tokens) == 10
    seen = set(prompt)
    for t in tokens:
        assert t not in seen, f"token {t} repeated despite penalty"
        seen.add(t)
    # control: without penalties the tiny random model DOES repeat
    tokens2, _ = await collect(engine, request(prompt, max_tokens=10, greedy=True))
    assert len(set(tokens2) | set(prompt)) < len(tokens2) + len(prompt)
    await engine.close()


async def test_engine_per_request_seed_reproducible():
    engine = make_engine()
    so = dict(temperature=1.0, seed=1234)
    a, _ = await collect(engine, request([3, 4, 5], max_tokens=8, **so))
    b, _ = await collect(engine, request([3, 4, 5], max_tokens=8, **so))
    assert a == b, "same seed + prompt must reproduce"
    c, _ = await collect(
        engine, request([3, 4, 5], max_tokens=8, temperature=1.0, seed=999)
    )
    assert len(c) == 8  # different seed serves fine (and usually differs)
    await engine.close()


async def test_pipeline_chat_logprobs_and_n():
    """HTTP-shaped pipeline: logprobs ride the SSE chunks and fold into
    the aggregate; n=2 produces two indexed choices."""
    from dynamo_tpu.llm.backend import Backend
    from dynamo_tpu.llm.model_card import ModelDeploymentCard
    from dynamo_tpu.llm.preprocessor import OpenAIPreprocessor
    from dynamo_tpu.llm.protocols.openai import (
        ChatCompletionRequest,
        aggregate_chat_stream,
    )
    from dynamo_tpu.runtime.pipeline.engine import link

    from .fixtures import tiny_model_dir

    card = ModelDeploymentCard.from_local_path(tiny_model_dir(), name="tiny")
    engine = make_engine(
        model=CFG.with_(vocab_size=512), max_model_len=256, num_pages=128
    )
    pipeline = link(OpenAIPreprocessor(card), Backend.from_card(card), engine)

    # logprobs on a single greedy choice
    req = ChatCompletionRequest.from_body({
        "model": "tiny",
        "messages": [{"role": "user", "content": "hello there"}],
        "max_tokens": 4,
        "logprobs": True,
        "dyn_ext": {"greed_sampling": True, "ignore_eos": True},
    })
    chunks = [c async for c in await pipeline.generate(Context(req))]
    entries = [
        e
        for c in chunks
        for ch in c.get("choices", [])
        if ch.get("logprobs")
        for e in ch["logprobs"]["content"]
    ]
    assert len(entries) == 4
    assert all(e["logprob"] <= 0.0 and isinstance(e["token"], str) for e in entries)

    async def _replay(items):
        for it in items:
            yield it

    full = await aggregate_chat_stream(_replay(chunks))
    assert len(full["choices"][0]["logprobs"]["content"]) == 4

    # n=2: two indexed choices, both finishing
    req2 = ChatCompletionRequest.from_body({
        "model": "tiny",
        "messages": [{"role": "user", "content": "fan out"}],
        "max_tokens": 3,
        "n": 2,
        "temperature": 1.0,
        "seed": 7,
        "dyn_ext": {"ignore_eos": True},
    })
    chunks2 = [c async for c in await pipeline.generate(Context(req2))]
    full2 = await aggregate_chat_stream(_replay(chunks2))
    assert [c["index"] for c in full2["choices"]] == [0, 1]
    assert all(c["finish_reason"] for c in full2["choices"])
    assert full2["usage"]["completion_tokens"] == 6
    await engine.close()


async def test_generate_after_close_raises():
    """A closed engine must refuse requests, not queue them forever."""
    import pytest

    engine = make_engine()
    tokens, _ = await collect(engine, request([3, 4], max_tokens=2, greedy=True))
    assert len(tokens) == 2
    await engine.close()
    with pytest.raises(RuntimeError, match="closed"):
        await engine.generate(
            Context(request([5, 6], max_tokens=2, greedy=True).to_dict())
        )


async def test_engine_top_logprobs():
    """top_logprobs: per position, the k best alternatives from the raw
    distribution — the sampled greedy token must lead the list."""
    engine = make_engine()
    _, frames = await collect(
        engine,
        request([5, 6, 7], max_tokens=4, greedy=True, logprobs=True,
                top_logprobs=3),
    )
    token_frames = [f for f in frames if f.get("token_ids")]
    # a frame is what one landing brought: each of its tokens is paired
    # with its own log-probability and its own row of alternatives
    per_token = [
        row for f in token_frames
        for row in zip(f["token_ids"], f["log_probs"], f["top_log_probs"],
                       strict=True)
    ]
    assert len(per_token) == 4
    for tok, lp, alts in per_token:
        assert len(alts) == 3
        # alternatives sorted descending; greedy sampled token == argmax
        lps = [a for _, a in alts]
        assert lps == sorted(lps, reverse=True)
        assert alts[0][0] == tok
        np.testing.assert_allclose(alts[0][1], lp, rtol=1e-5)
    await engine.close()


async def test_pipeline_chat_top_logprobs():
    from dynamo_tpu.llm.backend import Backend
    from dynamo_tpu.llm.model_card import ModelDeploymentCard
    from dynamo_tpu.llm.preprocessor import OpenAIPreprocessor
    from dynamo_tpu.llm.protocols.openai import ChatCompletionRequest
    from dynamo_tpu.runtime.pipeline.engine import link

    from .fixtures import tiny_model_dir

    card = ModelDeploymentCard.from_local_path(tiny_model_dir(), name="tiny")
    engine = make_engine(
        model=CFG.with_(vocab_size=512), max_model_len=256, num_pages=128
    )
    pipeline = link(OpenAIPreprocessor(card), Backend.from_card(card), engine)
    req = ChatCompletionRequest.from_body({
        "model": "tiny",
        "messages": [{"role": "user", "content": "alternatives"}],
        "max_tokens": 3,
        "logprobs": True,
        "top_logprobs": 2,
        "dyn_ext": {"greed_sampling": True, "ignore_eos": True},
    })
    chunks = [c async for c in await pipeline.generate(Context(req))]
    entries = [
        e
        for c in chunks
        for ch in c.get("choices", [])
        if ch.get("logprobs")
        for e in ch["logprobs"]["content"]
    ]
    assert len(entries) == 3
    for e in entries:
        assert len(e["top_logprobs"]) == 2
        assert all(
            isinstance(a["token"], str) and a["logprob"] <= 0.0
            for a in e["top_logprobs"]
        )
    await engine.close()


async def test_penalties_survive_preemption():
    """A penalized stream preempted mid-decode (pages exhausted) must,
    after re-admission, still see its full history in the count buffer —
    _count_prompt recounts prompt + generated-so-far from seq.tokens."""
    import asyncio

    engine = make_engine(
        num_pages=20,  # tight: concurrent streams force preemption
        max_batch_size=4,
        max_model_len=96,
        prefill_chunk=16,
        page_size=8,
    )
    prompts = [[10 + 7 * k, 11 + 7 * k, 12 + 7 * k] for k in range(6)]
    results = await asyncio.gather(*(
        collect(
            engine,
            request(p, max_tokens=8, greedy=True, frequency_penalty=100.0),
        )
        for p in prompts
    ))
    for (tokens, _), p in zip(results, prompts):
        assert len(tokens) == 8
        seen = set(p)
        for t in tokens:
            assert t not in seen, f"repeat {t} in {tokens} (prompt {p})"
            seen.add(t)
    await engine.close()


async def test_engine_penalty_and_plain_mix_in_batch():
    """Penalized and plain requests share one decode dispatch."""
    import asyncio

    engine = make_engine()
    r1 = collect(
        engine,
        request([30, 31], max_tokens=6, greedy=True, frequency_penalty=50.0),
    )
    r2 = collect(engine, request([40, 41], max_tokens=6, greedy=True))
    (t1, _), (t2, _) = await asyncio.gather(r1, r2)
    assert len(t1) == 6 and len(t2) == 6
    assert len(set(t1)) == 6  # penalized stream has no repeats
    await engine.close()


async def test_wide_and_negative_seeds_fold_and_reproduce():
    """OpenAI-style seeds outside int32 (2**40) and negative seeds must
    serve (no numpy OverflowError in the decode table build) and stay
    reproducible — admission folds them into [0, 2**31)
    (ADVICE r3: engine.py:1355 / scheduler.py:109)."""
    engine = make_engine()
    for seed in (2**40 + 17, -5):
        a, _ = await collect(
            engine, request([3, 4, 5], max_tokens=6, temperature=1.0, seed=seed)
        )
        b, _ = await collect(
            engine, request([3, 4, 5], max_tokens=6, temperature=1.0, seed=seed)
        )
        assert len(a) == 6 and a == b, f"seed {seed} not reproducible: {a} vs {b}"
    # a wide seed and its int32 fold are the SAME stream (documented fold)
    c, _ = await collect(
        engine,
        request([3, 4, 5], max_tokens=6, temperature=1.0,
                seed=(2**40 + 17) & 0x7FFFFFFF),
    )
    a, _ = await collect(
        engine, request([3, 4, 5], max_tokens=6, temperature=1.0, seed=2**40 + 17)
    )
    assert c == a
    await engine.close()


def test_delta_generator_role_per_choice():
    """n>1 chat streaming: every choice index gets `delta.role` on its
    first chunk, not just the first chunk overall (ADVICE r3)."""
    from dynamo_tpu.llm.protocols.openai import DeltaGenerator

    d = DeltaGenerator("m", kind="chat")
    c0 = d.chunk("a", index=0)
    c1 = d.chunk("b", index=1)
    c0b = d.chunk("c", index=0)
    assert c0["choices"][0]["delta"].get("role") == "assistant"
    assert c1["choices"][0]["delta"].get("role") == "assistant"
    assert "role" not in c0b["choices"][0]["delta"]


async def test_completion_aggregator_keeps_top_logprobs():
    """Non-streaming /v1/completions with logprobs=N must carry the top-N
    alternatives the streaming chunks emit (ADVICE r3: openai.py:346)."""
    from dynamo_tpu.llm.protocols.openai import aggregate_completion_stream

    async def _chunks():
        yield {
            "id": "x", "created": 1, "model": "m",
            "choices": [{
                "index": 0, "text": "hi",
                "logprobs": {
                    "tokens": ["hi"], "token_logprobs": [-0.1],
                    "top_logprobs": [{"hi": -0.1, "yo": -2.0}],
                },
            }],
        }
        yield {
            "id": "x", "created": 1, "model": "m",
            "choices": [{
                "index": 0, "text": "!", "finish_reason": "stop",
                "logprobs": {
                    "tokens": ["!"], "token_logprobs": [-0.2],
                    "top_logprobs": [{"!": -0.2}],
                },
            }],
        }

    full = await aggregate_completion_stream(_chunks())
    lp = full["choices"][0]["logprobs"]
    assert lp["tokens"] == ["hi", "!"]
    assert lp["top_logprobs"] == [{"hi": -0.1, "yo": -2.0}, {"!": -0.2}]


async def test_n_gt_1_stream_never_iterated_cancels_cleanly():
    """If the caller abandons an n>1 stream without iterating it, no
    engine streams were started — the engine drains to idle instead of
    generating until natural stop (ADVICE r3: preprocessor.py:318)."""
    import asyncio

    from dynamo_tpu.llm.backend import Backend
    from dynamo_tpu.llm.model_card import ModelDeploymentCard
    from dynamo_tpu.llm.preprocessor import OpenAIPreprocessor
    from dynamo_tpu.llm.protocols.openai import ChatCompletionRequest
    from dynamo_tpu.runtime.pipeline.engine import link

    from .fixtures import tiny_model_dir

    card = ModelDeploymentCard.from_local_path(tiny_model_dir(), name="tiny")
    engine = make_engine(
        model=CFG.with_(vocab_size=512), max_model_len=256, num_pages=128
    )
    pipeline = link(OpenAIPreprocessor(card), Backend.from_card(card), engine)
    req = ChatCompletionRequest.from_body({
        "model": "tiny",
        "messages": [{"role": "user", "content": "abandoned"}],
        "max_tokens": 64,
        "n": 3,
        "temperature": 1.0,
        "dyn_ext": {"ignore_eos": True},
    })
    stream = await pipeline.generate(Context(req))
    # never iterate `stream`; lazily-created pumps mean nothing started
    del stream
    await asyncio.sleep(0.05)
    m = engine.metrics()
    assert m["request_active_slots"] == 0 and m["num_requests_waiting"] == 0, (
        f"abandoned n>1 request left live sequences: {m}"
    )
    await engine.close()


async def test_n_gt_1_partial_fanout_failure_kills_admitted_siblings():
    """If fork k's admission fails mid-creation, the already-admitted
    forks 0..k-1 must have their contexts killed so the engine stops
    generating for them (r4 review finding)."""
    from dynamo_tpu.llm.backend import Backend
    from dynamo_tpu.llm.model_card import ModelDeploymentCard
    from dynamo_tpu.llm.preprocessor import OpenAIPreprocessor
    from dynamo_tpu.llm.protocols.openai import ChatCompletionRequest
    from dynamo_tpu.runtime.pipeline.engine import link

    from .fixtures import tiny_model_dir

    seen_ctxs = []

    class FlakyEngine:
        """Admits the first two forks, rejects the third."""

        async def generate(self, ctx):
            if len(seen_ctxs) >= 2:
                raise ValueError("admission rejected")
            seen_ctxs.append(ctx)

            async def _gen():
                yield {"token_ids": [1], "tokens": ["x"], "text": "x"}

            return _gen()

    card = ModelDeploymentCard.from_local_path(tiny_model_dir(), name="tiny")
    pipeline = link(OpenAIPreprocessor(card), Backend.from_card(card), FlakyEngine())
    req = ChatCompletionRequest.from_body({
        "model": "tiny",
        "messages": [{"role": "user", "content": "x"}],
        "max_tokens": 4,
        "n": 3,
        "temperature": 1.0,
    })
    stream = await pipeline.generate(Context(req))
    import pytest

    with pytest.raises(ValueError, match="admission rejected"):
        async for _ in stream:
            pass
    assert len(seen_ctxs) == 2
    assert all(c.is_stopped() for c in seen_ctxs), (
        "admitted sibling contexts must be killed on partial fan-out failure"
    )


# ------------------------------------------------- count-buffer saturation


def test_count_buffers_saturate_past_int8_range():
    """The penalty count buffers are int8: a token repeated more than 127
    times in one stream must SATURATE at 127, not wrap negative. A wrap
    flips `seen = cnt > 0` to False and turns every penalty into a
    REWARD for the most-repeated token — the exact failure a 200-repeat
    stream used to hit. Pins both accumulators (per-step bump_counts and
    the admission-time count_tokens prompt scatter) and the penalty
    direction at the saturated count."""
    from dynamo_tpu.ops.sampling import bump_counts, count_tokens

    B, V = 2, 32
    tok = 7
    counts = jnp.zeros((B, V), jnp.int8)
    tokens = jnp.asarray([tok, tok], jnp.int32)
    active = jnp.asarray([True, False])
    step = jax.jit(bump_counts)
    for _ in range(200):  # a 200-repeat stream
        counts = step(counts, tokens, active)
    out = np.asarray(counts)
    assert out[0, tok] == 127, f"wrapped: count={out[0, tok]}"
    assert out[1, tok] == 0  # inactive rows never bump
    assert (out >= 0).all()
    # admission path: a 200-token prompt of one repeated id saturates too
    counts2 = count_tokens(
        jnp.zeros((B, V), jnp.int8),
        jnp.asarray(0),
        jnp.full((200,), tok, jnp.int32),
    )
    assert np.asarray(counts2)[0, tok] == 127
    # and count_tokens ON TOP of an almost-saturated row stays pinned
    counts3 = count_tokens(
        counts, jnp.asarray(0), jnp.full((200,), tok, jnp.int32)
    )
    assert np.asarray(counts3)[0, tok] == 127
    # penalties at the saturated count still PENALIZE (never boost)
    logits = jnp.zeros((B, V))
    pen = apply_penalties(
        logits, counts,
        freq_pen=jnp.asarray([0.5, 0.5]),
        pres_pen=jnp.asarray([0.5, 0.5]),
        rep_pen=jnp.asarray([1.5, 1.5]),
    )
    assert float(pen[0, tok]) < float(logits[0, tok])
    assert float(pen[0, tok + 1]) == 0.0  # untouched elsewhere


async def test_engine_200_repeat_stream_counts_stay_saturated():
    """End-to-end regression for the int8 count wrap, driven past the
    wrap point: a stream whose token id 99 occurs 150 times (prompt
    scatter) plus decode steps. Saturated at 127, a huge frequency
    penalty keeps 99 suppressed for the whole stream; a wrapped count
    (-106) would flip the penalty into a +boost and greedy would emit 99
    every step. Also reads the count buffer back: no negative entries."""
    import asyncio

    engine = make_engine(max_model_len=256, max_batch_size=2)
    tok = 99
    prompt = [tok] * 150 + [20, 21]
    tokens, _ = await collect(
        engine,
        request(prompt, max_tokens=50, greedy=True, frequency_penalty=100.0),
    )
    assert len(tokens) == 50
    assert tok not in tokens, (
        "saturated count must keep penalizing token 99 — a wrapped int8 "
        "count would reward it instead"
    )
    # the count buffer itself: saturated at 127, nothing wrapped negative.
    # (one loop tick lets the pipelined in-flight step rebind the donated
    # buffer before we read it)
    counts = None
    for _ in range(100):
        try:
            counts = np.asarray(engine._state.counts)
            break
        except RuntimeError:
            await asyncio.sleep(0.02)
    assert counts is not None
    assert (counts >= 0).all(), "int8 count buffer wrapped negative"
    assert counts.max() == 127, f"expected saturation, got {counts.max()}"
    await engine.close()
