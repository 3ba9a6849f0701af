"""The frame contract (PR 35): what one fetch brought for one sequence is
ONE `EngineOutput` on its out_queue (`JaxEngine._emit`, the one emit path
of decode, spec-verify and mixed landings and of the first-token emits).
The text, token ids, log-probabilities and usage a client receives are
those of a one-token-a-frame stream; only the number of frames differs.
And the collector's side of the same loop: `telemetry.HeapWatch`."""

import asyncio
import gc
import inspect

import numpy as np
import pytest

from dynamo_tpu.engine import EngineConfig, JaxEngine, telemetry
from dynamo_tpu.engine import engine as enginemod
from dynamo_tpu.llm.protocols.common import (
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)
from dynamo_tpu.models import config as cfgmod
from dynamo_tpu.runtime.pipeline.context import Context

CFG = cfgmod.get_config("tiny")
LANDINGS = ("sync", "overlap")
MODES = {
    "greedy": {},
    "logprobs": {"logprobs": True},
    "top_logprobs": {"logprobs": True, "top_logprobs": 3},
}


def make_engine(**kw) -> JaxEngine:
    defaults = dict(
        model=CFG, dtype="float32", page_size=8, num_pages=64,
        max_batch_size=4, max_model_len=128, prefill_chunk=32, seed=0,
    )
    defaults.update(kw)
    return JaxEngine(EngineConfig(**defaults))


def request(prompt, max_tokens, stop_ids=(), **so) -> PreprocessedRequest:
    return PreprocessedRequest(
        token_ids=list(prompt),
        stop_conditions=StopConditions(
            max_tokens=max_tokens, ignore_eos=not stop_ids,
            stop_token_ids=list(stop_ids)),
        sampling_options=SamplingOptions(greedy=True, **so),
    )


async def stream(engine, pre) -> list[dict]:
    """Every frame of one request (`pre`: the request, or its Context)."""
    ctx = pre if isinstance(pre, Context) else Context(pre.to_dict())
    return [f async for f in await engine.generate(ctx)]


def flat(frames, key) -> list:
    return [x for f in frames for x in f.get(key) or []]


def token_frames(frames) -> list[dict]:
    return [f for f in frames if f.get("token_ids")]


def ends_once(frames, reason) -> None:
    """The final frame follows the last token and nothing follows it."""
    assert frames[-1]["finish_reason"] == reason
    assert not frames[-1].get("token_ids")
    assert all(not f.get("finish_reason") for f in frames[:-1])


PROMPTS = ([5, 6, 7], list(range(30, 41)))
LENGTHS = (20, 13)


async def serve_two(steps: int, so: dict):
    engine = make_engine(decode_steps=steps)
    both = await asyncio.gather(*(
        stream(engine, request(p, n, **so)) for p, n in zip(PROMPTS, LENGTHS)))
    rows = [r for r in engine.flight.snapshot() if r["kind"] in LANDINGS]
    metrics = engine.metrics()
    await engine.close()
    return both, rows, metrics


@pytest.fixture(scope="module")
def one_token_a_frame():
    """The reference streams: `decode_steps` 1, where a landing brings one
    token (two when a first token rides its carry row)."""
    cache = {}

    async def get(mode):
        if mode not in cache:
            cache[mode] = (await serve_two(1, MODES[mode]))[0]
        return cache[mode]

    return get


@pytest.mark.parametrize("mode", list(MODES))
@pytest.mark.parametrize("steps", [1, 8])
async def test_one_frame_per_sequence_per_landing(
        steps, mode, one_token_a_frame):
    both, rows, metrics = await serve_two(steps, MODES[mode])
    got = [token_frames(fr) for fr in both]
    # a frame holds what one fetch brought: at most the carry row's first
    # token and the scan's steps
    assert max(len(f["token_ids"]) for fr in got for f in fr) <= steps + 1
    # the engine's books agree with what the clients received
    assert metrics["frames_total"] == sum(len(fr) for fr in got)
    assert metrics["tokens_total"] == sum(LENGTHS)
    # a landing puts one frame for each sequence it lands, never more
    assert rows and all(0 < r["frames"] <= r["rows"] for r in rows)
    assert all(r["frames"] <= r["tokens"] <= r["frames"] * (steps + 1)
               for r in rows)
    landed = sum(r["frames"] for r in rows)
    assert landed <= metrics["frames_total"] <= landed + len(PROMPTS)
    if steps == 8:  # the mechanism engages: a whole scan in one frame
        assert max(r["tokens"] / r["frames"] for r in rows) >= steps
    for fr, frames, n in zip(got, both, LENGTHS):
        ends_once(frames, "length")
        # the first token's meta survives, on the first frame alone
        assert fr[0]["meta"]["prompt_tokens"] > 0
        assert "prefix_cached_tokens" in fr[0]["meta"]
        assert "engine_ttft_s" in fr[0]["meta"]
        assert all(not f.get("meta") for f in fr[1:])
        for f in fr:  # a column per token, or none
            cols = [len(f[k]) for k in ("log_probs", "top_log_probs")
                    if f.get(k) is not None]
            assert all(c == len(f["token_ids"]) for c in cols)
            assert (f.get("log_probs") is not None) == (mode != "greedy")
            assert (f.get("top_log_probs") is not None) == (
                mode == "top_logprobs")
        assert len(flat(fr, "token_ids")) == n
    # ... and they are the one-token-a-frame stream's
    for fr, ref in zip(both, await one_token_a_frame(mode)):
        assert flat(fr, "token_ids") == flat(ref, "token_ids")
        lps, want = flat(fr, "log_probs"), flat(ref, "log_probs")
        np.testing.assert_allclose(lps, want, rtol=1e-5, atol=1e-6)
        if mode != "greedy":
            np.testing.assert_allclose(
                token_frames(fr)[-1]["cum_log_probs"], sum(want), rtol=1e-5)
        tops, want = flat(fr, "top_log_probs"), flat(ref, "top_log_probs")
        assert [[t for t, _ in a] for a in tops] == [
            [t for t, _ in a] for a in want]
        np.testing.assert_allclose(
            [lp for a in tops for _, lp in a],
            [lp for a in want for _, lp in a], rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("how", ["length", "eos", "cancel"])
async def test_finish_inside_a_landing_ends_at_the_same_token(how):
    """A sequence that finishes among the tokens one fetch brought keeps
    those up to the finishing one; the rest are discarded and the final
    frame follows at once."""
    engine = make_engine(decode_steps=8)
    prompt = [5, 17, 42]
    ref = flat(await stream(engine, request(prompt, 14)), "token_ids")
    if how == "length":
        frames = await stream(engine, request(prompt, 5))
        ends_once(frames, "length")
        assert flat(frames, "token_ids") == ref[:5]
    elif how == "eos":
        # the first token of the stream that occurs nowhere before it,
        # from the third on: it lies inside the first decode landing
        k = next(i for i in range(2, 8) if ref[i] not in ref[:i])
        frames = await stream(engine, request(prompt, 14, stop_ids=[ref[k]]))
        ends_once(frames, "stop")
        assert flat(frames, "token_ids") == ref[:k + 1]
        assert len(token_frames(frames)[-1]["token_ids"]) < 8
    else:
        # the client cancels while the first decode landing is being
        # made: the landing keeps its first token (the check is made at a
        # token, as it was when a token was a frame), nothing more
        ctx = Context(request(prompt, 100).to_dict())
        emit = engine._emit

        def cancelled_meanwhile(seq, toks, *a, **k):
            if seq.generated:
                ctx.stop_generating()
            return emit(seq, toks, *a, **k)

        engine._emit = cancelled_meanwhile
        frames = await stream(engine, ctx)
        ends_once(frames, "cancelled")
        got = flat(frames, "token_ids")
        first = len(frames[0]["token_ids"])
        assert got == ref[:first + 1]
        assert [len(f["token_ids"]) for f in token_frames(frames)] == [first, 1]
    assert engine.metrics()["request_active_slots"] == 0
    await engine.close()


def _pipeline(engine):
    from dynamo_tpu.llm.backend import Backend
    from dynamo_tpu.llm.model_card import ModelDeploymentCard
    from dynamo_tpu.llm.preprocessor import OpenAIPreprocessor
    from dynamo_tpu.runtime.pipeline.engine import link

    from .fixtures import tiny_model_dir

    card = ModelDeploymentCard.from_local_path(tiny_model_dir(), name="tiny")
    return link(OpenAIPreprocessor(card), Backend.from_card(card), engine)


async def _chat(pipeline, **body):
    from dynamo_tpu.llm.protocols.openai import ChatCompletionRequest

    req = ChatCompletionRequest.from_body({
        "model": "tiny", "messages": [{"role": "user", "content": "frames"}],
        "logprobs": True,
        "dyn_ext": {"greed_sampling": True, "ignore_eos": True}, **body})
    chunks = [c async for c in await pipeline.generate(Context(req))]
    choices = [ch for c in chunks for ch in c.get("choices", [])]
    return {
        "text": "".join(ch["delta"].get("content") or "" for ch in choices),
        "finish": [ch["finish_reason"] for ch in choices
                   if ch.get("finish_reason")],
        "logprobs": [e for ch in choices if ch.get("logprobs")
                     for e in ch["logprobs"]["content"]],
        "usage": next(c["usage"] for c in chunks if c.get("usage")),
        "deltas": sum(1 for ch in choices if ch["delta"].get("content")),
    }


async def test_client_sees_the_same_answer_in_fewer_deltas():
    """Through the preprocessor and the detokenizing backend: the text,
    the log-probability entries and `usage` of a stream whose frames hold
    a whole scan equal those of one whose frames hold a token, and a stop
    string that completes inside a frame cuts both at the same place."""
    answers = {}
    for steps in (1, 8):
        engine = make_engine(
            model=CFG.with_(vocab_size=512), max_model_len=256,
            num_pages=128, decode_steps=steps)
        pipeline = _pipeline(engine)
        whole = await _chat(pipeline, max_tokens=20)
        # a stop string taken from the middle of the answer
        pieces = [e["token"] for e in whole["logprobs"]]
        k = next(i for i in range(4, 16) if pieces[i].strip()
                 and pieces[i] not in "".join(pieces[:i]))
        cut = await _chat(pipeline, max_tokens=20, stop=[pieces[k]])
        answers[steps] = (whole, cut, k)
        await engine.close()
    (whole1, cut1, k1), (whole8, cut8, k8) = answers[1], answers[8]
    assert whole8["usage"]["completion_tokens"] == 20 == len(whole8["logprobs"])
    assert whole8["deltas"] < whole1["deltas"]
    for a, b in ((whole1, whole8), (cut1, cut8)):
        assert a["text"] == b["text"] and a["finish"] == b["finish"]
        assert a["usage"] == b["usage"]
        assert [e["token"] for e in a["logprobs"]] == [
            e["token"] for e in b["logprobs"]]
        np.testing.assert_allclose(
            [e["logprob"] for e in a["logprobs"]],
            [e["logprob"] for e in b["logprobs"]], rtol=1e-5, atol=1e-6)
    assert k1 == k8 and cut8["finish"] == ["stop"]
    assert cut8["text"] == "".join(
        e["token"] for e in whole8["logprobs"][:k8])
    assert cut8["usage"]["completion_tokens"] == k8 + 1


REPETITIVE = [3, 4, 5, 6] * 6


@pytest.mark.parametrize("kw,counter", [
    ({"spec_decode": True}, "spec_rows"),
    ({"mixed_batching": True, "mixed_step_tokens": 64}, "mixed_steps"),
    ({"mixed_batching": True, "mixed_step_tokens": 64, "spec_decode": True},
     "mixed_steps"),
])
async def test_spec_and_mixed_landings_emit_through_the_one_helper(
        kw, counter):
    plain = make_engine(decode_steps=4, max_model_len=256, num_pages=128)
    other = make_engine(decode_steps=4, max_model_len=256, num_pages=128, **kw)
    calls = []
    emit = other._emit
    other._emit = lambda seq, toks, *a, **k: calls.append(
        emit(seq, toks, *a, **k)) or calls[-1]
    rng = np.random.RandomState(0)
    wave = [rng.randint(1, 200, size=45).tolist() for _ in range(3)]

    async def serve(engine):
        held = asyncio.create_task(stream(engine, request(REPETITIVE, 40)))
        await asyncio.sleep(0)
        rest = await asyncio.gather(
            *(stream(engine, request(p, 10)) for p in wave))
        return [await held, *rest]

    want, got = await serve(plain), await serve(other)
    for a, b in zip(want, got):
        assert flat(a, "token_ids") == flat(b, "token_ids")
        ends_once(b, "length")
    assert other.phase_stats[counter] > 0
    # every token frame a client received came from one `_emit` call
    n_frames = sum(len(token_frames(fr)) for fr in got)
    assert len(calls) == n_frames == other.metrics()["frames_total"]
    assert sum(calls) == 40 + 3 * 10 == other.metrics()["tokens_total"]
    await plain.close()
    await other.close()


def test_token_frames_are_built_in_one_place():
    src = inspect.getsource(enginemod)
    assert src.count("EngineOutput(token_ids=") == 1
    assert "EngineOutput(token_ids=" in inspect.getsource(JaxEngine._emit)


# ------------------------------------------------------------ the collector


async def test_heap_is_frozen_once_the_programs_stand_still(monkeypatch):
    """After `HEAP_QUIET_TICKS` ticks without a program compiled or
    loaded the loop collects and freezes, once; a later compile re-arms
    it, once; `close()` gives the heap back."""
    monkeypatch.setattr(telemetry, "HEAP_QUIET_TICKS", 3)
    base = gc.get_freeze_count()  # the interpreter's own, a few hundred
    engine = make_engine(decode_steps=2)
    froze = []
    settle = engine._heap.settle
    engine._heap.settle = lambda: froze.append(settle()) or froze[-1]
    # warm the programs (compile events move), then a quiet stretch
    await stream(engine, request([5, 6, 7], 6))
    assert (gc.get_freeze_count() > base) == (sum(froze) == 1)
    await stream(engine, request([5, 6, 7], 24))
    assert sum(froze) == 1
    assert engine.metrics()["gc_frozen_objects"] == gc.get_freeze_count()
    assert gc.get_freeze_count() > base + 10_000  # the compiled programs
    await stream(engine, request([5, 6, 7], 24))
    assert sum(froze) == 1  # nothing compiled since: not again
    with telemetry._lock:
        telemetry._compile_events += 1  # a program compiled or loaded
    await stream(engine, request([5, 6, 7], 24))
    assert sum(froze) == 2
    await stream(engine, request([5, 6, 7], 24))
    assert sum(froze) == 2
    await engine.close()
    assert gc.get_freeze_count() == 0
    assert engine._heap._on_pass not in gc.callbacks


async def test_collector_passes_are_booked_on_the_landing():
    engine = make_engine(decode_steps=2)
    before = engine.metrics()
    ctx = Context(request([5, 6, 7], 12).to_dict())
    async for _ in await engine.generate(ctx):
        gc.collect()  # a full pass between two landings
    rows = [r for r in engine.flight.snapshot() if r["kind"] in LANDINGS]
    after = engine.metrics()
    await engine.close()
    assert len(rows) >= 3 and all(r["gc_s"] >= 0 for r in rows)
    assert sum(r["gc_s"] > 0 for r in rows) >= 2
    assert sum(r["gc_s"] for r in rows) <= engine._heap.gc_s
    assert after["gc_full_passes_total"] >= before["gc_full_passes_total"] + 3
    assert after["gc_full_pass_s_total"] > before["gc_full_pass_s_total"]
    assert {"gc_frozen_objects", "frames_total", "tokens_total"} <= set(after)
