"""Self-speculative decoding (CPU, tiny model, non-slow).

Covers the full draft/verify/rollback loop:
- greedy speculative output byte-identical to the non-speculative engine;
- the rejection-sampling verifier preserves the sampler's distribution
  (ops-level statistical invariant — the crisp version of "same
  distribution as the non-speculative engine" for temperature > 0);
- mid-draft rejection leaves page accounting, prefix-cache registration
  and a preempt/resume cycle consistent;
- adaptive gating: non-repetitive input never speculates and matches the
  plain engine token-for-token;
- acceptance metrics exposed via metrics()/phase_stats.
"""

import asyncio

import jax
import jax.numpy as jnp
import numpy as np

from dynamo_tpu.engine import EngineConfig, JaxEngine
from dynamo_tpu.engine.spec import NgramProposer
from dynamo_tpu.llm.protocols.common import (
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)
from dynamo_tpu.models import config as cfgmod
from dynamo_tpu.ops.sampling import sample_tokens, verify_draft_tokens
from dynamo_tpu.runtime.pipeline.context import Context

CFG = cfgmod.get_config("tiny")

REPETITIVE = [5, 17, 42, 9] * 6  # 4-gram period: lookups mostly accepted
PROMPTS = [REPETITIVE, [1, 2, 3, 4, 5, 6] * 4, [9, 9, 9, 9] * 5]


def make_engine(**kw) -> JaxEngine:
    defaults = dict(
        model=CFG,
        dtype="float32",
        page_size=8,
        num_pages=128,
        max_batch_size=4,
        max_model_len=256,
        prefill_chunk=32,
        decode_steps=4,
        seed=0,
    )
    defaults.update(kw)
    return JaxEngine(EngineConfig(**defaults))


def request(prompt, max_tokens=48, temperature=None, top_k=0):
    so = (
        SamplingOptions(greedy=True)
        if temperature is None
        else SamplingOptions(temperature=temperature, top_k=top_k, top_p=1.0)
    )
    return PreprocessedRequest(
        token_ids=list(prompt),
        stop_conditions=StopConditions(max_tokens=max_tokens, ignore_eos=True),
        sampling_options=so,
    )


async def collect(engine, pre):
    frames = [
        f async for f in await engine.generate(Context(pre.to_dict()))
    ]
    tokens = [t for f in frames for t in f.get("token_ids") or []]
    return tokens, frames


def spec_stats(engine):
    return {
        k: v for k, v in engine.phase_stats.items() if k.startswith("spec")
    }


# ---------------------------------------------------------------------------
# proposer unit behavior


def test_ngram_proposer_lookup_and_gating():
    p = NgramProposer(3)
    p.extend([1, 2, 3, 4, 1, 2, 3])
    # suffix (1, 2, 3) last occurred at the start; continuation is 4, 1...
    assert p.propose(3) == [4, 1, 2]
    # longest suffix wins over shorter ones
    p2 = NgramProposer(3)
    p2.extend([7, 8, 9, 8, 9])
    assert p2.propose(2) == [8, 9]  # 2-gram (8, 9) -> continuation at 3
    # no prior occurrence -> no draft
    p3 = NgramProposer(3)
    p3.extend([1, 2, 3, 4, 5])
    assert p3.propose(4) == []
    # gating: a collapsed EMA stops drafting until the probe countdown
    # expires; the probe then PERSISTS until observe() re-arms it (a
    # build the engine discards must not eat the probe)
    p.ema = 0.0
    p.observe(1, 0)  # re-arm the countdown, EMA stays collapsed
    burst = [bool(p.maybe_draft(3)) for _ in range(40)]
    assert not any(burst[:32]) and all(burst[32:])
    p.observe(3, 0)  # the probe verified badly: gated again
    assert p.maybe_draft(3) == []
    # recovery: accepted drafts raise the EMA back over the gate
    for _ in range(10):
        p.observe(3, 3)
    assert p.maybe_draft(3) == [4, 1, 2]


# ---------------------------------------------------------------------------
# ops-level verification sampler


def test_ngram_index_window_bounds_memory():
    """The proposer must stay bounded on arbitrarily long streams: a
    100k-token extend with a 1k-position window may hold at most
    window x ngram_max index entries (and at most ~2 windows of
    history), old registrations are evicted, and a recurring n-gram
    re-registered inside the window keeps drafting."""
    rng = np.random.RandomState(3)
    p = NgramProposer(3, index_window=1000)
    p.extend(rng.randint(1, 64, size=100_000).tolist())
    assert len(p._index) <= 3 * 1000
    # history keeps the windowed tail only (chunked truncation: < 2x)
    assert len(p.history) < 2 * 1000
    assert p._hist_base + len(p.history) == 100_000
    # an n-gram seen ONLY before the window is gone (no stale drafts)
    p2 = NgramProposer(3, index_window=100)
    p2.extend([201, 202, 203, 204])
    p2.extend(list(range(1, 150)))
    assert p2.propose(4) == []
    assert (201, 202, 203) not in p2._index
    # ...but a recent recurrence still drafts
    p3 = NgramProposer(3, index_window=100)
    p3.extend([1, 2, 3, 4, 1, 2, 3])
    assert p3.propose(3) == [4, 1, 2]
    # default window comes from EngineConfig.spec_index_window
    from dynamo_tpu.engine import EngineConfig

    assert EngineConfig().spec_index_window == 8192


def test_verify_greedy_exact_match():
    V = 16
    logits = jax.random.normal(jax.random.PRNGKey(3), (2, 4, V)) * 3
    greedy = np.asarray(jnp.argmax(logits, -1))
    # row 0: drafts = the argmaxes (all accepted); row 1: first draft wrong
    draft = np.stack([greedy[0, :3], greedy[1, :3]]).astype(np.int32)
    draft[1, 0] = (draft[1, 0] + 1) % V
    out, n_emit = verify_draft_tokens(
        logits, jnp.asarray(draft), jnp.asarray([3, 3], jnp.int32),
        jax.random.PRNGKey(0), jnp.zeros(2), jnp.zeros(2, jnp.int32),
        jnp.ones(2), all_greedy=True,
    )
    out, n_emit = np.asarray(out), np.asarray(n_emit)
    assert n_emit.tolist() == [4, 1]
    # emitted tokens are the argmaxes at every emitted position
    assert (out == greedy).all()
    # a row with no draft emits exactly one token
    _, n0 = verify_draft_tokens(
        logits, jnp.asarray(draft), jnp.asarray([0, 0], jnp.int32),
        jax.random.PRNGKey(0), jnp.zeros(2), jnp.zeros(2, jnp.int32),
        jnp.ones(2), all_greedy=True,
    )
    assert np.asarray(n0).tolist() == [1, 1]


def test_verify_preserves_sampling_distribution():
    """Rejection-sampling invariant: the marginal of the token emitted at
    a position equals the plain sampler's distribution there — whether
    the draft was accepted or replaced by the residual resample."""
    V, K = 12, 3
    logits = jax.random.normal(jax.random.PRNGKey(7), (K + 1, V)) * 2.0
    # draft the MOST LIKELY token at positions 0 and 1 (read off the
    # logits, not a hard-coded id): the conditioning event below — "the
    # first draft token was accepted" — then has thousands of samples
    # whatever this jax version's RNG stream makes of PRNGKey(7)
    d0, d1 = (int(x) for x in jnp.argmax(logits[:2], axis=-1))
    draft = jnp.asarray([[d0, d1, 3]], jnp.int32)
    temp = jnp.asarray([0.8])
    topk = jnp.asarray([0])
    topp = jnp.asarray([1.0])
    N = 20000
    keys = jax.random.split(jax.random.PRNGKey(1), N)

    def spec_pair(k):
        out, n = verify_draft_tokens(
            logits[None], draft, jnp.asarray([K]), k, temp, topk, topp
        )
        return out[0, 0], out[0, 1], n[0]

    o0, o1, ns = map(np.asarray, jax.vmap(spec_pair)(keys))

    def ref(pos):
        def one(k):
            return sample_tokens(logits[pos][None], k, temp, topk, topp)[0]
        return np.asarray(jax.vmap(one)(keys))

    # position-0 marginal
    sc = np.bincount(o0, minlength=V) / N
    rc = np.bincount(ref(0), minlength=V) / N
    assert np.abs(sc - rc).max() < 0.015
    # position-1 marginal GIVEN the first draft was accepted
    mask = (o0 == d0) & (ns >= 2)
    assert mask.sum() > 2000
    sc1 = np.bincount(o1[mask], minlength=V) / mask.sum()
    rc1 = np.bincount(ref(1), minlength=V) / N
    assert np.abs(sc1 - rc1).max() < 0.05


# ---------------------------------------------------------------------------
# engine e2e


async def test_greedy_spec_identical_to_plain_engine():
    plain = make_engine()
    spec = make_engine(spec_decode=True)
    expected = await asyncio.gather(
        *(collect(plain, request(p)) for p in PROMPTS)
    )
    got = await asyncio.gather(*(collect(spec, request(p)) for p in PROMPTS))
    assert [t for t, _ in got] == [t for t, _ in expected]
    st = spec_stats(spec)
    assert st["spec_dispatches"] > 0 and st["spec_accepted"] > 0
    await plain.close()
    await spec.close()


async def test_spec_effective_tokens_per_step_and_metrics():
    spec = make_engine(spec_decode=True)
    tokens, _ = await collect(spec, request(REPETITIVE, max_tokens=64))
    assert len(tokens) == 64
    st = spec_stats(spec)
    m = spec.metrics()
    # acceptance-rate metric exposed and healthy on repetitive text
    assert m["spec_acceptance_rate"] == (
        st["spec_accepted"] / st["spec_drafted"]
    )
    # random tiny-model text is only loosely periodic; the hard bar is
    # the effective-tokens criterion below, not raw acceptance
    assert m["spec_acceptance_rate"] > 0.2
    # the parity target: > 1.3 tokens emitted per model step per sequence
    assert st["spec_emitted"] / st["spec_rows"] > 1.3
    await spec.close()


async def test_adversarial_input_never_speculates():
    """Non-repetitive text: the proposer finds no n-gram continuation, so
    the engine runs today's (pipelined, scanned) decode path — same
    steps, same tokens."""
    rng = np.random.RandomState(11)
    # distinct tokens: no suffix n-gram ever recurs
    prompt = rng.permutation(np.arange(2, 200))[:40].tolist()
    plain = make_engine()
    spec = make_engine(spec_decode=True)
    t0, _ = await collect(plain, request(prompt, max_tokens=24))
    t1, _ = await collect(spec, request(prompt, max_tokens=24))
    # tokens identical; the spec engine never paid a verify step for the
    # prompt (generated text may repeat by chance — the permutation
    # prompt itself guarantees a draft-free prefill/first dispatches)
    assert t0 == t1
    # close first: a dispatch books itself when its call returns, and the
    # pipelined overshoot dispatch is still in its worker when the last
    # frame arrives (read then, the count is one short or not by chance)
    await plain.close()
    await spec.close()
    st = spec_stats(spec)
    ps, pp = spec.phase_stats, plain.phase_stats
    # the scanned decode path is today's: no more scan dispatches than
    # the plain engine's. What the chance repeats of the generated text
    # earn on top is a couple of one-step verify dispatches
    assert ps["decode_dispatches"] <= pp["decode_dispatches"]
    assert st["spec_dispatches"] <= 0.1 * (
        pp["decode_dispatches"] * plain.config.decode_steps
    )


async def test_sampled_spec_stream_smoke():
    """temperature>0 through the spec engine: top_k=1 makes the sampled
    path deterministic (argmax), so acceptance is high and the stream
    must equal the plain engine's — this drives the REJECTION-SAMPLING
    verify path (is_greedy False) end to end."""
    plain = make_engine()
    spec = make_engine(spec_decode=True)
    t0, _ = await collect(
        plain, request(REPETITIVE, max_tokens=48, temperature=0.7, top_k=1)
    )
    t1, _ = await collect(
        spec, request(REPETITIVE, max_tokens=48, temperature=0.7, top_k=1)
    )
    assert t0 == t1
    st = spec_stats(spec)
    assert st["spec_dispatches"] > 0 and st["spec_accepted"] > 0
    await plain.close()
    await spec.close()


async def test_rollback_preempt_resume_consistency():
    """Mid-draft rejections + page-pool pressure: preemption and resume
    under speculation must reproduce the plain engine's streams, and the
    pool must drain back to empty afterwards."""
    kw = dict(num_pages=14, max_batch_size=2, max_model_len=64)
    plain = make_engine(**kw)
    spec = make_engine(spec_decode=True, **kw)
    prompts = [[5, 17, 42, 9] * 4, [1, 2, 3] * 5]
    expected = await asyncio.gather(
        *(collect(plain, request(p, max_tokens=20)) for p in prompts)
    )
    got = await asyncio.gather(
        *(collect(spec, request(p, max_tokens=20)) for p in prompts)
    )
    assert [t for t, _ in got] == [t for t, _ in expected]
    await plain.close()
    await spec.close()


async def test_rejected_tail_never_registered_in_prefix_cache():
    """A re-serve of the same prompt rides the prefix cache built by a
    SPECULATIVE serve; if a rejected draft's garbage KV page had been
    hash-registered, the cached continuation would diverge."""
    spec = make_engine(spec_decode=True)
    # a prompt whose drafts are only PARTLY right on the seeded model
    # (REPETITIVE's drafts are all accepted under this jax's RNG, which
    # would make the test vacuous) — the precondition is asserted, so a
    # future drift fails here by name instead of passing on nothing
    prompt = [9, 9, 9, 9] * 5
    t1, frames1 = await collect(spec, request(prompt, max_tokens=32))
    assert frames1[0]["meta"]["prefix_cached_tokens"] == 0
    st1 = spec_stats(spec)
    assert st1["spec_accepted"] > 0, "precondition: some drafts accepted"
    assert st1["spec_drafted"] > st1["spec_accepted"], (
        "precondition: some drafts rejected")
    t2, frames2 = await collect(spec, request(prompt, max_tokens=32))
    assert frames2[0]["meta"]["prefix_cached_tokens"] > 0
    assert t1 == t2
    await spec.close()


async def test_spec_frames_stream_in_order():
    """Multi-token emits arrive as ONE frame per landing (a verify row's
    accepted drafts and its corrected or bonus token together, at most
    spec_k_max + 1), in sequence order, with the finish frame last (SSE
    framing downstream relies on this invariant)."""
    spec = make_engine(spec_decode=True)
    tokens, frames = await collect(spec, request(REPETITIVE, max_tokens=24))
    assert len(tokens) == 24
    sizes = [len(f["token_ids"]) for f in frames if f.get("token_ids")]
    assert max(sizes) > 1  # an accepted draft rode with its verify token
    assert max(sizes) <= max(
        spec.config.spec_k_max + 1, spec.config.decode_steps + 1)
    assert spec.metrics()["frames_total"] == len(sizes)
    assert spec.metrics()["tokens_total"] == 24
    assert frames[-1].get("finish_reason") == "length"
    assert all(not f.get("finish_reason") for f in frames[:-1])
    await spec.close()


def test_spec_config_validation():
    import pytest

    with pytest.raises(ValueError, match="spec_k_max"):
        make_engine(spec_decode=True, spec_k_max=0)
