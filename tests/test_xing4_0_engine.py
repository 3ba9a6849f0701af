"""The Xing4.0 family through the ENGINE, tiny preset on the CPU: served
log-probabilities against `benchmark/references/xing4_0.py` on both
backends (a prompt prefilled in chunks, then decoded through the paged
latent pool), the expert load in the flight recorder, preempt-and-resume,
and the sentence of every plane and mesh axis it does not run. The model
itself is `tests/test_xing4_0.py`'s."""

from __future__ import annotations

import asyncio

import numpy as np
import pytest

from .test_engine import collect, greedy_request, make_engine
from .test_xing4_0 import CFG, _hf, _reference


async def _serve(engine, prompt, n=8):
    pre = greedy_request(prompt, max_tokens=n)
    pre.sampling_options.logprobs = True
    tokens, finish, frames = await collect(engine, pre)
    assert len(tokens) == n and finish == "length"
    return tokens, np.asarray(
        [lp for f in frames for lp in f.get("log_probs") or []])


def _prompt(n, seed=3):
    rng = np.random.RandomState(seed)
    return [int(x) for x in rng.randint(1, CFG.vocab_size, (n,))]


@pytest.mark.parametrize("backend", ["gather", "pallas"])
async def test_served_logprobs_match_the_reference(backend):
    """A prompt prefilled in two chunks, then 8 tokens decoded through the
    latent cache (`pallas`: the latent kernels in interpret mode, the path
    the chip takes; `gather`: plain XLA), each served log-probability
    against the reference's, teacher-forced; `generate` without HTTP."""
    engine = make_engine(model=CFG, attn_backend=backend, prefill_chunk=32)
    assert engine.attention_backend["kind"] == backend
    assert engine.kv.latent and len(engine.kv.k) == CFG.num_layers
    prompt = _prompt(44)
    tokens, served = await _serve(engine, prompt)
    want = _reference().token_logprobs(
        engine.params, _hf(CFG), prompt + tokens, 8, 64)
    np.testing.assert_allclose(served, want, atol=5e-5)
    if backend == "pallas":
        rows = engine.flight.snapshot()
        loads = [r for r in rows if r["moe_experts_hit"]]
        # two expert layers, one row, top-2: two experts hit a layer, one
        # token each; every expert is held, so the pass is one block
        assert loads and all(r["kind"] in ("sync", "overlap") for r in loads)
        assert all(r["moe_experts_hit"] == 2.0 and r["moe_load_max"] == 1.0
                   and r["moe_row_blocks"] == 1.0 for r in loads)
    await engine.close()


async def test_preempt_and_resume_equals_an_undisturbed_run():
    """Five long answers over a page pool too small for them: a sequence
    is preempted, prefills again from position 0, and every stream serves
    what it serves alone."""
    prompts = [_prompt(20 + 3 * i, 7 + i) for i in range(5)]
    engine = make_engine(model=CFG, attn_backend="gather", num_pages=30,
                         max_batch_size=4, decode_steps=4)
    outs = await asyncio.gather(*(_serve(engine, p, 60) for p in prompts))
    assert engine.metrics()["preemptions_total"] >= 1
    assert engine.kv_ledger.audit() == [] and engine.allocator.pages_used == 0
    await engine.close()
    # (the last stream is the one preempted: the youngest gives way)
    fresh = make_engine(model=CFG, attn_backend="gather", decode_steps=4)
    alone_t, alone_lp = await _serve(fresh, prompts[-1], 60)
    await fresh.close()
    assert outs[-1][0] == alone_t
    np.testing.assert_allclose(outs[-1][1], alone_lp, atol=5e-5)


@pytest.mark.parametrize("backend", ["gather", "pallas"])
async def test_a_prompt_sent_twice_reuses_its_latent_pages(backend):
    """The in-engine prefix cache over the latent pool of a four-stream
    model: the second serve reserves the first's five whole pages,
    prefills the tail alone and serves the same tokens and
    log-probabilities."""
    engine = make_engine(model=CFG, attn_backend=backend, prefill_chunk=32)
    summaries = []
    engine.subscribe_requests(summaries.append)
    prompt = _prompt(44, seed=5)
    first_t, first_lp = await _serve(engine, prompt)
    assert engine.allocator.pages_cached > 0
    assert engine.peek_prefix_tokens(prompt) == 40  # 5 whole pages of 8
    prefilled = engine.phase_stats["prefill_tokens"]
    again_t, again_lp = await _serve(engine, prompt)
    assert engine.phase_stats["prefill_tokens"] - prefilled == 4
    assert [s["prefix"]["reused_blocks"] for s in summaries] == [0, 5]
    assert again_t == first_t
    np.testing.assert_allclose(again_lp, first_lp, atol=5e-5)
    assert engine.kv_ledger.audit() == []
    await engine.close()


# --------------------------------------------------------- what it refuses

async def test_engine_refuses_the_page_moving_planes():
    engine = make_engine(model=CFG)
    pre = greedy_request([5, 6, 7, 8], max_tokens=2)
    with pytest.raises(ValueError, match="latent"):
        await engine.prefill_only(pre)
    with pytest.raises(ValueError, match="latent"):
        engine.ingest_prefix(list(range(16)), None, None)
    with pytest.raises(ValueError, match="latent"):
        engine.export_prefix(list(range(16)))
    await engine.close()


def test_checkpoint_loading_is_refused_with_a_sentence(tmp_path):
    from dynamo_tpu.models.weights import load_params

    with pytest.raises(ValueError, match="low-rank queries"):
        load_params(str(tmp_path), CFG)
