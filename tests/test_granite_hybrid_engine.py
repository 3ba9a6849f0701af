"""The Granite 4.0-H family through the ENGINE: served log-probabilities
against `benchmark/references/granite_hybrid.py` (one prefill chunk, several,
decode through the state pools), a padded group against its rows alone, a
slot reused, preempt-and-resume, the tail groups, and every refusal's
sentence. The configuration, the mixer's forms, the kernel and the other
models' jaxpr digests are `tests/test_granite_hybrid.py`'s."""

from __future__ import annotations

import asyncio

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from .test_engine import collect, greedy_request, make_engine
from .test_granite_hybrid import CFG, _hf, _reference


# ------------------------------------------- the engine against the reference


async def _serve(engine, prompt, n=12):
    pre = greedy_request(prompt, max_tokens=n)
    pre.sampling_options.logprobs = True
    tokens, finish, frames = await collect(engine, pre)
    assert len(tokens) == n and finish == "length"
    return tokens, np.asarray(
        [lp for f in frames for lp in f.get("log_probs") or []])


def _prompt(n, seed=3):
    rng = np.random.RandomState(seed)
    return [int(x) for x in rng.randint(1, CFG.vocab_size, (n,))]


@pytest.mark.parametrize("backend,n_prompt", [
    ("gather", 20), ("gather", 75), ("pallas", 20), ("pallas", 75)])
async def test_served_logprobs_match_the_reference(backend, n_prompt):
    """Through the engine: a prompt prefilled in one chunk (20 tokens: two
    SSD chunks and a partial one) or in three (75 tokens, the state carried
    between prefill chunks), then 12 tokens decoded through the state pools
    and the one attention layer's pages: each served log-probability
    against the reference's, teacher-forced."""
    engine = make_engine(model=CFG, attn_backend=backend, prefill_chunk=32,
                         decode_steps=4)
    assert engine.attention_backend["kind"] == backend
    # one K/V pool for the one layer that keeps pages; a state pool a
    # Mamba layer, a row a slot and the trash row
    assert len(engine.kv.k) == len(engine.kv.v) == 1
    assert len(engine.kv.ssm) == len(engine.kv.conv) == 3
    assert engine.kv.ssm[0].shape == (5, 1, 16, 128)  # 16 heads of 8 a row
    assert engine.kv.conv[0].shape == (5, 3, 160)
    prompt = _prompt(n_prompt)
    tokens, served = await _serve(engine, prompt)
    want = _reference().token_logprobs(
        engine.params, _hf(CFG), prompt + tokens, 12, 96)
    np.testing.assert_allclose(served, want, atol=5e-5)
    m = engine.metrics()
    assert m["state_resets_total"] == 1 and m["state_slots_used"] == 0
    rows = engine.flight.snapshot()
    decodes = [r for r in rows if r["kind"] == "decode"]
    assert decodes and all(
        r["state_rows_advanced"] == r["tokens"] == r["rows"] * 4
        for r in decodes)
    held = [r["state_slots_held"] for r in rows
            if r["kind"] in ("prefill", "decode")]
    # (a dispatch in flight when the sequence ended books an empty slot)
    assert set(held) <= {0, 1} and held[0] == 1 and sum(held) >= 3
    assert all(r["state_rows_advanced"] == 0 for r in rows
               if r["kind"] != "decode")
    assert engine.allocator.pages_used == 0 == engine.allocator.pages_cached
    await engine.close()


async def test_a_padded_group_equals_its_rows_alone():
    """Three prompts prefilled as one group (padded to four rows, buckets
    padded to the longest) and decoded side by side serve what each serves
    alone."""
    prompts = [_prompt(9, 1), _prompt(20, 2), _prompt(30, 3)]
    engine = make_engine(model=CFG, attn_backend="gather", decode_steps=4)
    together = await asyncio.gather(*(_serve(engine, p) for p in prompts))
    assert any(r["kind"] == "prefill" and r["rows"] > 1
               for r in engine.flight.snapshot())
    await engine.close()
    for p, (tokens, served) in zip(prompts, together):
        fresh = make_engine(model=CFG, attn_backend="gather", decode_steps=4)
        alone_t, alone_lp = await _serve(fresh, p)
        await fresh.close()
        assert tokens == alone_t
        np.testing.assert_allclose(served, alone_lp, atol=2e-5)


async def test_a_reused_slot_starts_from_zero():
    """One slot: the second sequence takes the slot the first left its
    state in, and serves what it serves on a fresh engine (its first
    chunk's position 0 starts the row from zero)."""
    engine = make_engine(model=CFG, attn_backend="gather", max_batch_size=1,
                         decode_steps=4)
    await _serve(engine, _prompt(40, 5))
    await asyncio.sleep(0.2)  # the overshoot dispatch drains
    with engine._kv_lock:     # (a dispatch donates the pools it takes)
        assert float(jnp.abs(engine.kv.ssm[0][0]).max()) > 0  # left behind
    second = await _serve(engine, _prompt(23, 6))
    assert engine.metrics()["state_resets_total"] == 2
    await engine.close()
    fresh = make_engine(model=CFG, attn_backend="gather", max_batch_size=1,
                        decode_steps=4)
    alone = await _serve(fresh, _prompt(23, 6))
    await fresh.close()
    assert second[0] == alone[0]
    np.testing.assert_allclose(second[1], alone[1], atol=2e-5)


async def test_preempt_and_resume_equals_an_undisturbed_run():
    """Five long answers over a page pool too small for them: a sequence
    is preempted (its pages and its state forgotten), prefills again from
    position 0 in whatever slot it is given, and every stream serves what
    it serves alone."""
    prompts = [_prompt(20 + 3 * i, 7 + i) for i in range(5)]
    engine = make_engine(model=CFG, attn_backend="gather", num_pages=30,
                         max_batch_size=4, decode_steps=4)
    outs = await asyncio.gather(*(_serve(engine, p, 60) for p in prompts))
    m = engine.metrics()
    assert m["preemptions_total"] >= 1
    assert m["state_resets_total"] >= 5 + m["preemptions_total"]
    assert engine.kv_ledger.audit() == [] and engine.allocator.pages_used == 0
    await engine.close()
    for p, (tokens, served) in zip(prompts, outs):
        fresh = make_engine(model=CFG, attn_backend="gather", decode_steps=4)
        alone_t, alone_lp = await _serve(fresh, p, 60)
        await fresh.close()
        assert tokens == alone_t
        np.testing.assert_allclose(served, alone_lp, atol=5e-5)


async def test_tails_that_meet_under_load_find_their_group_program_loaded():
    """As a window layout's engine: the first one-row tail of a two-chunk
    prompt loads its wider siblings on rows of padding (no slot: the trash
    row of the state pools), so tails that meet compile nothing."""
    import logging

    class Compiles(logging.Handler):
        n = 0

        def emit(self, record):
            self.n += "Compiling jit(_model_step)" in record.getMessage()

    seen, lg = Compiles(), logging.getLogger("jax._src.interpreters.pxla")
    engine = make_engine(model=CFG, attn_backend="gather", decode_steps=4,
                         prefill_group_tokens=32)
    prompts = [_prompt(40 + i, 17 + i) for i in range(3)]
    lg.addHandler(seen)
    jax.config.update("jax_log_compiles", True)
    try:
        # a request that asks for log-probabilities runs programs of its
        # own and loads no sibling (the benchmark's check prompts: six
        # large programs of a warm set-up that no traffic asked for)
        await _serve(engine, prompts[0], 6)
        assert seen.n == 2 and not engine._tail_groups_loaded
        seen.n = 0
        alone, _, _ = await collect(
            engine, greedy_request(prompts[0], max_tokens=6))
        assert seen.n == 3 and len(engine._tail_groups_loaded) == 1
        outs = await asyncio.gather(*(
            collect(engine, greedy_request(p, max_tokens=6))
            for p in prompts))
        assert seen.n == 3
    finally:
        jax.config.update("jax_log_compiles", False)
        lg.removeHandler(seen)
    assert outs[0][0] == alone
    await engine.close()


# ----------------------------------------------- what a state pool refuses

SENTENCE = "Mamba-2 layers beside attention"


async def test_state_engine_refuses_the_page_moving_planes():
    """Disaggregation (both sides), prefix ingest / export, the
    device-path transfer: each refuses with its sentence; the in-engine
    prefix cache registers nothing, so a prompt sent twice prefills twice."""
    from dynamo_tpu.engine.kv_transfer import device_transfer_kv
    from dynamo_tpu.runtime.pipeline.context import Context

    engine = make_engine(model=CFG)
    pre = greedy_request(list(range(1, 20)), max_tokens=2)
    with pytest.raises(ValueError, match=SENTENCE):
        await engine.generate_remote(Context(pre.to_dict()), 1, None, None)
    with pytest.raises(ValueError, match=SENTENCE):
        await engine.prefill_only(pre)
    with pytest.raises(ValueError, match=SENTENCE):
        engine.ingest_prefix(list(range(16)), None, None)
    with pytest.raises(ValueError, match=SENTENCE):
        engine.export_prefix(list(range(16)))
    with pytest.raises(ValueError, match=SENTENCE):
        device_transfer_kv(engine, engine, [1], [2], 8)
    first = await collect(engine, greedy_request(list(range(1, 40)), 4))
    assert engine.allocator.pages_cached == 0
    assert engine.peek_prefix_tokens(list(range(1, 40))) == 0
    again = await collect(engine, greedy_request(list(range(1, 40)), 4))
    assert first[0] == again[0]
    assert engine.metrics()["state_resets_total"] == 2
    assert SENTENCE in engine._mixed_unsupported_reason()
    await engine.close()
