"""The SDAR family through the ENGINE, tiny preset on the CPU: a prompt
prefilled in chunks under the block-causal mask, then generated a block at
a time through the paged cache, each served log-probability against
`benchmark/references/sdar_moe.py` on both backends, for prompt lengths of
every residue mod 4 and both fixed-count transfer strategies; rows that
join mid-dispatch at other phases; cuts inside a block; preempt-and-resume;
sampling a position; what it counts and what it refuses; and every OTHER
family's step programs pinned to the parent's. The model itself is
`tests/test_sdar.py`'s."""

from __future__ import annotations

import asyncio
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.llm.protocols.common import (
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)
from dynamo_tpu.models.config import PRESETS

from .test_engine import collect, greedy_request, make_engine
from .test_sdar import CFG, _hf, _reference

BACKENDS = ["gather", "pallas"]


def _prompt(n, seed=3):
    rng = np.random.RandomState(seed + n)
    return [int(x) for x in rng.randint(1, 250, (n,))]


def _watch_passes(engine):
    """Record what every landed pass filled, by sequence: the order the
    PROGRAM filled in, which the served ids alone do not tell."""
    seen, land = {}, engine._sync_dlm

    def sync(d, arrs):
        for i, seq in d.snapshot:
            if engine.slots[i] is seq:
                seen.setdefault(seq.ctx.id, []).extend(
                    np.asarray(arrs[0])[:, i].tolist())
        return land(d, arrs)

    engine._sync_dlm = sync
    return seen


def _order(passes, given, n_served, block):
    """From the filled ids of a row's passes ([-1] where a pass filled
    nothing) the pass of its block that filled each served token."""
    order, buf, k = [], [True] * given + [None] * (block - given), 0
    for row in passes:
        if None not in buf:              # the commit pass
            order += [p for p in buf if p is not True]
            buf, k = [None] * block, 0
            continue
        for j, tok in enumerate(row):
            if tok >= 0:
                buf[j] = k
        k += 1
    order += [p for p in buf if p is not True and p is not None]
    return order[:n_served]


async def _serve(engine, prompt, n=9, **kw):
    pre = greedy_request(prompt, max_tokens=n, **kw)
    pre.sampling_options.logprobs = True
    tokens, finish, frames = await collect(engine, pre)
    lps = [lp for f in frames for lp in f.get("log_probs") or []]
    return tokens, finish, np.asarray(lps), frames


@pytest.mark.parametrize("steps", [2, 4])
@pytest.mark.parametrize("backend", BACKENDS)
async def test_served_logprobs_match_the_reference(backend, steps):
    """Prompts of 1 to 9 tokens (every residue mod 4, so also a prompt
    shorter than a block and a tail of 1, 2 and 3 given tokens) and one of
    44 prefilled in two chunks, 9 tokens each through the block step
    (`pallas`: the flash kernel in interpret mode, the path the chip takes;
    `gather`: plain XLA), at two tokens a pass (the preset's 2 denoising
    steps) and at one (4 steps: a block's 5 passes straddle a dispatch of
    3): every served log-probability is the reference's, read from the pass
    that filled the token, and the program filled leftmost first."""
    cfg = CFG.with_(denoising_steps=steps)
    engine = make_engine(model=cfg, attn_backend=backend, prefill_chunk=32,
                         decode_steps=3)
    assert engine.attention_backend["kind"] == backend
    seen = _watch_passes(engine)
    ref = _reference()
    for length in (*range(1, 10), 44):
        prompt = _prompt(length)
        n = 9                       # ends inside a block
        tokens, finish, served, _ = await _serve(engine, prompt, n=n)
        assert len(tokens) == n and finish == "length"
        assert cfg.mask_token_id not in tokens
        order = _order(next(reversed(seen.values())), length % 4, n, 4)
        assert order == ref_order(length % 4, fill=4 // steps)
        want = ref.token_logprobs(
            engine.params, _hf(cfg), prompt + tokens, n, 64)
        np.testing.assert_allclose(served, want, atol=5e-5,
                                   err_msg=f"prompt of {length}")
    assert engine.kv_ledger.audit() == [] and engine.allocator.pages_used == 0
    await engine.close()


def ref_order(given, n=9, block=4, fill=2):
    """`sequential`'s order: the j-th masked position of a block in pass
    j // fill."""
    out, left = [], block - given
    while len(out) < n:
        out += [j // fill for j in range(left)]
        left = block
    return out[:n]


async def test_a_prompt_shorter_than_a_block_is_served_with_no_prefill():
    """The shortest prompt: one token. Nothing is prefilled (no whole
    block to encode); the first block opens at position 0 with the token
    as its given head and three masks."""
    engine = make_engine(model=CFG, attn_backend="gather", decode_steps=3)
    tokens, finish, served, _ = await _serve(engine, [17], n=7)
    assert len(tokens) == 7 and finish == "length"
    assert engine.phase_stats["prefill_dispatches"] == 0
    want = _reference().token_logprobs(
        engine.params, _hf(CFG), [17] + tokens, 7, 64)
    np.testing.assert_allclose(served, want, atol=5e-5)
    await engine.close()


@pytest.mark.parametrize("limit", [1, 2, 3, 5, 6, 7])
async def test_max_tokens_inside_a_block_cuts_exactly(limit):
    """`max_tokens` that ends inside a block (and inside a pass's two
    tokens): exactly that many tokens, the same as the longer stream's
    first, finish `length`; what the device did past the cut is dropped
    and every page comes back."""
    engine = make_engine(model=CFG, attn_backend="gather", decode_steps=3)
    prompt = _prompt(8)
    full, _, full_lps, _ = await _serve(engine, prompt, n=12)
    tokens, finish, served, _ = await _serve(engine, prompt, n=limit)
    assert finish == "length" and tokens == full[:limit]
    np.testing.assert_allclose(served, full_lps[:limit], atol=5e-5)
    assert engine.kv_ledger.audit() == [] and engine.allocator.pages_used == 0
    await engine.close()


@pytest.mark.parametrize("at", [1, 2, 5])
async def test_a_stop_token_inside_a_block_cuts_exactly(at):
    """A stop token that a pass fills in the middle of a block: the stream
    ends WITH it, though the same pass filled the position after it."""
    engine = make_engine(model=CFG, attn_backend="gather", decode_steps=3)
    prompt = _prompt(8)
    full, _, _, _ = await _serve(engine, prompt, n=12)
    stop = full[at]
    cut = full.index(stop)
    tokens, finish, _, _ = await _serve(
        engine, prompt, n=12, stop_token_ids=[stop])
    assert finish == "stop" or finish == "eos", finish
    assert tokens == full[:cut + 1]
    assert engine.allocator.pages_used == 0
    await engine.close()


@pytest.mark.parametrize("backend", BACKENDS)
async def test_rows_joining_mid_dispatch_give_the_stream_they_give_alone(
        backend):
    """Four prompts of four residues sent a few ticks apart, so that rows
    join a running batch while others are in another phase of their block
    (the phase is data: one program a width serves them all): each serves
    the tokens and log-probabilities it serves alone."""
    prompts = [_prompt(n, seed=11) for n in (6, 9, 3, 12)]
    engine = make_engine(model=CFG, attn_backend=backend, decode_steps=2)

    async def later(i, p):
        await asyncio.sleep(0.15 * i)
        return await _serve(engine, p, n=14)

    together = await asyncio.gather(
        *(later(i, p) for i, p in enumerate(prompts)))
    assert engine.metrics()["dlm_tokens_per_pass"] < 4 / 3
    await engine.close()
    fresh = make_engine(model=CFG, attn_backend=backend, decode_steps=2)
    for p, (tokens, _, lps, _) in zip(prompts, together):
        alone_t, _, alone_lp, _ = await _serve(fresh, p, n=14)
        assert tokens == alone_t
        np.testing.assert_allclose(lps, alone_lp, atol=5e-5)
    await fresh.close()


async def test_a_preempted_row_resumes_to_the_same_greedy_stream():
    """Five long answers over a page pool too small for them: a row is
    preempted, drops its open block, prefills again from its committed
    tokens and what the client has of the open block, and every stream
    serves what it serves alone."""
    prompts = [_prompt(20 + 3 * i, 7 + i) for i in range(5)]
    engine = make_engine(model=CFG, attn_backend="gather", num_pages=30,
                         max_batch_size=4, decode_steps=3)
    outs = await asyncio.gather(*(_serve(engine, p, 42) for p in prompts))
    assert engine.metrics()["preemptions_total"] >= 1
    assert engine.kv_ledger.audit() == [] and engine.allocator.pages_used == 0
    await engine.close()
    # (the last stream is the one preempted: the youngest gives way)
    fresh = make_engine(model=CFG, attn_backend="gather", decode_steps=3)
    for p, (tokens, finish, lps, _) in list(zip(prompts, outs))[-2:]:
        alone_t, _, alone_lp, _ = await _serve(fresh, p, 42)
        assert tokens == alone_t and finish == "length"
        np.testing.assert_allclose(lps, alone_lp, atol=5e-5)
    await fresh.close()


async def test_a_prompt_sent_twice_reuses_its_pages():
    """The prefix cache under the block-causal mask: a page holds whole
    blocks, so the second serve reserves the first's whole pages, prefills
    the rest of the whole blocks and serves the same stream."""
    engine = make_engine(model=CFG, attn_backend="gather", prefill_chunk=32)
    prompt = _prompt(45)
    first_t, _, first_lp, _ = await _serve(engine, prompt)
    prefilled = engine.phase_stats["prefill_tokens"]
    assert prefilled == 44           # the whole blocks of 45
    again_t, _, again_lp, _ = await _serve(engine, prompt)
    assert engine.phase_stats["prefill_tokens"] - prefilled == 4  # 40..43
    assert again_t == first_t
    np.testing.assert_allclose(again_lp, first_lp, atol=5e-5)
    await engine.close()


async def test_sampling_a_position_and_top_logprobs():
    """Temperature, top-k and top-p act a position: a top-k of 1 is the
    greedy stream; a hot stream differs from it, never holds the mask
    token, and carries its alternatives a token."""
    engine = make_engine(model=CFG, attn_backend="gather", decode_steps=3)
    prompt = _prompt(10)
    greedy, _, _, _ = await _serve(engine, prompt, n=12)

    def request(**kw):
        return PreprocessedRequest(
            token_ids=prompt, stop_conditions=StopConditions(max_tokens=12),
            sampling_options=SamplingOptions(
                logprobs=True, top_logprobs=3, **kw))

    tokens, _, frames = await collect(
        engine, request(temperature=1.5, top_k=1))
    assert tokens == greedy
    hot, _, frames = await collect(
        engine, request(temperature=5.0, top_k=50, top_p=0.95))
    assert len(hot) == 12 and hot != greedy
    assert CFG.mask_token_id not in hot
    tops = [t for f in frames for t in f.get("top_log_probs") or []]
    assert len(tops) == 12 and all(len(t) == 3 for t in tops)
    await engine.close()


@pytest.mark.parametrize("backend", BACKENDS)
async def test_what_the_block_step_counts(backend):
    """Two rows in step: `dlm_tokens_per_pass` is 4/3 while both run whole
    blocks; the digests carry a `dlm` row a dispatch with its passes and a
    sync row with what landed; the expert load rides the sync rows. On the
    pallas backend a `dlm` row also books what ONE layer's block kernel
    reads over the passes, by the kernel's own rule (the keys through the
    end of each row's open block): the pages it copies in, the pages
    those rows hold (equal: it reads what a row holds) and the work items
    it walks; the gather backend books none."""
    engine = make_engine(model=CFG, attn_backend=backend, decode_steps=3)
    await asyncio.gather(*(
        _serve(engine, _prompt(8, seed=s), n=24) for s in (1, 2)))
    m = engine.metrics()
    assert m["dlm_committed"] == 12 and m["dlm_filled"] == 48
    assert m["dlm_row_passes"] >= 36 and m["dlm_passes"] % 3 == 0
    rows = engine.flight.snapshot()
    dlm = [r for r in rows if r["kind"] == "dlm"]
    assert dlm and all(r["dlm_passes"] == 3 and r["rows"] in (1, 2)
                       and r["tokens"] == r["rows"] * 12 for r in dlm)
    landed = [r for r in rows if r["dlm_row_passes"]]
    assert all(r["kind"] in ("sync", "overlap") for r in landed)
    assert sum(r["dlm_filled"] for r in landed) == 48
    assert sum(r["dlm_committed"] for r in landed) == 12
    assert any(r["moe_experts_hit"] for r in landed)
    assert not [r for r in rows if r["kind"] == "decode"]
    ps = engine.page_size
    if backend == "pallas":
        # a prompt of 8 is two whole blocks: the first dispatch's three
        # passes (fill 2, fill 2, commit) all read 8 + 4 keys a row, in
        # work items of 4 pages
        row_passes = dlm[0]["rows"] * 3
        assert dlm[0]["kv_pages_held"] == row_passes * -(-12 // ps)
        assert dlm[0]["dlm_work_items"] == row_passes * -(-12 // (4 * ps))
        for r in dlm:
            assert r["kv_pages_streamed"] == r["kv_pages_held"] > 0
            # a row a pass walks an item at least, and an item a page
            assert (r["rows"] * 3 <= r["dlm_work_items"]
                    <= r["kv_pages_streamed"] <= 4 * r["dlm_work_items"])
        # the open block moves by 4 a commit: later dispatches read more
        assert (dlm[-1]["kv_pages_held"] / dlm[-1]["rows"]
                > dlm[0]["kv_pages_held"] / dlm[0]["rows"] or ps >= 36)
    else:
        assert all(r["kv_pages_streamed"] == r["kv_pages_held"]
                   == r["dlm_work_items"] == 0 for r in dlm)
    assert all(r["dlm_work_items"] == 0 for r in rows if r["kind"] != "dlm")
    await engine.close()


async def test_the_normal_http_path_serves_sse_with_logprobs(tmp_path):
    """`in=http out=jax` on a model directory whose config.json says
    `model_type: sdar_moe` with the block keys beside the model's own:
    chat completions over SSE with log-probabilities through
    `build_http_service`, the served config.json handed to the reference
    as `hf`; `/metrics` renders the block step's counters."""
    import aiohttp

    import chip_smoke as cs
    from dynamo_tpu.run import build_http_service, build_parser

    hf = {**_hf(CFG), "vocab_size": 512, "mask_token_id": 500,
          "intermediate_size": 128, "num_hidden_layers": 2,
          "moe_intermediate_size": 32, "max_position_embeddings": 2048,
          "tie_word_embeddings": False}
    words = cs.write_model_dir(str(tmp_path), hf, 7)
    vocab = {w: i for i, w in enumerate(words)}
    args = build_parser().parse_args([
        "in=http", "out=jax", "--model-path", str(tmp_path),
        "--model-name", "tiny-sdar-http", "--http-host", "127.0.0.1",
        "--max-batch-size", "8", "--max-model-len", "256",
        "--prefill-chunk", "64", "--page-size", "16", "--decode-steps", "3",
        "--dtype", "float32", "--attn-backend", "gather"])
    svc, engine = await build_http_service(args, "jax")
    await svc.start(args.http_host, 0)
    try:
        assert engine.model_cfg.dlm and engine.model_cfg.mask_token_id == 500
        base = f"http://127.0.0.1:{svc.port}"
        content = " ".join(words[20 + i] for i in range(13))   # 21 tokens
        async with aiohttp.ClientSession() as session:
            got = await cs._chat(session, base, "tiny-sdar-http", content,
                                 10, vocab, stream=True, logprobs=True)
            async with session.get(f"{base}/metrics") as r:
                scrape = await r.text()
        assert "engine_dlm_tokens_per_pass" in scrape
        assert "engine_dlm_committed" in scrape
        # the chat template around one user message, from the vocabulary
        bos, sh, eh, eot = (vocab[t] for t in (
            "<|begin_of_text|>", "<|start_header_id|>", "<|end_header_id|>",
            "<|eot_id|>"))
        prompt = [bos, sh, vocab["user"], eh] + [
            vocab[w] for w in content.split()] + [
            eot, sh, vocab["assistant"], eh]
        assert got["prompt_tokens"] == len(prompt) == 21
        want = _reference().token_logprobs(
            engine.params, hf, prompt + got["ids"], 10, 64)
        np.testing.assert_allclose(got["lps"], want, atol=5e-5)
        assert 500 not in got["ids"]
    finally:
        await svc.stop()
        await engine.close()


# --------------------------------------------------------- what it refuses

@pytest.mark.parametrize("asked,named", [
    (dict(frequency_penalty=0.5), "penalties"),
    (dict(presence_penalty=0.5), "penalties"),
    (dict(repetition_penalty=1.2), "penalties"),
    (dict(seed=7), "a per-request seed"),
])
async def test_a_request_is_refused_by_name(asked, named):
    engine = make_engine(model=CFG)
    pre = PreprocessedRequest(
        token_ids=[5, 6, 7], stop_conditions=StopConditions(max_tokens=4),
        sampling_options=SamplingOptions(temperature=1.0, **asked))
    with pytest.raises(ValueError, match=named) as err:
        await collect(engine, pre)
    assert "generation by diffusion over blocks" in str(err.value)
    await engine.close()


async def test_the_page_moving_planes_refuse_with_the_rows_sentence():
    engine = make_engine(model=CFG)
    pre = greedy_request([5, 6, 7, 8], max_tokens=2)
    for refused in (
        lambda: engine.prefill_only(pre),
        lambda: engine.generate_remote(pre, 5, None, None),
    ):
        with pytest.raises(ValueError, match="diffusion over blocks"):
            await refused()
    with pytest.raises(ValueError, match="diffusion over blocks"):
        engine.ingest_prefix(list(range(16)), None, None)
    with pytest.raises(ValueError, match="diffusion over blocks"):
        engine.export_prefix(list(range(16)))
    await engine.close()


# --------------- every other family's step programs are the parent's

# sha256 (first 16 hex) of str(jax.make_jaxpr(...)) of the decode scan
# (`_decode_multi`, width 4) and the prefill step (`_model_step`, 2 rows of
# 16) of an engine built as below, TAKEN ON THE PARENT TREE (8ff63cd)
# before this family's first edit: the state's optional member, the mask's
# block on `AttnSpec` and the kernel's static argument leave every other
# preset's program as it was
PARENT_STEP_JAXPR = {
    ("tiny", "decode", "gather"): "53d73051de544766",
    ("tiny", "prefill", "gather"): "453a76dfde34a399",
    ("tiny", "decode", "pallas"): "7f5a7a9b83715148",
    ("tiny", "prefill", "pallas"): "e2b0478e28ee4f62",
    ("tiny-mla", "decode", "gather"): "be1b3b8432cdbdae",
    ("tiny-mla", "prefill", "gather"): "f0042c32c6cb9dd1",
    ("tiny-mla", "decode", "pallas"): "0cb35e08504a7245",
    ("tiny-mla", "prefill", "pallas"): "2a693c0b441aa8fd",
    ("tiny-mimo", "decode", "gather"): "38a2d541cfb1d804",
    ("tiny-mimo", "prefill", "gather"): "ceb5610f2bb8b06a",
    ("tiny-mimo", "decode", "pallas"): "63846dbc46e48fdc",
    ("tiny-mimo", "prefill", "pallas"): "56142e21bbf9d3fe",
    ("tiny-granite", "decode", "gather"): "b78fabd2f42d6b5a",
    ("tiny-granite", "prefill", "gather"): "21efdf8dc603ac2e",
    ("tiny-granite", "decode", "pallas"): "5e13fd8c9b7ab9bb",
    ("tiny-granite", "prefill", "pallas"): "b3df6396844afd5b",
    ("tiny-xing", "decode", "gather"): "91bd8f85dae17c97",
    ("tiny-xing", "prefill", "gather"): "fd98a3a94f569fc0",
    ("tiny-xing", "decode", "pallas"): "38096d3223d44316",
    ("tiny-xing", "prefill", "pallas"): "96d72987fb965991",
}


def _step_jaxprs(engine, rows=2, bucket=16):
    st = engine._take_state()
    w = engine._host_rows_i.shape[1]

    def i32(*s):
        return jax.ShapeDtypeStruct(s, jnp.int32)

    def f32(*s):
        return jax.ShapeDtypeStruct(s, jnp.float32)

    def kinds(a):
        return (a, a) if engine._hybrid else a

    ppc = -(-bucket // engine.page_size)
    pallas = engine._attn_pallas
    return {
        "decode": jax.make_jaxpr(
            engine._decode_multi, static_argnums=(5, 6, 7))(
            engine.params, engine.kv, st, i32(4, 4 + w), f32(4, 5),
            True, False, False),
        "prefill": jax.make_jaxpr(
            engine._model_step, static_argnums=(13, 14, 15))(
            engine.params, engine.kv, st, i32(rows, bucket),
            i32(rows, bucket), kinds(i32(rows * bucket)),
            kinds(i32(rows, engine._smat_width)), i32(rows, 5), f32(rows, 5),
            kinds(i32(rows * ppc)) if pallas else None,
            kinds(i32(rows, 4)) if pallas else None, None, None,
            True, False, False),
    }


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("name", [
    "tiny", "tiny-mla", "tiny-mimo", "tiny-granite", "tiny-xing"])
def test_other_families_step_programs_are_the_parents(name, backend):
    engine = make_engine(model=PRESETS[name], attn_backend=backend)
    assert engine._state.dlm is None and not engine._dlm
    for prog, jaxpr in _step_jaxprs(engine).items():
        got = hashlib.sha256(str(jaxpr).encode()).hexdigest()[:16]
        assert got == PARENT_STEP_JAXPR[(name, prog, backend)], (
            name, prog, backend)
