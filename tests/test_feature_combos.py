"""Cross-feature combinations nothing else guards: int8 quantization
composed with disaggregated KV transfer, the HBM→host offload tier, and
the logprobs/penalty sampling paths — regressions here would only show
up in production topologies, not per-feature suites."""

import asyncio

import numpy as np

from dynamo_tpu.engine import EngineConfig, JaxEngine
from dynamo_tpu.llm.protocols.common import (
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)
from dynamo_tpu.models import config as cfgmod
from dynamo_tpu.runtime.pipeline.context import Context

CFG = cfgmod.get_config("tiny")


def make_engine(**kw) -> JaxEngine:
    defaults = dict(
        model=CFG,
        dtype="float32",
        quantization="int8",
        page_size=8,
        num_pages=64,
        max_batch_size=4,
        max_model_len=128,
        prefill_chunk=32,
        seed=0,
    )
    defaults.update(kw)
    return JaxEngine(EngineConfig(**defaults))


def req(prompt, max_tokens=6, **so):
    return PreprocessedRequest(
        token_ids=list(prompt),
        stop_conditions=StopConditions(max_tokens=max_tokens, ignore_eos=True),
        sampling_options=SamplingOptions(greedy=True, **so),
    )


async def collect(engine, pre):
    frames = [f async for f in await engine.generate(Context(pre.to_dict()))]
    return [t for f in frames for t in f.get("token_ids") or []], frames


async def test_quant_disagg_roundtrip_bit_identical():
    """int8 prefill_only -> generate_remote must reproduce int8 local
    greedy exactly (same quantized weights, KV transferred bf16)."""
    prompt = list(range(30, 70))
    prefill_e, decode_e, local_e = make_engine(), make_engine(), make_engine()
    ref, _ = await collect(local_e, req(prompt))
    first, k, v, ks, vs = await prefill_e.prefill_only(req(prompt))
    assert first == ref[0]
    out = [
        f async for f in await decode_e.generate_remote(
            Context(req(prompt).to_dict()), first, k, v
        )
    ]
    got = [t for f in out for t in f.get("token_ids") or []]
    assert got == ref
    for e in (prefill_e, decode_e, local_e):
        await e.close()


async def test_quant_offload_prefix_hits_preserve_outputs():
    """int8 + host KV tier under page pressure: prefix hits restored
    from the host pool must not change greedy outputs."""
    engine = make_engine(
        num_pages=24, host_kv_pages=64, offload_batch_pages=4,
        max_model_len=96, prefill_chunk=16,
    )
    rng = np.random.RandomState(0)
    prompts = [
        [int(x) for x in rng.randint(2, 250, size=rng.randint(20, 50))]
        for _ in range(8)
    ]
    first = await asyncio.gather(*(collect(engine, req(p)) for p in prompts))
    again = await asyncio.gather(*(collect(engine, req(p)) for p in prompts[:3]))
    for (tokens, _), (ref_tokens, _) in zip(again, first[:3]):
        assert tokens == ref_tokens
    await engine.close()


async def test_quant_with_logprobs_and_penalties():
    """The three sampling step variants all run on quantized weights."""
    engine = make_engine()
    tokens, frames = await collect(
        engine, req([5, 6, 7], logprobs=True, top_logprobs=2)
    )
    tf = [f for f in frames if f.get("token_ids")]
    # a frame carries what one landing brought: a column per token
    assert all(len(f["log_probs"]) == len(f["token_ids"])
               == len(f["top_log_probs"]) for f in tf)
    assert all(lp <= 0.0 for f in tf for lp in f["log_probs"])
    assert all(len(alts) == 2 for f in tf for alts in f["top_log_probs"])

    tokens2, _ = await collect(
        engine, req([20, 21, 22], max_tokens=8, frequency_penalty=100.0)
    )
    seen = {20, 21, 22}
    for t in tokens2:
        assert t not in seen
        seen.add(t)
    await engine.close()


async def test_gemma_config_serves_quantized():
    """Gemma-family forward (GeGLU, scaled embeddings, (1+w) norms)
    through the full engine, int8-quantized."""
    gcfg = CFG.with_(
        hidden_act="gelu_pytorch_tanh",
        scale_embeddings=True,
        norm_weight_offset=1.0,
        rms_norm_eps=1e-6,
    )
    engine = make_engine(model=gcfg)
    tokens, frames = await collect(engine, req([7, 8, 9], max_tokens=5))
    assert len(tokens) == 5
    # unquantized sanity run: random tiny weights give near-uniform
    # logits, so int8-vs-bf16 greedy agreement is NOT guaranteed here —
    # numeric agreement is asserted by test_model's HF oracle instead
    engine2 = make_engine(model=gcfg, quantization=None)
    tokens2, _ = await collect(engine2, req([7, 8, 9], max_tokens=5))
    assert len(tokens2) == 5
    for e in (engine, engine2):
        await e.close()
