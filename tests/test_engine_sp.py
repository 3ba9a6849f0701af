"""Engine serving with sequence parallelism: sp=2 (and sp x tp) engines
must reproduce the single-device engine's greedy output exactly."""

from __future__ import annotations

import pytest

from dynamo_tpu.models.config import get_config
from dynamo_tpu.parallel.mesh import MeshConfig

from .test_engine import collect, greedy_request, make_engine

CFG4 = get_config("tiny").with_(dtype="float32", num_layers=4)


async def test_sp2_engine_ring_prefill_matches_single_device():
    """sp=2 engine (ring-attention whole-prompt prefill) must reproduce
    the single-device engine's greedy output exactly."""
    prompt = [5, 17, 42, 9, 88, 3, 14, 21, 21, 4, 19, 77, 8, 2, 30, 6]
    ref_engine = make_engine(model=CFG4, prefill_chunk=128)
    ref, _, _ = await collect(ref_engine, greedy_request(prompt, max_tokens=6))
    await ref_engine.close()

    engine = make_engine(
        model=CFG4, mesh=MeshConfig(sp=2), prefill_chunk=128
    )
    tokens, finish, _ = await collect(
        engine, greedy_request(prompt, max_tokens=6)
    )
    assert finish == "length" and tokens == ref
    await engine.close()


async def test_sp2_tp2_engine_concurrent():
    import asyncio

    prompt_a = list(range(2, 2 + 20))
    prompt_b = [9, 8, 7, 6, 5]
    ref_engine = make_engine(model=CFG4, prefill_chunk=128)
    ref_a, _, _ = await collect(ref_engine, greedy_request(prompt_a, max_tokens=4))
    ref_b, _, _ = await collect(ref_engine, greedy_request(prompt_b, max_tokens=4))
    await ref_engine.close()

    engine = make_engine(
        model=CFG4, mesh=MeshConfig(sp=2, tp=2), prefill_chunk=128
    )
    (a, _, _), (b, _, _) = await asyncio.gather(
        collect(engine, greedy_request(prompt_a, max_tokens=4)),
        collect(engine, greedy_request(prompt_b, max_tokens=4)),
    )
    assert a == ref_a and b == ref_b
    await engine.close()


def test_sp_mode_requires_whole_prompt_prefill():
    with pytest.raises(ValueError, match="prefill_chunk"):
        make_engine(model=CFG4, mesh=MeshConfig(sp=2), prefill_chunk=32)


async def test_sp2_engine_keeps_prefix_cache():
    """sp>1 now composes with the prefix cache: a
    repeated prompt's second serve rides cached pages (the ring runs
    only over the uncached tail) and stays bit-identical."""
    prompt = list(range(40, 40 + 24))  # 3 pages of 8
    ref_engine = make_engine(model=CFG4, prefill_chunk=128)
    ref, _, _ = await collect(ref_engine, greedy_request(prompt, max_tokens=5))
    await ref_engine.close()

    engine = make_engine(model=CFG4, mesh=MeshConfig(sp=2), prefill_chunk=128)
    first, _, frames1 = await collect(
        engine, greedy_request(prompt, max_tokens=5)
    )
    assert first == ref
    second, _, frames2 = await collect(
        engine, greedy_request(prompt, max_tokens=5)
    )
    assert second == ref, f"cached-prefix ring diverged: {second} vs {ref}"
    meta = (frames2[0].get("meta") or {})
    assert meta.get("prefix_cached_tokens", 0) >= 16, meta
    # a prefix-extension prompt also rides the cache
    longer = prompt + [3, 1, 4, 1, 5, 9, 2, 6]
    ref_engine = make_engine(model=CFG4, prefill_chunk=128)
    ref_l, _, _ = await collect(
        ref_engine, greedy_request(longer, max_tokens=4)
    )
    await ref_engine.close()
    got_l, _, frames3 = await collect(
        engine, greedy_request(longer, max_tokens=4)
    )
    assert got_l == ref_l
    assert (frames3[0].get("meta") or {}).get("prefix_cached_tokens", 0) >= 16
    await engine.close()


async def test_sp2_engine_int8_kv_serving():
    """sp=2 (ring prefill) composes with the int8 KV cache: pool writes
    quantize, the cached-prefix ring dequantizes its gathered block, and
    decode serves from int8 pages. Greedy must match the single-device
    int8-KV engine, including a prefix-cache continuation."""
    prompt = list(range(7, 7 + 24))
    ref_engine = make_engine(
        model=CFG4, prefill_chunk=128, kv_quantization="int8"
    )
    ref, _, _ = await collect(ref_engine, greedy_request(prompt, max_tokens=6))
    await ref_engine.close()

    engine = make_engine(
        model=CFG4, mesh=MeshConfig(sp=2), prefill_chunk=128,
        kv_quantization="int8",
    )
    assert engine.kv.quantized and not engine._kv_packed
    tokens, finish, _ = await collect(
        engine, greedy_request(prompt, max_tokens=6)
    )
    assert finish == "length" and tokens == ref
    # prefix-cache continuation: the cached rows ride the int8 pool
    # through the ring's prefix block (dequantized on gather)
    t2, _, frames = await collect(engine, greedy_request(prompt, max_tokens=4))
    assert t2 == ref[:4]
    assert frames[0]["meta"]["prefix_cached_tokens"] > 0
    await engine.close()

    # sp x tp composition: the scale-pool row layout is tp-BLOCKED
    # (ops/quant.kv_scale_subl) — the ring spec must carry the engine's
    # kv_tp or head scales scatter into padding rows and decode reads
    # 1.0 (caught by review: wrong tokens on sp=2 x tp=2)
    engine2 = make_engine(
        model=CFG4, mesh=MeshConfig(sp=2, tp=2), prefill_chunk=128,
        kv_quantization="int8",
    )
    t3, finish3, _ = await collect(
        engine2, greedy_request(prompt, max_tokens=6)
    )
    assert finish3 == "length" and t3 == ref, f"sp2xtp2 int8 diverged: {t3} vs {ref}"
    await engine2.close()
