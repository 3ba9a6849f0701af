"""int8 KV cache: quantized page pools + f32 scale pools end to end.

The decode phase streams every live KV page per step — at wide batches
decode attention is most of an int8-weights step (PERF.md section 5),
all of it page bandwidth. int8 pages halve that traffic. These tests pin the
scheme (ops/quant.quantize_kv_rows: per-token-per-kv-head symmetric
absmax) against the jnp oracle, the three pallas kernels (interpret
mode), the serving engine, the offload tier, the disagg wire (including
mixed int8/bf16 pairs), and the device-path transfer.

Reference counterpart: the FP8 KV cache of the reference's vLLM
baselines (docs/architecture.md:76-83) plus the block-copy machinery
(lib/llm/src/kernels/block_copy.cu) that moves those pages.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine import EngineConfig, JaxEngine
from dynamo_tpu.llm.protocols.common import (
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)
from dynamo_tpu.models import config as cfgmod
from dynamo_tpu.models import llama
from dynamo_tpu.ops.quant import (
    dequantize_kv_rows,
    quantize_kv_rows,
    unpack_kv_slots,
)
from dynamo_tpu.runtime.pipeline.context import Context

CFG = cfgmod.get_config("tiny")


def make_engine(**kw) -> JaxEngine:
    defaults = dict(
        model=CFG,
        dtype="float32",
        kv_quantization="int8",
        page_size=8,
        num_pages=64,
        max_batch_size=4,
        max_model_len=128,
        prefill_chunk=32,
        seed=0,
    )
    defaults.update(kw)
    return JaxEngine(EngineConfig(**defaults))


def req(prompt, max_tokens=8, **so):
    return PreprocessedRequest(
        token_ids=list(prompt),
        stop_conditions=StopConditions(max_tokens=max_tokens, ignore_eos=True),
        sampling_options=SamplingOptions(greedy=True, **so),
    )


async def collect(engine, pre):
    frames = [f async for f in await engine.generate(Context(pre.to_dict()))]
    return [t for f in frames for t in f.get("token_ids") or []], frames


# ------------------------------------------------------------- unit level


def test_kv_rows_roundtrip():
    key = jax.random.PRNGKey(0)
    rows = jax.random.normal(key, (7, 4 * 32)) * 3.0
    q, s = quantize_kv_rows(rows, 4)
    assert q.dtype == jnp.int8 and s.shape == (7, 4)
    back = dequantize_kv_rows(q, s)
    rel = float(jnp.max(jnp.abs(back - rows)) / jnp.max(jnp.abs(rows)))
    assert rel < 0.01  # 8-bit absmax: <1% relative error
    # zero rows stay exactly zero (scale sentinel 1.0, no NaN)
    qz, sz = quantize_kv_rows(jnp.zeros((2, 128)), 4)
    assert np.all(np.asarray(sz) == 1.0)
    assert np.all(np.asarray(dequantize_kv_rows(qz, sz)) == 0.0)


def test_forward_oracle_agreement():
    """Gather-path forward with an int8 KV cache tracks the bf16-KV
    forward: same argmax, logit cosine > 0.999."""
    cfg = CFG
    key = jax.random.PRNGKey(0)
    params = llama.init_params(cfg, key, dtype=jnp.float32)
    B, T, num_slots = 2, 16, 256
    tokens = jax.random.randint(key, (B, T), 0, cfg.vocab_size)
    positions = jnp.tile(jnp.arange(T), (B, 1))
    wslots = (jnp.arange(B * T) + 8).astype(jnp.int32)
    smat = jnp.concatenate(
        [wslots.reshape(B, T), jnp.zeros((B, 8), jnp.int32)], axis=1
    )
    kv_f = llama.init_kv_cache(cfg, num_slots, dtype=jnp.float32)
    kv_q = llama.init_kv_cache(cfg, num_slots, kv_quant="int8")
    h_f, _ = llama.forward(params, cfg, tokens, positions, kv_f, wslots, smat)
    h_q, kv_q2 = llama.forward(params, cfg, tokens, positions, kv_q, wslots, smat)
    assert kv_q2.k[0].dtype == jnp.int8 and kv_q2.ks[0].dtype == jnp.float32
    lg_f = llama.logits(params, cfg, h_f[:, -1])
    lg_q = llama.logits(params, cfg, h_q[:, -1])
    cos = jnp.sum(lg_f * lg_q) / (
        jnp.linalg.norm(lg_f) * jnp.linalg.norm(lg_q)
    )
    assert float(cos) > 0.999
    assert bool((jnp.argmax(lg_f, -1) == jnp.argmax(lg_q, -1)).all())


# --------------------------------------------------------- pallas kernels


def _to_pool(dense, num_pages, page, kh):
    """Dense per-slot scales [N, K] -> pool layout [P, SUBL, S]."""
    from dynamo_tpu.ops.quant import init_kv_scale_pool, scatter_kv_scales

    pool = init_kv_scale_pool(num_pages, page, kh)
    slots = jnp.arange(num_pages * page, dtype=jnp.int32)
    return scatter_kv_scales(pool, slots, dense, kh)


def _quant_setup(seed=0, W=4):
    key = jax.random.PRNGKey(seed)
    B, H, KH, Hd, page = 3, 8, 4, 32, 8
    kw = KH * Hd
    num_pages = B * W + 1
    num_slots = num_pages * page
    kf = jax.random.normal(key, (num_slots, kw))
    vf = jax.random.normal(jax.random.fold_in(key, 1), (num_slots, kw))
    kq, ks = quantize_kv_rows(kf, KH)
    vq, vs = quantize_kv_rows(vf, KH)
    ks_pool = _to_pool(ks, num_pages, page, KH)
    vs_pool = _to_pool(vs, num_pages, page, KH)
    q = jax.random.normal(jax.random.fold_in(key, 2), (B, H, Hd))
    # disjoint pages per sequence (the engine's invariant)
    tables = jnp.asarray(
        [[1 + i * W + j for j in range(W)] for i in range(B)], jnp.int32
    )
    return B, H, KH, Hd, page, kw, q, kq, ks_pool, vq, vs_pool, tables


def test_fused_decode_kernel_int8():
    from dynamo_tpu.ops.attention import paged_attention, slots_from_pages
    from dynamo_tpu.ops.pallas_attention import fused_paged_decode_attention

    B, H, KH, Hd, page, kw, q, kq, ks, vq, vs, tables = _quant_setup()
    key = jax.random.PRNGKey(9)
    newk = jax.random.normal(key, (B, kw))
    newv = jax.random.normal(jax.random.fold_in(key, 1), (B, kw))
    from dynamo_tpu.ops.quant import gather_kv_scales, kv_scale_subl, _scale_rows

    nkq, nks = quantize_kv_rows(newk, KH)
    nvq, nvs = quantize_kv_rows(newv, KH)
    subl = kv_scale_subl(KH)
    rows = _scale_rows(KH, 1)
    nks_p = jnp.ones((B, subl), jnp.float32).at[:, rows].set(nks)
    nvs_p = jnp.ones((B, subl), jnp.float32).at[:, rows].set(nvs)
    lengths = jnp.asarray([10, 17, 32], jnp.int32)
    wpos = lengths - 1
    out, k2, v2, ks2, vs2 = fused_paged_decode_attention(
        q, nkq, nvq, kq, vq, tables, lengths, wpos, ks, vs, nks_p, nvs_p,
        page_size=page, pages_per_block=2, nbuf=2, interpret=True,
    )
    # oracle on dequantized pools with the quantized rows injected
    all_slots = jnp.arange(kq.shape[0], dtype=jnp.int32)
    kd = dequantize_kv_rows(kq, gather_kv_scales(ks, all_slots, KH))
    vd = dequantize_kv_rows(vq, gather_kv_scales(vs, all_slots, KH))
    slots = jnp.asarray([
        int(tables[b, int(wpos[b]) // page]) * page + int(wpos[b]) % page
        for b in range(B)
    ])
    kd = kd.at[slots].set(dequantize_kv_rows(nkq, nks))
    vd = vd.at[slots].set(dequantize_kv_rows(nvq, nvs))
    smat = slots_from_pages(tables, page)
    ref = paged_attention(q[:, None], kd, vd, smat, (lengths - 1)[:, None])[:, 0]
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=5e-3)
    # cache update: int8 rows + scale columns landed in their pages
    sc2 = gather_kv_scales(ks2, slots, KH)
    sv2 = gather_kv_scales(vs2, slots, KH)
    for b in range(B):
        s = int(slots[b])
        np.testing.assert_array_equal(np.asarray(k2[s]), np.asarray(nkq[b]))
        np.testing.assert_allclose(np.asarray(sc2[b]), np.asarray(nks[b]))
        np.testing.assert_array_equal(np.asarray(v2[s]), np.asarray(nvq[b]))
        np.testing.assert_allclose(np.asarray(sv2[b]), np.asarray(nvs[b]))


def test_readonly_decode_kernel_int8():
    from dynamo_tpu.ops.attention import paged_attention, slots_from_pages
    from dynamo_tpu.ops.pallas_attention import paged_decode_attention

    B, H, KH, Hd, page, kw, q, kq, ks, vq, vs, tables = _quant_setup(3)
    lengths = jnp.asarray([9, 24, 32], jnp.int32)
    out = paged_decode_attention(
        q, kq, vq, tables, lengths, ks, vs,
        page_size=page, pages_per_block=2, interpret=True,
    )
    from dynamo_tpu.ops.quant import gather_kv_scales

    all_slots = jnp.arange(kq.shape[0], dtype=jnp.int32)
    smat = slots_from_pages(tables, page)
    ref = paged_attention(
        q[:, None],
        dequantize_kv_rows(kq, gather_kv_scales(ks, all_slots, KH)),
        dequantize_kv_rows(vq, gather_kv_scales(vs, all_slots, KH)),
        smat, (lengths - 1)[:, None],
    )[:, 0]
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=5e-3)


def test_flash_prefill_kernel_int8():
    from dynamo_tpu.ops.attention import paged_attention, slots_from_pages
    from dynamo_tpu.ops.pallas_prefill import flash_prefill_attention

    B, H, KH, Hd, page, kw, _, kq, ks, vq, vs, tables = _quant_setup(5)
    key = jax.random.PRNGKey(11)
    T = 16
    qp = jax.random.normal(key, (B, T, H, Hd))
    pos0 = jnp.asarray([0, 8, 16], jnp.int32)
    tval = jnp.asarray([16, 8, 16], jnp.int32)
    out = flash_prefill_attention(
        qp, kq, vq, tables, pos0, tval, ks, vs,
        page_size=page, t_tile=8, pages_per_block=2, interpret=True,
    )
    from dynamo_tpu.ops.quant import gather_kv_scales

    all_slots = jnp.arange(kq.shape[0], dtype=jnp.int32)
    smat = slots_from_pages(tables, page)
    posm = pos0[:, None] + jnp.arange(T)[None, :]
    ref = paged_attention(
        qp,
        dequantize_kv_rows(kq, gather_kv_scales(ks, all_slots, KH)),
        dequantize_kv_rows(vq, gather_kv_scales(vs, all_slots, KH)),
        smat, posm,
    )
    mask = (jnp.arange(T)[None] < tval[:, None])[..., None, None]
    err = float(jnp.max(jnp.abs((out - ref) * mask)))
    assert err < 2e-2


def test_paged_kv_write_kernel_int8():
    from dynamo_tpu.ops.pallas_kv_write import paged_kv_write
    from dynamo_tpu.ops.quant import gather_kv_scales

    KH, Hd, page = 4, 32, 8
    kw = KH * Hd
    num_pages = 6
    num_slots = num_pages * page
    key = jax.random.PRNGKey(2)
    kq, ks = quantize_kv_rows(jax.random.normal(key, (num_slots, kw)), KH)
    vq, vs = quantize_kv_rows(
        jax.random.normal(jax.random.fold_in(key, 1), (num_slots, kw)), KH
    )
    ks_pool = _to_pool(ks, num_pages, page, KH)
    vs_pool = _to_pool(vs, num_pages, page, KH)
    nk, nks = quantize_kv_rows(
        jax.random.normal(jax.random.fold_in(key, 2), (2, page, kw)), KH
    )
    nv, nvs = quantize_kv_rows(
        jax.random.normal(jax.random.fold_in(key, 3), (2, page, kw)), KH
    )
    # source scale tiles in pool layout: [2, SUBL, page]
    nks_t = _to_pool(nks.reshape(2 * page, KH), 2, page, KH)
    nvs_t = _to_pool(nvs.reshape(2 * page, KH), 2, page, KH)
    table = jnp.asarray([3, 5], jnp.int32)
    kq_host = np.asarray(kq)  # pools are donated below
    k2, v2, ks2, vs2 = paged_kv_write(
        kq, vq, table, nk, nv, ks_pool, vs_pool, nks_t, nvs_t,
        page_size=page, interpret=True,
    )
    for i, pid in enumerate([3, 5]):
        sl = slice(pid * page, (pid + 1) * page)
        slots = jnp.arange(pid * page, (pid + 1) * page, dtype=jnp.int32)
        np.testing.assert_array_equal(np.asarray(k2[sl]), np.asarray(nk[i]))
        np.testing.assert_allclose(
            np.asarray(gather_kv_scales(ks2, slots, KH)), np.asarray(nks[i])
        )
        np.testing.assert_array_equal(np.asarray(v2[sl]), np.asarray(nv[i]))
        np.testing.assert_allclose(
            np.asarray(gather_kv_scales(vs2, slots, KH)), np.asarray(nvs[i])
        )
    # untouched pages intact
    np.testing.assert_array_equal(np.asarray(k2[: 3 * page]), kq_host[: 3 * page])


# ------------------------------------------------------------ engine level


async def test_engine_int8_kv_greedy_matches_bf16_kv():
    e_f = make_engine(kv_quantization=None)
    e_q = make_engine()
    prompt = list(range(30, 50))
    a, _ = await collect(e_f, req(prompt))
    b, _ = await collect(e_q, req(prompt))
    match = sum(x == y for x, y in zip(a, b))
    assert match >= len(a) - 1, f"int8-KV diverged: {a} vs {b}"
    # prefix-cache continuation serves on quantized pages
    c, frames = await collect(e_q, req(prompt, 4))
    assert len(c) == 4
    assert frames[0]["meta"]["prefix_cached_tokens"] > 0
    await e_f.close()
    await e_q.close()


async def test_engine_int8_kv_preemption_and_batch():
    """Concurrent streams under page pressure (preemption + re-prefill
    over quantized pages) still serve full streams."""
    import asyncio

    engine = make_engine(num_pages=20, max_model_len=96, prefill_chunk=16)
    prompts = [[10 + 7 * k, 11 + 7 * k, 12 + 7 * k] for k in range(6)]
    results = await asyncio.gather(*(
        collect(engine, req(p, 8)) for p in prompts
    ))
    for tokens, _ in results:
        assert len(tokens) == 8
    await engine.close()


async def test_engine_int8_kv_offload_restore():
    """Host tier stores int8 pages + scales; restore-after-eviction
    preserves greedy outputs."""
    engine = make_engine(
        num_pages=24, host_kv_pages=64, offload_batch_pages=4,
        max_model_len=96, prefill_chunk=16, page_size=8,
    )
    prompt = list(range(40, 72))  # 4 pages
    ref, _ = await collect(engine, req(prompt, 6))
    # churn through enough other prompts to evict the HBM prefix
    import asyncio

    for k in range(6):
        await collect(engine, req([100 + 9 * k + j for j in range(24)], 4))
        await asyncio.sleep(0.05)
    got, frames = await collect(engine, req(prompt, 6))
    assert got == ref
    await engine.close()


async def test_disagg_int8_wire_roundtrip():
    """int8-KV prefiller -> int8-KV decoder: the wire carries int8 +
    scales and greedy continuation is bit-identical to local."""
    pe, de, le = make_engine(), make_engine(), make_engine()
    prompt = list(range(30, 70))
    ref, _ = await collect(le, req(prompt, 6))
    first, k, v, ks, vs = await pe.prefill_only(req(prompt, 6))
    assert k.dtype == np.int8 and ks is not None
    assert ks.shape == (CFG.num_layers, len(prompt), CFG.num_kv_heads)
    out = [
        f async for f in await de.generate_remote(
            Context(req(prompt, 6).to_dict()), first, k, v, ks, vs
        )
    ]
    got = [t for f in out for t in f.get("token_ids") or []]
    assert got == ref
    for e in (pe, de, le):
        await e.close()


@pytest.mark.parametrize("quant_prefill", [True, False])
async def test_disagg_mixed_dtype_pairs(quant_prefill):
    """int8 <-> bf16 engine pairs convert the wire payload on injection
    and still serve the full stream (exact match not required across the
    dtype boundary, first token is)."""
    pe = make_engine(kv_quantization="int8" if quant_prefill else None)
    de = make_engine(kv_quantization=None if quant_prefill else "int8")
    prompt = list(range(30, 60))
    first, k, v, ks, vs = await pe.prefill_only(req(prompt, 6))
    assert (ks is not None) == quant_prefill
    out = [
        f async for f in await de.generate_remote(
            Context(req(prompt, 6).to_dict()), first, k, v, ks, vs
        )
    ]
    got = [t for f in out for t in f.get("token_ids") or []]
    assert len(got) == 6
    await pe.close()
    await de.close()


async def test_device_transfer_int8_pair():
    """Device-path transfer between two int8-KV engines moves pages +
    scales; a mixed pair is rejected toward the host-staged plane."""
    from dynamo_tpu.engine.kv_transfer import device_transfer_kv

    src, dst = make_engine(), make_engine()
    prompt = list(range(20, 44))  # 3 pages
    ref, _ = await collect(src, req(prompt, 1))
    # source pages now hold the prompt KV in its prefix cache
    hashes = None
    from dynamo_tpu.llm.tokens import TokenBlockSequence

    blocks = TokenBlockSequence(prompt, src.page_size)
    hashes = blocks.sequence_hashes()
    src_pages = src.allocator.match_prefix(hashes)
    assert len(src_pages) == 3
    dst_pages = dst.allocator.allocate(3)
    device_transfer_kv(src, dst, src_pages, dst_pages, 24)
    # spot-check: dst pool rows equal src pool rows (int8 + scales)
    s_slot = src_pages[0] * src.page_size
    d_slot = dst_pages[0] * dst.page_size
    np.testing.assert_array_equal(
        np.asarray(src.kv.k[0][s_slot]), np.asarray(dst.kv.k[0][d_slot])
    )
    from dynamo_tpu.ops.quant import gather_kv_scales

    kh = CFG.num_kv_heads
    np.testing.assert_allclose(
        np.asarray(gather_kv_scales(
            src.kv.ks[0], jnp.asarray([s_slot]), kh)),
        np.asarray(gather_kv_scales(
            dst.kv.ks[0], jnp.asarray([d_slot]), kh)),
    )
    mixed = make_engine(kv_quantization=None)
    with pytest.raises(ValueError, match="matching kv_quantization"):
        device_transfer_kv(src, mixed, src_pages, dst_pages, 24)
    src.allocator.release(src_pages)
    for e in (src, dst, mixed):
        await e.close()


# ------------------------------------------------- int32-PACKED pools


def test_pack_unpack_roundtrip():
    from dynamo_tpu.ops.quant import (
        gather_packed_kv,
        pack_kv_slots,
        unpack_kv_slots,
    )

    rng = np.random.RandomState(0)
    rows = jnp.asarray(rng.randint(-127, 128, size=(16, 64)), jnp.int8)
    packed = pack_kv_slots(rows)
    assert packed.shape == (4, 64) and packed.dtype == jnp.int32
    np.testing.assert_array_equal(
        np.asarray(unpack_kv_slots(packed)), np.asarray(rows)
    )
    # int32 row t must hold token rows 4t..4t+3 as little-endian bytes
    # (pltpu.bitcast's order on the chip; chip_smoke.py's int8 phase holds it)
    u = np.asarray(packed).view(np.uint32)
    for j in range(4):
        np.testing.assert_array_equal(
            ((u >> (8 * j)) & 0xFF).astype(np.uint8).view(np.int8),
            np.asarray(rows)[j::4],
        )
    # arbitrary-slot gather matches the dense rows
    slots = jnp.asarray([0, 5, 11, 2, 15], jnp.int32)
    np.testing.assert_array_equal(
        np.asarray(gather_packed_kv(packed, slots)),
        np.asarray(rows)[np.asarray(slots)],
    )


def test_fused_decode_kernel_packed_matches_unpacked():
    """The int32-packed decode kernel is BIT-identical to the dense-int8
    kernel on both the attention output and the written-back pages."""
    from dynamo_tpu.ops.pallas_attention import fused_paged_decode_attention
    from dynamo_tpu.ops.quant import (
        kv_scale_subl,
        _scale_rows,
        pack_kv_slots,
        unpack_kv_slots,
    )

    B, H, KH, Hd, page, kw, q, kq, ks, vq, vs, tables = _quant_setup(7)
    key = jax.random.PRNGKey(21)
    nkq, nks = quantize_kv_rows(jax.random.normal(key, (B, kw)), KH)
    nvq, nvs = quantize_kv_rows(
        jax.random.normal(jax.random.fold_in(key, 1), (B, kw)), KH
    )
    subl = kv_scale_subl(KH)
    rows = _scale_rows(KH, 1)
    nks_p = jnp.ones((B, subl), jnp.float32).at[:, rows].set(nks)
    nvs_p = jnp.ones((B, subl), jnp.float32).at[:, rows].set(nvs)
    lengths = jnp.asarray([10, 17, 31], jnp.int32)
    wpos = lengths - 1
    kwargs = dict(page_size=page, pages_per_block=2, nbuf=2, interpret=True)
    out_u, k_u, v_u, ks_u, vs_u = fused_paged_decode_attention(
        q, nkq, nvq, kq, vq, tables, lengths, wpos, ks, vs, nks_p, nvs_p,
        **kwargs,
    )
    out_p, k_p, v_p, ks_p2, vs_p2 = fused_paged_decode_attention(
        q, nkq, nvq, pack_kv_slots(kq), pack_kv_slots(vq), tables, lengths,
        wpos, ks, vs, nks_p, nvs_p, **kwargs,
    )
    assert k_p.dtype == jnp.int32 and k_p.shape[0] == kq.shape[0] // 4
    np.testing.assert_array_equal(np.asarray(out_p), np.asarray(out_u))
    np.testing.assert_array_equal(
        np.asarray(unpack_kv_slots(k_p)), np.asarray(k_u)
    )
    np.testing.assert_array_equal(
        np.asarray(unpack_kv_slots(v_p)), np.asarray(v_u)
    )
    np.testing.assert_array_equal(np.asarray(ks_p2), np.asarray(ks_u))
    np.testing.assert_array_equal(np.asarray(vs_p2), np.asarray(vs_u))


# (pages_per_block, pages the LAST block of a sequence holds): the first,
# a middle and the last page of a block
LIVE_CASES = [(2, 1), (2, 2), (4, 1), (4, 3), (4, 4)]


def _new_rows(B, kw, KH, seed):
    """Quantized new-token rows and their scale columns in the pool's
    sublane-row layout."""
    from dynamo_tpu.ops.quant import kv_scale_subl, _scale_rows

    key = jax.random.PRNGKey(seed)
    nkq, nks = quantize_kv_rows(jax.random.normal(key, (B, kw)), KH)
    nvq, nvs = quantize_kv_rows(
        jax.random.normal(jax.random.fold_in(key, 1), (B, kw)), KH
    )
    rows = _scale_rows(KH, 1)
    pad = jnp.ones((B, kv_scale_subl(KH)), jnp.float32)
    return (nkq, nks, pad.at[:, rows].set(nks),
            nvq, nvs, pad.at[:, rows].set(nvs))


@pytest.mark.parametrize("packed", [False, True], ids=["dense", "packed"])
@pytest.mark.parametrize("ppb,tail", LIVE_CASES)
def test_decode_kernel_int8_never_touches_pages_not_held(ppb, tail, packed):
    """Every page no sequence holds, the trash page included, carries NaN
    scales: the output and the written pages must equal the oracle's on
    clean pools. A scale tile of NaN times a masked probability of 0 is
    NaN, so this holds only if the COMPUTE skips what the copy skipped."""
    from dynamo_tpu.ops.attention import paged_attention, slots_from_pages
    from dynamo_tpu.ops.pallas_attention import fused_paged_decode_attention
    from dynamo_tpu.ops.quant import (
        gather_kv_scales,
        pack_kv_slots,
        unpack_kv_slots,
    )

    B, H, KH, Hd, page, kw, q, kq, ks, vq, vs, tables = _quant_setup(
        11, W=3 * ppb
    )
    t_blk = ppb * page
    # the last block holds `tail` pages: inside the first block; one block
    # in, ending on a page's end; two blocks in, the newest token the
    # FIRST of its page
    lengths = np.asarray([
        (tail - 1) * page + 3,
        t_blk + tail * page,
        2 * t_blk + (tail - 1) * page + 1,
    ], np.int32)
    wpos = lengths - 1
    nkq, nks, nks_p, nvq, nvs, nvs_p = _new_rows(B, kw, KH, 5)

    tb = np.asarray(tables)
    held = np.concatenate(
        [tb[i, : -(-int(n) // page)] for i, n in enumerate(lengths)]
    )
    unheld = np.setdiff1d(np.arange(ks.shape[0]), held)
    assert 0 in unheld
    ks_nan = ks.at[unheld].set(jnp.nan)
    vs_nan = vs.at[unheld].set(jnp.nan)

    pools = (pack_kv_slots(kq), pack_kv_slots(vq)) if packed else (kq, vq)
    out, k2, v2, ks2, vs2 = fused_paged_decode_attention(
        q, nkq, nvq, *pools, tables, jnp.asarray(lengths), jnp.asarray(wpos),
        ks_nan, vs_nan, nks_p, nvs_p,
        page_size=page, pages_per_block=ppb, nbuf=2, interpret=True,
    )
    if packed:
        k2, v2 = unpack_kv_slots(k2), unpack_kv_slots(v2)

    all_slots = jnp.arange(kq.shape[0], dtype=jnp.int32)
    slots = jnp.asarray([
        int(tb[b, wpos[b] // page]) * page + int(wpos[b]) % page
        for b in range(B)
    ])
    kd = dequantize_kv_rows(kq, gather_kv_scales(ks, all_slots, KH))
    vd = dequantize_kv_rows(vq, gather_kv_scales(vs, all_slots, KH))
    kd = kd.at[slots].set(dequantize_kv_rows(nkq, nks))
    vd = vd.at[slots].set(dequantize_kv_rows(nvq, nvs))
    ref = paged_attention(
        q[:, None], kd, vd, slots_from_pages(tables, page),
        jnp.asarray(wpos)[:, None],
    )[:, 0]
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=5e-3)
    # data pages: only the written rows changed; scale pages: the written
    # columns landed, held pages are otherwise as they were, the rest
    # still NaN (never written back)
    np.testing.assert_array_equal(
        np.asarray(k2), np.asarray(kq.at[slots].set(nkq))
    )
    np.testing.assert_array_equal(
        np.asarray(v2), np.asarray(vq.at[slots].set(nvq))
    )
    np.testing.assert_allclose(
        np.asarray(gather_kv_scales(ks2, slots, KH)), np.asarray(nks)
    )
    np.testing.assert_allclose(
        np.asarray(gather_kv_scales(vs2, slots, KH)), np.asarray(nvs)
    )
    assert not np.isnan(np.asarray(ks2)[held]).any()
    assert np.isnan(np.asarray(ks2)[unheld]).all()
    assert np.isnan(np.asarray(vs2)[unheld]).all()


@pytest.mark.parametrize("packed", [False, True], ids=["dense", "packed"])
@pytest.mark.parametrize("ppb,tail", LIVE_CASES)
def test_decode_kernel_int8_write_that_opens_a_page_lands(ppb, tail, packed):
    """`length - 1` a multiple of the page size: the new token is the
    first of the item's LAST live page, which held nothing before. Rows
    and scale columns are bit-equal to the scatter oracle's."""
    from dynamo_tpu.ops.pallas_attention import fused_paged_decode_attention
    from dynamo_tpu.ops.quant import (
        pack_kv_slots,
        scatter_kv_scales,
        unpack_kv_slots,
    )

    B, H, KH, Hd, page, kw, q, kq, ks, vq, vs, tables = _quant_setup(
        13, W=3 * ppb
    )
    t_blk = ppb * page
    wpos = np.asarray([
        (tail - 1) * page,
        t_blk + (tail - 1) * page,
        2 * t_blk + (tail - 1) * page,
    ], np.int32)
    nkq, nks, nks_p, nvq, nvs, nvs_p = _new_rows(B, kw, KH, 6)
    pools = (pack_kv_slots(kq), pack_kv_slots(vq)) if packed else (kq, vq)
    _, k2, v2, ks2, vs2 = fused_paged_decode_attention(
        q, nkq, nvq, *pools, tables, jnp.asarray(wpos + 1),
        jnp.asarray(wpos), ks, vs, nks_p, nvs_p,
        page_size=page, pages_per_block=ppb, nbuf=2, interpret=True,
    )
    if packed:
        k2, v2 = unpack_kv_slots(k2), unpack_kv_slots(v2)
    tb = np.asarray(tables)
    slots = jnp.asarray(
        [int(tb[b, wpos[b] // page]) * page for b in range(B)]
    )
    np.testing.assert_array_equal(
        np.asarray(k2), np.asarray(kq.at[slots].set(nkq))
    )
    np.testing.assert_array_equal(
        np.asarray(v2), np.asarray(vq.at[slots].set(nvq))
    )
    np.testing.assert_array_equal(
        np.asarray(ks2), np.asarray(scatter_kv_scales(ks, slots, nks, KH))
    )
    np.testing.assert_array_equal(
        np.asarray(vs2), np.asarray(scatter_kv_scales(vs, slots, nvs, KH))
    )


def test_flash_prefill_kernel_packed_matches_unpacked():
    from dynamo_tpu.ops.pallas_prefill import flash_prefill_attention
    from dynamo_tpu.ops.quant import pack_kv_slots

    B, H, KH, Hd, page, kw, _, kq, ks, vq, vs, tables = _quant_setup(5)
    key = jax.random.PRNGKey(11)
    T = 16
    qp = jax.random.normal(key, (B, T, H, Hd))
    pos0 = jnp.asarray([0, 8, 16], jnp.int32)
    tval = jnp.asarray([16, 8, 16], jnp.int32)
    kwargs = dict(page_size=page, t_tile=8, pages_per_block=2, interpret=True)
    out_u = flash_prefill_attention(
        qp, kq, vq, tables, pos0, tval, ks, vs, **kwargs
    )
    out_p = flash_prefill_attention(
        qp, pack_kv_slots(kq), pack_kv_slots(vq), tables, pos0, tval, ks, vs,
        **kwargs,
    )
    np.testing.assert_array_equal(np.asarray(out_p), np.asarray(out_u))


def test_paged_kv_write_kernel_packed():
    from dynamo_tpu.ops.pallas_kv_write import paged_kv_write
    from dynamo_tpu.ops.quant import pack_kv_slots, unpack_kv_slots

    KH, Hd, page = 4, 32, 8
    kw = KH * Hd
    num_pages = 6
    num_slots = num_pages * page
    key = jax.random.PRNGKey(2)
    kq, ks = quantize_kv_rows(jax.random.normal(key, (num_slots, kw)), KH)
    vq, vs = quantize_kv_rows(
        jax.random.normal(jax.random.fold_in(key, 1), (num_slots, kw)), KH
    )
    ks_pool = _to_pool(ks, num_pages, page, KH)
    vs_pool = _to_pool(vs, num_pages, page, KH)
    nk, nks = quantize_kv_rows(
        jax.random.normal(jax.random.fold_in(key, 2), (2, page, kw)), KH
    )
    nv, nvs = quantize_kv_rows(
        jax.random.normal(jax.random.fold_in(key, 3), (2, page, kw)), KH
    )
    nks_t = _to_pool(nks.reshape(2 * page, KH), 2, page, KH)
    nvs_t = _to_pool(nvs.reshape(2 * page, KH), 2, page, KH)
    table = jnp.asarray([3, 5], jnp.int32)
    kq_host = np.asarray(kq)
    k2, v2, ks2, vs2 = paged_kv_write(
        pack_kv_slots(kq), pack_kv_slots(vq), table,
        pack_kv_slots(nk), pack_kv_slots(nv),
        ks_pool, vs_pool, nks_t, nvs_t, page_size=page, interpret=True,
    )
    assert k2.dtype == jnp.int32
    k2u, v2u = np.asarray(unpack_kv_slots(k2)), np.asarray(unpack_kv_slots(v2))
    for i, pid in enumerate([3, 5]):
        sl = slice(pid * page, (pid + 1) * page)
        np.testing.assert_array_equal(k2u[sl], np.asarray(nk[i]))
        np.testing.assert_array_equal(v2u[sl], np.asarray(nv[i]))
    np.testing.assert_array_equal(k2u[: 3 * page], kq_host[: 3 * page])


async def test_engine_packed_int8_kv_serving():
    """An attn_backend='pallas' int8-KV engine on page_size=128 runs the
    PACKED pool format end to end (pools int32, greedy matches the
    dense-int8 gather engine, prefix cache + extract/inject work)."""
    e_p = make_engine(
        attn_backend="pallas", page_size=128, num_pages=12,
        max_model_len=512, prefill_chunk=128, max_batch_size=2,
    )
    assert e_p._kv_packed and e_p.kv.k[0].dtype == jnp.int32
    e_g = make_engine(num_pages=64, max_model_len=512, prefill_chunk=128)
    assert not e_g._kv_packed
    prompt = list(range(7, 150))
    a, _ = await collect(e_p, req(prompt))
    b, _ = await collect(e_g, req(prompt))
    match = sum(x == y for x, y in zip(a, b))
    assert match >= len(a) - 1, f"packed diverged: {a} vs {b}"
    # prefix-cache continuation on packed pages
    c, frames = await collect(e_p, req(prompt, 4))
    assert len(c) == 4
    assert frames[0]["meta"]["prefix_cached_tokens"] > 0
    await e_p.close()
    await e_g.close()


def make_packed_engine(**kw):
    defaults = dict(
        attn_backend="pallas", page_size=128, num_pages=12,
        max_model_len=512, prefill_chunk=128, max_batch_size=2,
    )
    defaults.update(kw)
    return make_engine(**defaults)


async def test_disagg_packed_wire_roundtrip():
    """Packed-pool prefiller -> packed-pool decoder: extract unpacks to
    the dense int8 wire, inject re-packs page-granular; greedy matches a
    local packed serve bit-identically."""
    pe, de, le = make_packed_engine(), make_packed_engine(), make_packed_engine()
    prompt = list(range(30, 30 + 140))
    ref, _ = await collect(le, req(prompt, 6))
    first, k, v, ks, vs = await pe.prefill_only(req(prompt, 6))
    assert k.dtype == np.int8 and ks is not None  # wire stays dense int8
    out = [
        f async for f in await de.generate_remote(
            Context(req(prompt, 6).to_dict()), first, k, v, ks, vs
        )
    ]
    got = [t for f in out for t in f.get("token_ids") or []]
    assert got == ref
    for e in (pe, de, le):
        await e.close()


async def test_device_transfer_packed_pair():
    """Device-path transfer between two PACKED engines: dense rows over
    the wire, page-granular pack on injection."""
    from dynamo_tpu.engine.kv_transfer import device_transfer_kv
    from dynamo_tpu.llm.tokens import TokenBlockSequence
    from dynamo_tpu.ops.quant import gather_packed_kv

    src, dst = make_packed_engine(), make_packed_engine()
    ps = src.page_size
    prompt = list(range(20, 20 + 3 * ps))
    await collect(src, req(prompt, 1))
    blocks = TokenBlockSequence(prompt, ps)
    src_pages = src.allocator.match_prefix(blocks.sequence_hashes())
    assert len(src_pages) == 3
    dst_pages = dst.allocator.allocate(3)
    device_transfer_kv(src, dst, src_pages, dst_pages, 3 * ps)
    s = jnp.asarray([src_pages[0] * ps + 5], jnp.int32)
    d = jnp.asarray([dst_pages[0] * ps + 5], jnp.int32)
    np.testing.assert_array_equal(
        np.asarray(gather_packed_kv(src.kv.k[0], s)),
        np.asarray(gather_packed_kv(dst.kv.k[0], d)),
    )
    src.allocator.release(src_pages)
    for e in (src, dst):
        await e.close()


async def test_engine_packed_tp2_serving_and_inject():
    """Packed pools under a tp=2 mesh: the serving kernels AND the
    page-granular inject path run per-shard inside shard_map (a pallas
    call has no GSPMD partitioning rule — bare jit would not partition).
    Greedy must match the single-device packed engine; the disagg inject
    lands remotely-prefilled KV into the tp-sharded packed pools."""
    from dynamo_tpu.parallel.mesh import MeshConfig

    e1 = make_packed_engine()
    e2 = make_packed_engine(mesh=MeshConfig(tp=2))
    assert e2._kv_packed
    prompt = list(range(60, 60 + 140))
    a, _ = await collect(e1, req(prompt, 6))
    b, _ = await collect(e2, req(prompt, 6))
    assert a == b, f"tp=2 packed diverged: {a} vs {b}"
    # disagg: prefill on the tp=2 engine, decode on the tp=2 engine
    # (extract gathers packed pools per shard; inject scatters them)
    first, k, v, ks, vs = await e2.prefill_only(req(prompt, 6))
    de = make_packed_engine(mesh=MeshConfig(tp=2))
    out = [
        f async for f in await de.generate_remote(
            Context(req(prompt, 6).to_dict()), first, k, v, ks, vs
        )
    ]
    got = [t for f in out for t in f.get("token_ids") or []]
    assert got == a
    for e in (e1, e2, de):
        await e.close()


# ------------------------------------- the pools' pass-through the scan
#
# Every quantized pool (k, v AND the f32 scale pools ks, vs) goes through
# the decode scan in place: donated to the step program, aliased onto its
# outputs, written by the kernel (or the row scatter) where it lies. On
# the v5e a scale pool that XLA may place freely is moved to VMEM and
# back around its kernel on every step (KVCache docstring); what the CPU
# can hold is the contract around it: donation and aliasing of all
# 4 x num_layers leaves, and the bytes the scan leaves in them.

POOL_FORMATS = [
    pytest.param(dict(kv_quantization="int8", attn_backend="pallas"),
                 id="int8-packed"),
    pytest.param(dict(kv_quantization="int8", attn_backend="gather"),
                 id="int8-dense"),
    pytest.param(dict(kv_quantization="int4", attn_backend="pallas"),
                 id="int4"),
]
POOL_ENGINE = dict(
    page_size=128, num_pages=8, max_model_len=512, prefill_chunk=128,
    max_batch_size=2,
)
POOL_PROMPT = list(range(7, 47))


async def _decode_3_dispatches(engine):
    """Greedy tokens of one request that ends with the third decode
    dispatch (the first token is the prefill's), and how many positions
    then hold a written KV row (every token but the last was fed back)."""
    n_new = 1 + 3 * engine.config.decode_steps
    toks, _ = await collect(engine, req(POOL_PROMPT, n_new))
    return toks, len(POOL_PROMPT) + n_new - 1


@pytest.mark.parametrize("fmt", POOL_FORMATS)
async def test_decode_scan_donates_and_aliases_every_pool(fmt):
    """The compiled `_decode_multi` takes every pool leaf as a donated
    parameter and its input/output aliasing maps each onto an output:
    4 x num_layers leaves (k, v, ks, vs), none left out."""
    e = make_engine(**POOL_ENGINE, **fmt)
    calls = []
    jitted = e._decode_fn

    def recording(*args, **kw):
        calls.append(jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)
            if isinstance(a, jax.Array) else a, args))
        return jitted(*args, **kw)

    e._decode_fn = recording
    await _decode_3_dispatches(e)
    assert len(calls) >= 3
    lowered = jitted.lower(*calls[0])
    n_pools = 4 * CFG.num_layers
    assert len(jax.tree.leaves(calls[0][1])) == n_pools

    # donated: jit marks every kv leaf (argument 1) as donated
    donated = jax.tree.leaves(lowered.args_info[0][1])
    assert len(donated) == n_pools and all(a.donated for a in donated)

    # aliased: each `kv.*` parameter of the optimized module appears in
    # its input_output_alias map
    text = lowered.compile().as_text()
    header = text.split("\n", 1)[0]
    aliased = {
        int(p) for p in re.findall(r"\}: \((\d+), \{\}", header)
    }
    pool_params = {
        name: int(num) for num, name in re.findall(
            r"parameter\((\d+)\)[^\n]*op_name=\"(kv\.[a-z]+\[\d+\])\"", text)
    }
    assert sorted(pool_params) == sorted(
        f"kv.{leaf}[{l}]" for leaf in ("k", "v", "ks", "vs")
        for l in range(CFG.num_layers)
    )
    missing = {n for n, p in pool_params.items() if p not in aliased}
    assert not missing, f"pool leaves without an aliased output: {missing}"
    await e.close()


@pytest.mark.parametrize("fmt", POOL_FORMATS)
async def test_decode_scan_pool_bytes_match_gather_oracle(fmt):
    """Three dispatches x `decode_steps` through the scan leave the
    tokens and the pools of the gather oracle stepping ONE token a
    dispatch: every written row's K / V bytes and scale columns, leaf by
    leaf. Rows past the sequence are not compared (a page-granular
    prefill write pads its page; a pipelined dispatch overshoots)."""
    e = make_engine(**POOL_ENGINE, **fmt)
    oracle = make_engine(**POOL_ENGINE, **{
        **fmt, "attn_backend": "gather", "decode_steps": 1,
    })
    toks, n = await _decode_3_dispatches(e)
    want = (await collect(oracle, req(POOL_PROMPT, len(toks))))[0]
    assert toks == want
    assert n <= e.page_size  # one page: the first the allocator hands out
    same_arithmetic = not e._attn_pallas

    def pools(eng):
        # under the lock: a dispatch in flight has donated `eng.kv`
        with eng._kv_lock:
            kv = eng.kv
            if eng._kv_packed:
                kv = kv._replace(
                    k=tuple(map(unpack_kv_slots, kv.k)),
                    v=tuple(map(unpack_kv_slots, kv.v)),
                )
            return jax.tree.map(np.asarray, kv)

    got_kv, ref_kv = pools(e), pools(oracle)
    page = slice(e.page_size, e.page_size + n)
    for l in range(CFG.num_layers):
        for leaf in ("k", "v"):
            np.testing.assert_array_equal(
                getattr(got_kv, leaf)[l][page],
                getattr(ref_kv, leaf)[l][page], err_msg=f"{leaf}[{l}]",
            )
        for leaf in ("ks", "vs"):
            got = getattr(got_kv, leaf)[l][1, :, :n]
            ref = getattr(ref_kv, leaf)[l][1, :, :n]
            assert (ref != 1.0).any(), f"{leaf}[{l}]: oracle wrote no scale"
            if l == 0 or same_arithmetic:
                np.testing.assert_array_equal(got, ref, err_msg=f"{leaf}[{l}]")
            else:
                # a deeper layer's rows come through the layers below,
                # whose attention the kernels sum in another order than
                # the gather path: the last bit of a scale may differ
                # (measured 1.5e-8 absolute), a lost write-back reads 1.0
                np.testing.assert_allclose(
                    got, ref, rtol=1e-5, atol=0, err_msg=f"{leaf}[{l}]")
    await e.close()
    await oracle.close()
