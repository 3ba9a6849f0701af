"""int4 packed KV tier: nibble-packed page pools end to end.

The int8 tier halved decode's dominant page-streaming traffic; the int4
tier halves it AGAIN — two 4-bit values per pool byte (ops/quant.
quantize_kv_rows_int4: grouped symmetric absmax, clip to [-7, 7]), so KV
bytes are a QUARTER of bf16. These tests pin:

- the packing scheme against exact round-trips (nibble layout, grouped
  scales, zero-row sentinel);
- the int4 pallas kernels (interpret mode) against the gather oracle on
  DEQUANTIZED pools (exact agreement — quantization noise is bounded
  separately, against the f32 forward: test_forward_oracle_agreement_int4);
- every KV-moving plane at int4: serving engine, allocator byte
  accounting (exact 4x vs bf16), host-tier offload spill->evict->restore
  (packed bytes + scales byte-identical), export_prefix/ingest_prefix
  and the disagg wire (packed bytes ride the wire, greedy continuation
  bit-identical), the device-path transfer;
- the quant-mismatch ladder: int4<->int8<->bf16 cross-tier combinations
  raise typed KvQuantMismatchError instead of silently requantizing —
  packed pools quantize exactly once at KV-write time.

CPU caveat: the fused/read-only decode kernels fold per-kv-head scales
with pltpu.repeat, whose interpret-mode semantics differ from TPU for
grouped query attention (q_heads > kv_heads) — the pre-existing int8
decode-kernel tests document that. The int4 decode-kernel tests here use
H == KH so interpret mode is faithful; prefill (one-hot head matmul, no
repeat) covers GQA.
"""

import asyncio

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine import EngineConfig, JaxEngine
from dynamo_tpu.llm.protocols.common import (
    KvQuantMismatchError,
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)
from dynamo_tpu.models import config as cfgmod
from dynamo_tpu.models import llama
from dynamo_tpu.ops.quant import (
    dequantize_kv_rows_int4,
    int4_scale_channels,
    quantize_kv_rows_int4,
    unpack_int4_kv,
)
from dynamo_tpu.runtime.pipeline.context import Context

CFG = cfgmod.get_config("tiny")


def make_engine(**kw) -> JaxEngine:
    defaults = dict(
        model=CFG,
        dtype="float32",
        kv_quantization="int4",
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


def test_int4_rows_roundtrip():
    key = jax.random.PRNGKey(0)
    kh, hd = 4, 32
    rows = jax.random.normal(key, (7, kh * hd)) * 3.0
    q, s = quantize_kv_rows_int4(rows, kh)
    # packed rows: HALF the byte width; one scale per token per kv head
    assert q.dtype == jnp.int8 and q.shape == (7, kh * hd // 2)
    assert s.shape == (7, kh)
    back = dequantize_kv_rows_int4(q, s, kh)
    rel = float(jnp.max(jnp.abs(back - rows)) / jnp.max(jnp.abs(rows)))
    assert rel < 0.15  # 4-bit absmax: coarse, but bounded
    # re-quantizing the dequantized rows is a FIXED POINT: the packed
    # bytes and scales come back byte-identical (pool-to-pool moves
    # carry the packed representation, never a requantization hop)
    q2, s2 = quantize_kv_rows_int4(back, kh)
    np.testing.assert_array_equal(np.asarray(q2), np.asarray(q))
    np.testing.assert_allclose(np.asarray(s2), np.asarray(s), rtol=1e-6)
    # zero rows stay exactly zero (scale sentinel 1.0, no NaN)
    qz, sz = quantize_kv_rows_int4(jnp.zeros((2, kh * hd)), kh)
    assert np.all(np.asarray(sz) == 1.0)
    assert np.all(np.asarray(dequantize_kv_rows_int4(qz, sz, kh)) == 0.0)


def test_int4_nibble_layout():
    """PLANAR per-head packing: byte j of a head's packed half holds
    feature j (low nibble) and feature j + hd/2 (high nibble)."""
    kh, hd = 2, 8
    q = jnp.asarray(
        np.arange(-7, 9).reshape(1, kh * hd) % 8, jnp.float32
    )  # values 0..7 and -7..0: all nibble patterns both signs
    packed, s = quantize_kv_rows_int4(q * 1.0, kh)
    unpacked = np.asarray(unpack_int4_kv(packed, kh))
    b = np.asarray(packed).astype(np.int32)
    for k in range(kh):
        half = hd // 2
        head = b[0, k * half:(k + 1) * half]
        lo = ((head & 15) ^ 8) - 8
        hi = head >> 4
        np.testing.assert_array_equal(
            lo, unpacked[0, k * hd:k * hd + half]
        )
        np.testing.assert_array_equal(
            hi, unpacked[0, k * hd + half:(k + 1) * hd]
        )


def test_int4_grouped_scales():
    key = jax.random.PRNGKey(1)
    kh, hd, g = 2, 32, 8
    assert int4_scale_channels(kh, hd, g) == kh * hd // g
    rows = jax.random.normal(key, (5, kh * hd)) * 2.0
    qg, sg = quantize_kv_rows_int4(rows, kh, g)
    assert sg.shape == (5, kh * (hd // g))
    back_g = dequantize_kv_rows_int4(qg, sg, kh)
    q1, s1 = quantize_kv_rows_int4(rows, kh)
    back_1 = dequantize_kv_rows_int4(q1, s1, kh)
    err_g = float(jnp.mean(jnp.abs(back_g - rows)))
    err_1 = float(jnp.mean(jnp.abs(back_1 - rows)))
    assert err_g <= err_1 + 1e-6  # finer groups never hurt on average
    with pytest.raises(ValueError, match="must divide head_dim"):
        int4_scale_channels(kh, hd, 7)


def test_forward_oracle_agreement_int4():
    """Gather-path forward with an int4 KV cache tracks the f32-KV
    forward: logit cosine > 0.98, and the same argmax at every position
    whose f32 top-1 margin exceeds 4-bit noise (random-init weights are
    the worst case for 4-bit noise; trained nets sit much higher — no
    benchmark cell runs an int4-KV deployment yet: ROADMAP queue 2).

    Random weights put many positions at a near-tie (margin ~0.001
    against a max logit error of ~0.05-0.1 from 4-bit KV), where the
    argmax is decided by rounding and says nothing about the format —
    so agreement is asserted on the positions with margin > 0.05 (a
    third of the logit std, 0.16), over all B*T causal positions, and
    the test insists that enough of them qualify."""
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
    kv_q = llama.init_kv_cache(cfg, num_slots, kv_quant="int4")
    spec = llama.AttnSpec.gather(smat, int4_groups=1)
    h_f, _ = llama.forward(params, cfg, tokens, positions, kv_f, wslots, smat)
    h_q, kv_q2 = llama.forward(
        params, cfg, tokens, positions, kv_q, wslots, spec
    )
    # pools hold the packed half-width rows
    assert kv_q2.k[0].dtype == jnp.int8
    assert kv_q2.k[0].shape[1] == cfg.num_kv_heads * cfg.head_dim // 2
    lg_f = llama.logits(params, cfg, h_f[:, -1])
    lg_q = llama.logits(params, cfg, h_q[:, -1])
    cos = jnp.sum(lg_f * lg_q) / (
        jnp.linalg.norm(lg_f) * jnp.linalg.norm(lg_q)
    )
    assert float(cos) > 0.98
    all_f = llama.logits(params, cfg, h_f).reshape(B * T, -1)
    all_q = llama.logits(params, cfg, h_q).reshape(B * T, -1)
    top2 = jnp.sort(all_f, axis=-1)[:, -2:]
    decided = (top2[:, 1] - top2[:, 0]) > 0.05
    assert int(decided.sum()) >= 8, "too few positions with a real margin"
    same = jnp.argmax(all_f, -1) == jnp.argmax(all_q, -1)
    assert bool(same[decided].all())
    # the error that decides the near-ties stays bounded (~1 logit std)
    assert float(jnp.abs(all_f - all_q).max()) < 0.16


# --------------------------------------------------------- pallas kernels


def _to_pool(dense, num_pages, page, s_ch):
    """Dense per-slot scales [N, S] -> pool layout [P, SUBL, page]."""
    from dynamo_tpu.ops.quant import init_kv_scale_pool, scatter_kv_scales

    pool = init_kv_scale_pool(num_pages, page, s_ch)
    slots = jnp.arange(num_pages * page, dtype=jnp.int32)
    return scatter_kv_scales(pool, slots, dense, s_ch)


def _int4_setup(seed=0, h=4, kh=4):
    """Quantized pools + query for the decode kernels. Defaults to
    H == KH (MHA): interpret-mode pltpu.repeat diverges from TPU for
    G > 1 (see module docstring)."""
    key = jax.random.PRNGKey(seed)
    Hd, page, W = 32, 8, 4
    B = 3
    kw = kh * Hd  # full (unpacked) feature width
    num_pages = B * W + 1
    num_slots = num_pages * page
    kf = jax.random.normal(key, (num_slots, kw))
    vf = jax.random.normal(jax.random.fold_in(key, 1), (num_slots, kw))
    kq, ks = quantize_kv_rows_int4(kf, kh)
    vq, vs = quantize_kv_rows_int4(vf, kh)
    ks_pool = _to_pool(ks, num_pages, page, kh)
    vs_pool = _to_pool(vs, num_pages, page, kh)
    q = jax.random.normal(jax.random.fold_in(key, 2), (B, h, Hd))
    tables = jnp.asarray(
        [[1 + i * W + j for j in range(W)] for i in range(B)], jnp.int32
    )
    return B, h, kh, Hd, page, kw, q, kq, ks_pool, vq, vs_pool, tables


def _dequant_pools(kq, ks_pool, vq, vs_pool, kh):
    from dynamo_tpu.ops.quant import gather_kv_scales

    all_slots = jnp.arange(kq.shape[0], dtype=jnp.int32)
    kd = dequantize_kv_rows_int4(
        kq, gather_kv_scales(ks_pool, all_slots, kh), kh
    )
    vd = dequantize_kv_rows_int4(
        vq, gather_kv_scales(vs_pool, all_slots, kh), kh
    )
    return kd, vd


def test_gather_oracle_int4_matches_dequantized_pools():
    """paged_attention(int4_groups=...) == paged_attention on the
    explicitly dequantized pools — exact, both groupings."""
    from dynamo_tpu.ops.attention import paged_attention, slots_from_pages

    B, H, KH, Hd, page, kw, q, kq, ks, vq, vs, tables = _int4_setup(2, 8, 4)
    smat = slots_from_pages(tables, page)
    pos = jnp.asarray([[9], [17], [31]], jnp.int32)
    out = paged_attention(
        q[:, None], kq, vq, smat, pos,
        k_scales=ks, v_scales=vs, int4_groups=1,
    )
    kd, vd = _dequant_pools(kq, ks, vq, vs, KH)
    ref = paged_attention(q[:, None], kd, vd, smat, pos)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=1e-5
    )


def test_fused_decode_kernel_int4():
    from dynamo_tpu.ops.attention import paged_attention, slots_from_pages
    from dynamo_tpu.ops.pallas_attention import fused_paged_decode_attention
    from dynamo_tpu.ops.quant import _scale_rows, gather_kv_scales, kv_scale_subl

    B, H, KH, Hd, page, kw, q, kq, ks, vq, vs, tables = _int4_setup()
    key = jax.random.PRNGKey(9)
    newk = jax.random.normal(key, (B, kw))
    newv = jax.random.normal(jax.random.fold_in(key, 1), (B, kw))
    nkq, nks = quantize_kv_rows_int4(newk, KH)
    nvq, nvs = quantize_kv_rows_int4(newv, KH)
    subl = kv_scale_subl(KH)
    rows = _scale_rows(KH, 1)
    nks_p = jnp.ones((B, subl), jnp.float32).at[:, rows].set(nks)
    nvs_p = jnp.ones((B, subl), jnp.float32).at[:, rows].set(nvs)
    lengths = jnp.asarray([10, 17, 32], jnp.int32)
    wpos = lengths - 1
    out, k2, v2, ks2, vs2 = fused_paged_decode_attention(
        q, nkq, nvq, kq, vq, tables, lengths, wpos, ks, vs, nks_p, nvs_p,
        page_size=page, pages_per_block=2, nbuf=2, interpret=True, int4=True,
    )
    # oracle on dequantized pools with the new rows injected
    kd, vd = _dequant_pools(kq, ks, vq, vs, KH)
    slots = jnp.asarray([
        int(tables[b, int(wpos[b]) // page]) * page + int(wpos[b]) % page
        for b in range(B)
    ])
    kd = kd.at[slots].set(dequantize_kv_rows_int4(nkq, nks, KH))
    vd = vd.at[slots].set(dequantize_kv_rows_int4(nvq, nvs, KH))
    smat = slots_from_pages(tables, page)
    ref = paged_attention(q[:, None], kd, vd, smat, (lengths - 1)[:, None])[:, 0]
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=5e-3)
    # cache update: the PACKED rows + scale columns landed byte-identical
    sc2 = gather_kv_scales(ks2, slots, KH)
    sv2 = gather_kv_scales(vs2, slots, KH)
    for b in range(B):
        s = int(slots[b])
        np.testing.assert_array_equal(np.asarray(k2[s]), np.asarray(nkq[b]))
        np.testing.assert_allclose(np.asarray(sc2[b]), np.asarray(nks[b]))
        np.testing.assert_array_equal(np.asarray(v2[s]), np.asarray(nvq[b]))
        np.testing.assert_allclose(np.asarray(sv2[b]), np.asarray(nvs[b]))


def test_readonly_decode_kernel_int4():
    from dynamo_tpu.ops.attention import paged_attention, slots_from_pages
    from dynamo_tpu.ops.pallas_attention import paged_decode_attention

    B, H, KH, Hd, page, kw, q, kq, ks, vq, vs, tables = _int4_setup(3)
    lengths = jnp.asarray([9, 24, 32], jnp.int32)
    out = paged_decode_attention(
        q, kq, vq, tables, lengths, ks, vs,
        page_size=page, pages_per_block=2, interpret=True, int4=True,
    )
    kd, vd = _dequant_pools(kq, ks, vq, vs, KH)
    smat = slots_from_pages(tables, page)
    ref = paged_attention(q[:, None], kd, vd, smat, (lengths - 1)[:, None])[:, 0]
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=5e-3)


def test_flash_prefill_kernel_int4_gqa():
    """Prefill kernel at int4 with GQA (H=8 > KH=4): the one-hot head
    matmul has no repeat, so interpret mode is faithful here."""
    from dynamo_tpu.ops.attention import paged_attention, slots_from_pages
    from dynamo_tpu.ops.pallas_prefill import flash_prefill_attention

    B, H, KH, Hd, page, kw, _, kq, ks, vq, vs, tables = _int4_setup(5, 8, 4)
    key = jax.random.PRNGKey(11)
    T = 16
    qp = jax.random.normal(key, (B, T, H, Hd))
    pos0 = jnp.asarray([0, 8, 16], jnp.int32)
    tval = jnp.asarray([16, 8, 16], jnp.int32)
    out = flash_prefill_attention(
        qp, kq, vq, tables, pos0, tval, ks, vs,
        page_size=page, t_tile=8, pages_per_block=2, interpret=True,
        int4=True,
    )
    kd, vd = _dequant_pools(kq, ks, vq, vs, KH)
    smat = slots_from_pages(tables, page)
    posm = pos0[:, None] + jnp.arange(T)[None, :]
    ref = paged_attention(qp, kd, vd, smat, posm)
    mask = (jnp.arange(T)[None] < tval[:, None])[..., None, None]
    err = float(jnp.max(jnp.abs((out - ref) * mask)))
    assert err < 2e-2


def test_int4_int32_packed_compose():
    """int32-packing (4 bytes/element DMA tiling) composes with the
    nibble-packed rows: prefill output is bit-identical dense vs packed."""
    from dynamo_tpu.ops.pallas_prefill import flash_prefill_attention
    from dynamo_tpu.ops.quant import pack_kv_slots, unpack_kv_slots

    B, H, KH, Hd, page, kw, _, kq, ks, vq, vs, tables = _int4_setup(7, 8, 4)
    np.testing.assert_array_equal(
        np.asarray(unpack_kv_slots(pack_kv_slots(kq))), np.asarray(kq)
    )
    key = jax.random.PRNGKey(13)
    T = 16
    qp = jax.random.normal(key, (B, T, H, Hd))
    pos0 = jnp.asarray([0, 8, 16], jnp.int32)
    tval = jnp.asarray([16, 8, 16], jnp.int32)
    kwargs = dict(
        page_size=page, t_tile=8, pages_per_block=2, interpret=True,
        int4=True,
    )
    out_u = flash_prefill_attention(
        qp, kq, vq, tables, pos0, tval, ks, vs, **kwargs
    )
    out_p = flash_prefill_attention(
        qp, pack_kv_slots(kq), pack_kv_slots(vq), tables, pos0, tval,
        ks, vs, **kwargs,
    )
    np.testing.assert_array_equal(np.asarray(out_p), np.asarray(out_u))


# ------------------------------------------------------------ engine level


async def test_engine_int4_kv_serves_and_tracks_f32():
    """int4-KV engine serves greedy streams deterministically and its
    first decode token stays inside the f32-KV engine's top
    alternatives. Token-for-token equality with f32 is NOT asserted:
    random-init tiny weights produce near-tied logits (the f32 top-3
    sit within ~0.01 of each other), so 4-bit noise legitimately flips
    a near-tied argmax. The deployment's quality bound is a chip run's
    `correct` at an int4-KV configuration; no cell has one yet."""
    e_f = make_engine(kv_quantization=None)
    e_q = make_engine()
    assert e_q._kv_quant == "int4" and e_q._kv_int4_groups == 1
    # pools: packed half-width int8
    assert e_q.kv.k[0].dtype == jnp.int8
    assert e_q.kv.k[0].shape[1] == CFG.num_kv_heads * CFG.head_dim // 2
    prompt = list(range(30, 50))
    a, fr_f = await collect(
        e_f, req(prompt, logprobs=True, top_logprobs=8)
    )
    b, _ = await collect(e_q, req(prompt))
    assert len(b) == len(a) == 8
    top_first = {
        int(t) for t, _lp in (fr_f[0].get("top_log_probs") or [[]])[0]
    }
    assert b[0] in top_first, (
        f"int4-KV first token {b[0]} left the f32 top-8 {top_first}"
    )
    # deterministic serving on packed pages (fresh engine, same seed)
    e_q2 = make_engine()
    b2, _ = await collect(e_q2, req(prompt))
    assert b2 == b
    # prefix-cache continuation serves on packed pages
    c, frames = await collect(e_q, req(prompt, 4))
    assert len(c) == 4
    assert frames[0]["meta"]["prefix_cached_tokens"] > 0
    await e_f.close()
    await e_q.close()
    await e_q2.close()


def test_int4_allocator_accounting_quarter_bytes():
    """The auto-sizer's per-page data bytes at int4 are exactly 1/4 of
    bf16's and 1/2 of int8's (scale tiles accounted separately)."""
    m = CFG
    engines = {}
    for quant in (None, "int8", "int4"):
        e = make_engine(kv_quantization=quant, dtype="bfloat16")
        engines[quant] = e
    data_bf16 = (
        m.num_layers * engines[None].page_size
        * m.num_kv_heads * m.head_dim * 2 * 2
    )
    # replicate _auto_num_pages' data term per tier
    ps = engines[None].page_size
    data_int8 = m.num_layers * 2 * ps * m.num_kv_heads * m.head_dim
    data_int4 = m.num_layers * 2 * ps * m.num_kv_heads * m.head_dim // 2
    assert data_int4 * 4 == data_bf16
    assert data_int4 * 2 == data_int8
    # restore-gate byte accounting (H2D cost model) agrees with the tier
    r8 = engines["int8"]._restore_page_bytes()
    r4 = engines["int4"]._restore_page_bytes()
    expected_scales = m.num_layers * ps * m.num_kv_heads * 4 * 2
    assert r8 - expected_scales == data_int8
    assert r4 - expected_scales == data_int4
    # the live pools themselves: int4 data pool is half int8's byte size
    assert (
        engines["int4"].kv.k[0].size * 2 == engines["int8"].kv.k[0].size
    )
    # and a quarter of bf16's, int8 half of it: the exact 4.0 / 2.0 data
    # ratios between the tiers, read off the pools the engines allocated
    pool_bytes = {
        q: sum(a.nbytes for a in e.kv.k) + sum(a.nbytes for a in e.kv.v)
        for q, e in engines.items()
    }
    assert pool_bytes[None] == 4 * pool_bytes["int4"] == 2 * pool_bytes["int8"]
    for e in engines.values():
        asyncio.run(e.close())


async def test_engine_int4_offload_spill_evict_restore():
    """Host tier stores the PACKED int4 pages + grouped scales;
    spill -> evict -> restore preserves greedy outputs, the restored
    pages register as prefix hits, and the host copy is byte-identical
    to the device pool's packed rows."""
    engine = make_engine(
        num_pages=24, host_kv_pages=64, offload_batch_pages=4,
        max_model_len=96, prefill_chunk=16, page_size=8,
    )
    prompt = list(range(40, 72))  # 4 pages
    ref, _ = await collect(engine, req(prompt, 6))
    # wait for the write-through spill, then compare host vs device bytes
    for _ in range(100):
        await asyncio.sleep(0.05)
        if len(engine.host_pool) >= 4:
            break
    from dynamo_tpu.llm.tokens import TokenBlockSequence
    from dynamo_tpu.ops.quant import gather_kv_scales

    blocks = TokenBlockSequence(prompt, engine.page_size)
    pages = engine.allocator.match_prefix(blocks.sequence_hashes())
    assert pages, "prefix evicted before the spill check"
    hit = blocks.blocks[0].sequence_hash
    buf = engine.host_pool.get(hit)
    assert buf is not None, "first page never spilled to the host tier"
    ps = engine.page_size
    # host buffers carry the HALF-width packed bytes + grouped scales
    assert buf["kv"].shape == (
        2, CFG.num_layers, ps, CFG.num_kv_heads * CFG.head_dim // 2
    )
    assert buf["kv"].dtype == np.int8
    assert buf["scales"].shape == (
        2, CFG.num_layers, ps, CFG.num_kv_heads
    )
    slots = jnp.arange(pages[0] * ps, (pages[0] + 1) * ps, dtype=jnp.int32)
    np.testing.assert_array_equal(
        buf["kv"][0][:, :], np.asarray(
            jnp.stack([engine.kv.k[l][slots] for l in range(CFG.num_layers)])
        ),
    )
    np.testing.assert_allclose(
        buf["scales"][0][:, :], np.asarray(jnp.stack([
            gather_kv_scales(engine.kv.ks[l], slots, CFG.num_kv_heads)
            for l in range(CFG.num_layers)
        ])),
    )
    engine.allocator.release(pages)
    # churn through enough other prompts to evict the HBM prefix
    for k in range(6):
        await collect(engine, req([100 + 9 * k + j for j in range(24)], 4))
        await asyncio.sleep(0.05)
    got, frames = await collect(engine, req(prompt, 6))
    assert got == ref
    await engine.close()


async def test_int4_export_ingest_roundtrip():
    """export_prefix -> ingest_prefix between two int4 engines: the wire
    carries the packed bytes + grouped scales, the landed pool rows are
    byte-identical to the source pool, and the restored pages register
    as prefix hits (greedy continuation bit-identical)."""
    a, b = make_engine(), make_engine()
    prompt = list(range(30, 70))  # 5 pages
    ref, _ = await collect(a, req(prompt, 6))
    out = a.export_prefix(prompt)
    assert out is not None
    n, k, v, ks, vs = out
    assert n >= 40 - a.page_size
    assert k.dtype == np.int8
    assert k.shape[-1] == CFG.num_kv_heads * CFG.head_dim // 2  # packed
    assert ks.shape[-1] == CFG.num_kv_heads  # S = K at group=head_dim
    landed = b.ingest_prefix(prompt[:n], k, v, ks, vs)
    assert landed == n
    # pool-to-pool byte identity: the ingested packed rows match the
    # exporter's pool exactly (quantized once, moved as bytes)
    from dynamo_tpu.llm.tokens import TokenBlockSequence

    blocks = TokenBlockSequence(prompt, a.page_size)
    pa = a.allocator.match_prefix(blocks.sequence_hashes())
    pb = b.allocator.match_prefix(blocks.sequence_hashes())
    assert len(pb) == n // b.page_size
    ps = a.page_size
    sa = jnp.arange(pa[0] * ps, (pa[0] + 1) * ps, dtype=jnp.int32)
    sb = jnp.arange(pb[0] * ps, (pb[0] + 1) * ps, dtype=jnp.int32)
    np.testing.assert_array_equal(
        np.asarray(a.kv.k[0][sa]), np.asarray(b.kv.k[0][sb])
    )
    a.allocator.release(pa)
    b.allocator.release(pb)
    got, frames = await collect(b, req(prompt, 6))
    # a fully-cached prompt still prefills its last page for logits, so
    # the hit is capped one page below the ingested prefix
    assert frames[0]["meta"]["prefix_cached_tokens"] >= n - b.page_size
    assert got == ref, f"ingest continuation diverged: {got} vs {ref}"
    await a.close()
    await b.close()


async def test_disagg_int4_wire_roundtrip():
    """int4 prefiller -> int4 decoder over the host-staged disagg wire:
    packed bytes + scales ride the wire (a QUARTER of the bf16 payload)
    and greedy continuation is bit-identical to local."""
    pe, de, le = make_engine(), make_engine(), make_engine()
    prompt = list(range(30, 70))
    ref, _ = await collect(le, req(prompt, 6))
    first, k, v, ks, vs = await pe.prefill_only(req(prompt, 6))
    assert k.dtype == np.int8 and ks is not None
    assert k.shape == (
        CFG.num_layers, len(prompt), CFG.num_kv_heads * CFG.head_dim // 2
    )
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


async def test_disagg_bf16_prefiller_int4_decoder():
    """bf16 wire entering an int4 pool quantizes ON INJECTION (a fresh
    quantization of model-dtype rows, not a requantization hop) and
    still serves the full stream."""
    pe = make_engine(kv_quantization=None)
    de = make_engine()
    prompt = list(range(30, 60))
    first, k, v, ks, vs = await pe.prefill_only(req(prompt, 6))
    assert ks is None
    out = [
        f async for f in await de.generate_remote(
            Context(req(prompt, 6).to_dict()), first, k, v, ks, vs
        )
    ]
    got = [t for f in out for t in f.get("token_ids") or []]
    assert len(got) == 6
    await pe.close()
    await de.close()


async def test_quant_mismatch_typed_errors():
    """Cross-tier combos raise KvQuantMismatchError (a ValueError) on
    every plane — never a silent dequant/requantization."""
    from dynamo_tpu.engine.kv_transfer import device_transfer_kv

    e4 = make_engine()
    e8 = make_engine(kv_quantization="int8")
    ef = make_engine(kv_quantization=None)
    prompt = list(range(20, 44))  # 3 pages
    await collect(e4, req(prompt, 1))
    from dynamo_tpu.llm.tokens import TokenBlockSequence

    blocks = TokenBlockSequence(prompt, e4.page_size)
    src_pages = e4.allocator.match_prefix(blocks.sequence_hashes())
    assert len(src_pages) == 3
    # device path: int4 <-> int8 and int4 <-> bf16 both refuse
    for dst in (e8, ef):
        dst_pages = dst.allocator.allocate(3)
        with pytest.raises(ValueError, match="matching kv_quantization"):
            device_transfer_kv(e4, dst, src_pages, dst_pages, 24)
        dst.allocator.release(dst_pages)
    # host-staged wire: int4 payload entering int8 / bf16 pools refuses,
    # int8 payload entering an int4 pool refuses (typed, both ways)
    n, k4, v4, ks4, vs4 = e4.export_prefix(prompt)
    for dst in (e8, ef):
        with pytest.raises(KvQuantMismatchError):
            dst.ingest_prefix(prompt[:n], k4, v4, ks4, vs4)
    # reverse direction needs a prompt e4 has NOT cached: ingest_prefix
    # short-circuits on a full prefix hit before any payload conversion
    p2 = list(range(60, 84))
    n8, k8, v8, ks8, vs8 = await _export_via_prefill(e8, p2)
    with pytest.raises(KvQuantMismatchError):
        e4.ingest_prefix(p2[:n8], k8, v8, ks8, vs8)
    e4.allocator.release(src_pages)
    for e in (e4, e8, ef):
        await e.close()


async def _export_via_prefill(engine, prompt):
    first, k, v, ks, vs = await engine.prefill_only(req(prompt, 1))
    n = len(prompt) // engine.page_size * engine.page_size
    return n, k[:, :n], v[:, :n], (
        ks[:, :n] if ks is not None else None
    ), (vs[:, :n] if vs is not None else None)


async def test_device_transfer_int4_pair_byte_identical():
    """Device-path transfer between two int4 engines moves the PACKED
    pages + grouped scales byte-identically."""
    from dynamo_tpu.engine.kv_transfer import device_transfer_kv
    from dynamo_tpu.llm.tokens import TokenBlockSequence
    from dynamo_tpu.ops.quant import gather_kv_scales

    src, dst = make_engine(), make_engine()
    prompt = list(range(20, 44))
    await collect(src, req(prompt, 1))
    blocks = TokenBlockSequence(prompt, src.page_size)
    src_pages = src.allocator.match_prefix(blocks.sequence_hashes())
    assert len(src_pages) == 3
    dst_pages = dst.allocator.allocate(3)
    device_transfer_kv(src, dst, src_pages, dst_pages, 24)
    s_slot = src_pages[0] * src.page_size
    d_slot = dst_pages[0] * dst.page_size
    np.testing.assert_array_equal(
        np.asarray(src.kv.k[0][s_slot]), np.asarray(dst.kv.k[0][d_slot])
    )
    kh = CFG.num_kv_heads
    np.testing.assert_allclose(
        np.asarray(gather_kv_scales(
            src.kv.ks[0], jnp.asarray([s_slot]), kh)),
        np.asarray(gather_kv_scales(
            dst.kv.ks[0], jnp.asarray([d_slot]), kh)),
    )
    src.allocator.release(src_pages)
    for e in (src, dst):
        await e.close()


def test_int4_config_validation():
    with pytest.raises(ValueError, match="must divide"):
        make_engine(kv_quant_group=7)
    with pytest.raises(ValueError, match="one scale group per kv head"):
        make_engine(
            kv_quant_group=CFG.head_dim // 2, attn_backend="pallas",
            page_size=128, num_pages=12, max_model_len=256,
            prefill_chunk=128,
        )
    # finer groups on the gather backend are fine
    e = make_engine(kv_quant_group=CFG.head_dim // 2)
    assert e._kv_int4_groups == 2
    assert e._kv_scale_channels() == CFG.num_kv_heads * 2
    asyncio.run(e.close())
