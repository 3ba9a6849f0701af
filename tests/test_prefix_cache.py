"""Prefix-cache economics: the edge cases behind the warm-TTFT fix.

Covers the ISSUE-12 satellite matrix — partial trailing pages never
match, eviction pressure against pinned matches keeps refcounts sound,
the int8-KV host pool round-trips byte-identically, a prefix-hit greedy
stream is byte-identical to its cold serve — plus the new prefix
attribution plane (phase counters, engine.prefix trace track, metric
rename) and the restore-gate EMA reset on degrade trips.
"""

import asyncio

import numpy as np

from dynamo_tpu.engine import EngineConfig, JaxEngine
from dynamo_tpu.engine.allocator import PageAllocator
from dynamo_tpu.llm.protocols.common import (
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)
from dynamo_tpu.llm.tokens import TokenBlockSequence, compute_block_hashes
from dynamo_tpu.models import config as cfgmod
from dynamo_tpu.runtime.pipeline.context import Context
from dynamo_tpu.utils import tracing

PAGE = 8
TINY = cfgmod.get_config("tiny")


def engine_config(**kw):
    base = dict(
        model=TINY, dtype="float32", page_size=PAGE, num_pages=64,
        max_batch_size=2, max_model_len=256, prefill_chunk=32,
    )
    base.update(kw)
    return EngineConfig(**base)


def pre_request(tokens, max_tokens=6):
    return PreprocessedRequest(
        token_ids=tokens,
        stop_conditions=StopConditions(max_tokens=max_tokens, ignore_eos=True),
        sampling_options=SamplingOptions(greedy=True),
    )


async def collect(engine, tokens, max_tokens=6, metadata=None):
    ctx = Context(pre_request(tokens, max_tokens).to_dict(), metadata=metadata)
    out, meta0 = [], None
    async for frame in await engine.generate(ctx):
        out.extend(frame.get("token_ids") or [])
        if meta0 is None and frame.get("meta"):
            meta0 = frame["meta"]
    return out, meta0


# --------------------------------------------------------------- allocator


def test_partial_trailing_page_never_matches():
    """A trailing partial page has no hash identity: 2.5 pages of prompt
    cache exactly 2 blocks, and the peek agrees with reservation."""
    rng = np.random.RandomState(0)
    tokens = rng.randint(1, TINY.vocab_size, size=2 * PAGE + PAGE // 2).tolist()
    seq = TokenBlockSequence(tokens, PAGE)
    assert len(seq.blocks) == 2 and len(seq.partial) == PAGE // 2
    assert len(compute_block_hashes(tokens, PAGE)) == 2

    async def run():
        engine = JaxEngine(engine_config())
        try:
            await collect(engine, tokens)
            # full pages cached; the partial tail must NOT appear cached
            assert engine.peek_prefix_tokens(tokens) == 2 * PAGE
            _, meta = await collect(engine, tokens)
            assert meta["prefix_cached_tokens"] == 2 * PAGE
        finally:
            await engine.close()

    asyncio.run(run())


def test_eviction_pressure_against_pinned_match_keeps_refcounts_sound():
    """match_prefix pins its run; allocation pressure that evicts the
    REST of the cache must never steal a pinned page, and releasing the
    pins returns the pool to a consistent census."""
    alloc = PageAllocator(num_pages=8, page_size=PAGE)
    # two chained cached runs: [h1, h2] and [h3, h4]
    a = alloc.allocate(2)
    alloc.register(a, [(1, 11), (2, 12)], parent_hash=None)
    b = alloc.allocate(2)
    alloc.register(b, [(3, 13), (4, 14)], parent_hash=None)
    alloc.release(a)
    alloc.release(b)
    assert alloc.pages_cached == 4 and alloc.pages_used == 0

    pinned = alloc.match_prefix([1, 2])
    assert pinned == a and alloc.pages_used == 2
    # demand every remaining page: free list (3) + evictable cached (2)
    got = alloc.allocate(5)
    assert got is not None and set(got).isdisjoint(pinned)
    # the pinned run survived; the other cached run was evicted
    assert alloc.pin(1) is not None and alloc.pin(3) is None
    alloc.release(pinned)  # the extra pin() above
    alloc.release(pinned)
    alloc.release(got)
    # census identity: every page is free, cached, or used
    assert alloc.pages_used == 0
    assert alloc.pages_free + alloc.pages_cached == alloc.num_pages - 1
    # a fresh match still returns the surviving run soundly
    again = alloc.match_prefix([1, 2])
    assert len(again) == 2
    alloc.release(again)


def test_full_demand_eviction_mid_match_no_double_free():
    """Evicting ALL cached pages while a match holds refs, then
    releasing, must not corrupt the free list (no double-add)."""
    alloc = PageAllocator(num_pages=6, page_size=PAGE)
    a = alloc.allocate(2)
    alloc.register(a, [(1, 11), (2, 12)], parent_hash=None)
    alloc.release(a)
    pinned = alloc.match_prefix([1, 2])
    got = alloc.allocate(3)  # everything else
    assert got is not None
    alloc.release(got)
    alloc.release(pinned)
    free_list = list(alloc._free) + list(alloc._lru.values())
    assert len(free_list) == len(set(free_list))
    assert alloc.num_free == alloc.num_pages - 1


# ----------------------------------------------------------- byte identity


async def test_prefix_hit_greedy_stream_byte_identical():
    """The warm serve must emit the exact cold stream — reuse is an
    optimization, never a sampler input."""
    engine = JaxEngine(engine_config())
    rng = np.random.RandomState(1)
    tokens = rng.randint(1, TINY.vocab_size, size=3 * PAGE + 3).tolist()
    try:
        cold, meta_c = await collect(engine, tokens, max_tokens=8)
        cold_prefilled = engine.phase_stats["prefill_tokens"]
        warm, meta_w = await collect(engine, tokens, max_tokens=8)
        assert meta_c["prefix_cached_tokens"] == 0
        assert meta_w["prefix_cached_tokens"] == 3 * PAGE
        assert warm == cold
        st = engine.phase_stats
        # the warm serve dispatched fewer prefill tokens than the cold one
        assert 0 < st["prefill_tokens"] - cold_prefilled < cold_prefilled
        assert st["prefix_hits"] == 1
        assert st["prefix_reused_tokens"] == 3 * PAGE
        assert st["prefix_tail_tokens"] == 3
    finally:
        await engine.close()


async def test_int8_host_pool_roundtrip_byte_identical():
    """int8-KV pages written through to the host pool, evicted from HBM
    and restored must reproduce the cold greedy stream exactly (the
    quantized buffers round-trip bit-exact — no requantize on restore)."""
    engine = JaxEngine(
        engine_config(kv_quantization="int8", host_kv_pages=16)
    )
    rng = np.random.RandomState(2)
    tokens = rng.randint(1, TINY.vocab_size, size=3 * PAGE + 2).tolist()
    try:
        cold, _ = await collect(engine, tokens, max_tokens=8)
        hs = compute_block_hashes(tokens, PAGE)
        for _ in range(100):
            if all(h in engine.host_pool for h in hs):
                break
            engine._wake.set()
            await asyncio.sleep(0.05)
        assert all(h in engine.host_pool for h in hs)
        # evict every evictable HBM page; the host tier must carry it
        grabbed = []
        while True:
            got = engine.allocator.allocate(1)
            if not got:
                break
            grabbed.extend(got)
        engine.allocator.release(grabbed)
        assert engine.peek_prefix_tokens(tokens) == 3 * PAGE  # host tier
        warm, meta = await collect(engine, tokens, max_tokens=8)
        assert warm == cold
        assert engine.offload_gate_stats["restored"] >= 1
        assert engine.phase_stats["prefix_restored_tokens"] >= 3 * PAGE
    finally:
        await engine.close()


# ------------------------------------------------- attribution + plumbing


async def test_prefix_trace_track_and_metric_rename():
    tracing.enable()
    tracing.clear()
    engine = JaxEngine(engine_config())
    rng = np.random.RandomState(3)
    tokens = rng.randint(1, TINY.vocab_size, size=2 * PAGE + 1).tolist()
    try:
        await collect(engine, tokens)
        await collect(engine, tokens)
        m = engine.metrics()
        assert m["prefix_cache_hit_rate"] > 0
        # the PR-9 one-release gpu_* alias is gone from metrics() and
        # the wire: from_dict tolerates (ignores) it from stale senders
        assert "gpu_prefix_cache_hit_rate" not in m
        from dynamo_tpu.llm.kv_router.protocols import ForwardPassMetrics

        fpm = ForwardPassMetrics.from_dict(
            {"gpu_prefix_cache_hit_rate": 0.5}
        )
        assert fpm.prefix_cache_hit_rate == 0.0
        assert not hasattr(fpm, "gpu_prefix_cache_hit_rate")
        assert "gpu_prefix_cache_hit_rate" not in ForwardPassMetrics(
            prefix_cache_hit_rate=0.7
        ).to_dict()
        assert m["prefix_hits"] == 1
        # every prefix gauge is an always-present zero-series key
        for key in ("prefix_full_hits", "prefix_reused_tokens",
                    "prefix_restored_tokens", "prefix_tail_tokens"):
            assert key in m
        evs = tracing.export()["traceEvents"]
        hits = [e for e in evs if e["name"] == "prefix.hit"]
        assert hits and hits[0]["args"]["reused_blocks"] == 2
        tids = {e["args"]["name"]: e["tid"] for e in evs if e["ph"] == "M"}
        assert "engine.prefix" in tids
        assert hits[0]["tid"] == tids["engine.prefix"]
    finally:
        tracing.disable()
        tracing.clear()
        await engine.close()


async def test_metadata_hash_chain_skips_rehash_and_reuses():
    """A request carrying the router's precomputed hash chain registers
    under exactly those hashes, and a later plain request (hashing
    locally) still hits the cache — the two paths agree."""
    engine = JaxEngine(engine_config())
    rng = np.random.RandomState(4)
    tokens = rng.randint(1, TINY.vocab_size, size=2 * PAGE + 2).tolist()
    tbs = TokenBlockSequence(tokens, PAGE)
    md = {
        "kv_block_size": PAGE,
        "kv_seq_hashes": tbs.sequence_hashes(),
        "kv_local_hashes": [b.local_hash for b in tbs.blocks],
    }
    try:
        cold, _ = await collect(engine, tokens, metadata=md)
        for h in tbs.sequence_hashes():
            assert h in engine.allocator._by_hash
        warm, meta = await collect(engine, tokens)  # no metadata: rehash
        assert meta["prefix_cached_tokens"] == 2 * PAGE
        assert warm == cold
        # mismatched chain (wrong block size) is ignored, not trusted
        bad = dict(md, kv_block_size=PAGE * 2)
        again, meta2 = await collect(engine, tokens, metadata=bad)
        assert again == cold and meta2["prefix_cached_tokens"] == 2 * PAGE
    finally:
        await engine.close()


def test_with_hashes_guards():
    tokens = list(range(1, 2 * PAGE + 3))
    real = TokenBlockSequence(tokens, PAGE)
    rebuilt = TokenBlockSequence.with_hashes(
        tokens, PAGE, real.sequence_hashes(),
        [b.local_hash for b in real.blocks],
    )
    assert rebuilt.sequence_hashes() == real.sequence_hashes()
    assert rebuilt.partial == real.partial
    # later extends chain from the provided hashes identically
    rebuilt.extend(list(range(100, 100 + PAGE)))
    real.extend(list(range(100, 100 + PAGE)))
    assert rebuilt.sequence_hashes() == real.sequence_hashes()
    # wrong chain length refuses
    try:
        TokenBlockSequence.with_hashes(tokens, PAGE, [1], [2])
    except ValueError:
        pass
    else:
        raise AssertionError("short hash chain must raise")


async def test_restore_gate_ema_resets_on_degrade_trip():
    engine = JaxEngine(engine_config(host_kv_pages=4))
    try:
        engine._ema_restore_bps = 1e9
        engine._ema_prefill_tps = 1e5
        engine._degrade.trip_next("test trip")
        assert engine._ema_restore_bps is None
        assert engine._ema_prefill_tps is None
        # a repeat trip of the SAME rung only extends the timer and must
        # not fire the hook again mid-recalibration
        engine._ema_restore_bps = 2e9
        engine._degrade.trip("step_pipeline", "again")
        assert engine._ema_restore_bps == 2e9
    finally:
        await engine.close()
