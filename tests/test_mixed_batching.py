"""Stall-free mixed prefill+decode batching (engine `_mixed_tick`).

Contract under test (docs/architecture.md "Stall-free mixed batching"):

- greedy token streams are BYTE-IDENTICAL with mixed batching on vs. the
  plain engine, across an admission wave arriving mid-decode (a decode
  row is a q_len=1 row of the same unified step family — same math);
- one mixed step never exceeds the `mixed_step_tokens` budget (decode
  rows cost 1 each; non-final prefill chunks shrink to page multiples);
- the `mixed_*` metrics/phase counters reflect what actually ran;
- incompatible engines refuse at init (explicit misconfig) and the
  runtime toggle degrades to the normal paths instead of corrupting.

Also here: `_grow_and_collect` width-bucketing edges and growth
preemption (the decode-dispatch prep shared by normal/spec/mixed paths).
"""

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
        page_size=8,
        num_pages=64,
        max_batch_size=4,
        max_model_len=128,
        prefill_chunk=32,
        seed=0,
    )
    defaults.update(kw)
    return JaxEngine(EngineConfig(**defaults))


def greedy_request(prompt, max_tokens=8) -> PreprocessedRequest:
    return PreprocessedRequest(
        token_ids=list(prompt),
        stop_conditions=StopConditions(max_tokens=max_tokens, ignore_eos=True),
        sampling_options=SamplingOptions(greedy=True),
    )


async def collect(engine, pre):
    frames = [f async for f in await engine.generate(Context(pre.to_dict()))]
    return [t for f in frames for t in f.get("token_ids") or []]


async def _held_mid_decode(engine, prompt, max_tokens):
    """Start one held stream; return (its task, its token list) once its
    first decode dispatch has landed: a point in the stream, not a time
    on the clock (how long a cold engine takes to get there is not the
    tests' business)."""
    tokens = []
    decoding = asyncio.Event()

    async def held():
        pre = greedy_request(prompt, max_tokens)
        async for f in await engine.generate(Context(pre.to_dict())):
            tokens.extend(f.get("token_ids") or [])
            if len(tokens) > 1:
                decoding.set()

    task = asyncio.create_task(held())
    await decoding.wait()
    return task, tokens


async def _admission_wave(engine, wave_len=45):
    """One held decode stream + a 3-prompt admission wave arriving after
    the stream is mid-decode; returns (held tokens, wave streams)."""
    rng = np.random.RandomState(0)
    held_prompt = rng.randint(1, 200, size=20).tolist()
    task, held = await _held_mid_decode(engine, held_prompt, 40)
    out = {"held": held}
    wave = [rng.randint(1, 200, size=wave_len).tolist() for _ in range(3)]
    streams = await asyncio.gather(
        *(collect(engine, greedy_request(p, 10)) for p in wave)
    )
    await task
    return out["held"], streams


async def test_greedy_streams_byte_identical_across_admission_wave():
    plain = make_engine()
    held_a, wave_a = await _admission_wave(plain)
    await plain.close()

    mixed = make_engine(mixed_batching=True, mixed_step_tokens=64)
    held_b, wave_b = await _admission_wave(mixed)
    ps = mixed.phase_stats
    await mixed.close()

    # the wave genuinely exercised the mixed path...
    assert ps["mixed_steps"] > 0
    assert ps["mixed_decode_rows"] > 0
    assert ps["mixed_prefill_tokens"] > 0
    # ...and every stream is byte-identical to the plain engine
    assert held_a == held_b
    assert wave_a == wave_b


async def test_mixed_respects_token_budget_and_metrics():
    budget = 24  # 3 pages of prefill room next to <= 4 decode rows
    engine = make_engine(mixed_batching=True, mixed_step_tokens=budget)
    held, streams = await _admission_wave(engine)
    ps = engine.phase_stats
    m = engine.metrics()
    await engine.close()
    assert ps["mixed_steps"] > 0
    assert 0 < ps["mixed_step_tokens_max"] <= budget
    # metrics() exposes the counters (router wire drops unknown keys)
    assert m["mixed_steps"] == ps["mixed_steps"]
    assert m["mixed_decode_rows"] == ps["mixed_decode_rows"]
    assert m["mixed_prefill_tokens"] == ps["mixed_prefill_tokens"]
    assert all(len(s) == 10 for s in streams)
    assert len(held) == 40


def test_select_mixed_prefill_budget_policy():
    """Scheduler unit test: strict FIFO prefix, chunks shrink to the
    leftover budget, NON-final chunks round down to page multiples,
    zero-room front seq stops the scan (no queue jumping)."""
    engine = make_engine(mixed_batching=True)

    class _Ctx:
        def is_stopped(self):
            return False

    class _Seq:
        preloaded = None
        prompt_embeds = None
        num_computed = 0
        needs_ext_sampling = False
        ctx = _Ctx()

        def __init__(self, total):
            self.total_tokens = total

    try:
        a, b, c = _Seq(30), _Seq(45), _Seq(5)
        engine._prefilling.extend([a, b, c])
        # page_size=8, prefill_chunk=32:
        # a: need 30 <= leftover 40 -> final chunk 30 (no rounding)
        # b: need 45, chunk min(45, 32, 10) = 10 -> non-final, rounds to 8
        # c: leftover 2 < need 5 -> chunk 2 non-final rounds to 0 -> stop
        picks = engine._select_mixed_prefill(40)
        assert [(s is a or s is b, ch) for s, ch in picks] == [
            (True, 30), (True, 8)
        ]
        assert sum(ch for _, ch in picks) <= 40
        # a front seq that cannot take a page stops the scan entirely
        assert engine._select_mixed_prefill(7) == []
        # penalties/seeded/logprobs front seq: its final chunk would
        # sample on the plain path — must go through the normal ext
        # dispatch, so the scan stops (strict FIFO, no queue jumping)
        a.needs_ext_sampling = True
        assert engine._select_mixed_prefill(40) == []
        a.needs_ext_sampling = False
        # disagg-injected front seq: mixed stands down (normal path owns
        # KV injection)
        a.preloaded = (0, None, None, None, None)
        assert engine._select_mixed_prefill(40) == []
    finally:
        engine._prefilling.clear()


async def test_mixed_with_int8_kv_gather_matches_plain():
    """int8 KV pages compose with mixed steps on the gather path (the
    write quantizes rows + scatters scales exactly like chunked
    prefill)."""
    plain = make_engine(kv_quantization="int8")
    held_a, wave_a = await _admission_wave(plain)
    await plain.close()
    mixed = make_engine(
        kv_quantization="int8", mixed_batching=True, mixed_step_tokens=64
    )
    held_b, wave_b = await _admission_wave(mixed)
    ps = mixed.phase_stats
    await mixed.close()
    assert ps["mixed_steps"] > 0
    assert held_a == held_b
    assert wave_a == wave_b


def test_mixed_incompatible_configs_raise():
    import pytest

    with pytest.raises(ValueError, match="mixed_step_tokens"):
        make_engine(mixed_batching=True, mixed_step_tokens=0)
    # spec_decode is NOT an exclusion anymore: the two features compose
    # (ragged verify rows inside mixed steps, tests/test_spec_mixed.py)
    engine = make_engine(mixed_batching=True, spec_decode=True)
    assert engine._mixed_unsupported_reason() is None


async def test_mixed_runtime_toggle_on_unsupported_engine_degrades():
    """Toggling mixed_batching on at runtime (the bench A/B pattern) on
    an engine whose config cannot support it must keep serving through
    the normal paths, not corrupt or crash."""
    from dynamo_tpu.parallel.mesh import MeshConfig

    # sp>1: ring attention prefills whole prompts, no chunk to ride
    engine = make_engine(mesh=MeshConfig(sp=2), prefill_chunk=128)
    engine.config.mixed_batching = True
    held, streams = await _admission_wave(engine)
    ps = engine.phase_stats
    await engine.close()
    assert ps["mixed_steps"] == 0  # degraded, never built a mixed step
    assert len(held) == 40 and all(len(s) == 10 for s in streams)


# ---------------------------------------------------------------------------
# _grow_and_collect: the decode-prep shared by the normal/spec/mixed paths


def _fake_ready(engine, slots):
    """Park minimal live Sequences in the given slot indices."""
    from dynamo_tpu.engine.scheduler import Sequence

    ready = []
    for i in slots:
        pre = greedy_request([1, 2, 3], max_tokens=4)
        seq = Sequence.from_request(
            Context(pre.to_dict()), pre, engine.page_size,
            engine.config.max_model_len,
        )
        seq.slot = i
        seq.page_ids = engine.allocator.allocate(1)
        seq.num_computed = 2
        seq.device_pos = 2
        engine.slots[i] = seq
        ready.append((i, seq))
    return ready


def test_grow_and_collect_width_buckets():
    engine = make_engine(max_batch_size=32, num_pages=128)
    try:
        # b_needed = 1 (slot 0 only): width floors at 8
        ready = _fake_ready(engine, [0])
        active, b = engine._grow_and_collect(ready, lambda s: s.device_pos)
        assert [i for i, _ in active] == [0] and b == 8
        # exactly a power of two: highest slot 15 -> b_needed 16 -> b 16
        ready = _fake_ready(engine, [15])
        active, b = engine._grow_and_collect(ready, lambda s: s.device_pos)
        assert b == 16
        # one past a power of two buckets UP: slot 16 -> b 32
        ready = _fake_ready(engine, [16])
        active, b = engine._grow_and_collect(ready, lambda s: s.device_pos)
        assert b == 32
    finally:
        engine.slots = [None] * len(engine.slots)


def test_grow_and_collect_clamps_to_slot_count():
    # max_batch_size 4 < the 8 floor: width clamps to len(slots)
    engine = make_engine(max_batch_size=4)
    try:
        ready = _fake_ready(engine, [3])
        active, b = engine._grow_and_collect(ready, lambda s: s.device_pos)
        assert b == 4
    finally:
        engine.slots = [None] * len(engine.slots)


def test_grow_and_collect_growth_preemption_returns_none():
    """When growing pages preempts the growing sequence itself (pool
    exhausted, it is the newest), the prep returns None mid-pass and the
    caller retries next tick."""
    engine = make_engine(max_batch_size=4, num_pages=4)  # 3 usable pages
    try:
        ready = _fake_ready(engine, [0])
        # drain the pool so growth must preempt; the only candidate
        # victim is the growing sequence itself
        grabbed = []
        while True:
            got = engine.allocator.allocate(1)
            if not got:
                break
            grabbed.extend(got)
        (slot, seq), = ready
        # needs a page beyond its single one -> allocate fails ->
        # preempts itself -> None
        prep = engine._grow_and_collect(
            ready, lambda s: 3 * engine.page_size
        )
        assert prep is None
        assert seq.slot == -1 and engine.slots[slot] is None
        assert seq in engine.waiting
        engine.allocator.release(grabbed)
    finally:
        engine.slots = [None] * len(engine.slots)
        engine.waiting.clear()
