"""Spec x mixed composition: ragged multi-token verify rows riding the
stall-free mixed prefill+decode steps (engine `_mixed_tick`), plus the
pallas routing of the standalone verify step.

Contract under test (docs/architecture.md "Ragged verify rows"):

- greedy token streams are BYTE-IDENTICAL to the plain engine with
  `mixed_batching` AND `spec_decode` both on, across an admission wave
  arriving mid-decode, on the gather AND pallas (interpret) backends —
  a spec decode row inside a mixed step is the same verify math the
  standalone `_spec_verify_step` runs, and greedy acceptance is exact
  argmax match;
- the composition actually engages (mixed_spec_rows > 0) and the token
  budget counts 1 + k per spec row (mixed_step_tokens_max never exceeds
  the budget);
- standalone spec verify on a pallas engine routes through the ragged
  flash kernel and still reproduces the plain engine's greedy stream;
- rollback under composition: a re-serve rides the prefix cache without
  divergence (rejected-tail pages never hash-registered).
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

# 4-gram period: prompt-lookup drafts mostly verifiable, so the held
# stream genuinely exercises accept/reject paths inside mixed steps
REPETITIVE = [5, 17, 42, 9] * 6


def make_engine(**kw) -> JaxEngine:
    defaults = dict(
        model=CFG,
        dtype="float32",
        page_size=8,
        num_pages=64,
        max_batch_size=4,
        max_model_len=256,
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


async def _admission_wave(engine):
    """One REPETITIVE held stream (draftable) + a 3-prompt admission
    wave arriving after the stream is mid-decode — the wave prompts are
    mid-wave admissions by construction (they enter _prefilling while
    the held row decodes, so decode rows and prefill chunks coexist)."""
    rng = np.random.RandomState(0)
    out = {"held": []}
    decoding = asyncio.Event()  # the wave's cue: a point in the held
    # stream (its first decode dispatch landed), not a time on the clock

    async def held():
        pre = greedy_request(REPETITIVE, 48)
        async for f in await engine.generate(Context(pre.to_dict())):
            out["held"] += f.get("token_ids") or []
            if len(out["held"]) > 1:
                decoding.set()

    task = asyncio.create_task(held())
    await decoding.wait()
    wave = [rng.randint(1, 200, size=45).tolist() for _ in range(3)]
    streams = await asyncio.gather(
        *(collect(engine, greedy_request(p, 10)) for p in wave)
    )
    await task
    return out["held"], streams


async def _byte_identity(backend_kw):
    plain = make_engine(**backend_kw)
    held_a, wave_a = await _admission_wave(plain)
    await plain.close()

    both = make_engine(
        mixed_batching=True, mixed_step_tokens=64, spec_decode=True,
        **backend_kw,
    )
    held_b, wave_b = await _admission_wave(both)
    ps = both.phase_stats
    await both.close()
    return (held_a, wave_a), (held_b, wave_b), ps


async def test_greedy_byte_identical_both_features_gather():
    a, b, ps = await _byte_identity({})
    # the wave genuinely exercised mixed steps AND composed verify rows
    assert ps["mixed_steps"] > 0
    assert ps["mixed_spec_rows"] > 0
    assert ps["spec_drafted"] > 0
    assert a == b


async def test_greedy_byte_identical_both_features_pallas():
    """Interpret-mode pallas engine: the mixed step's row-scatter write +
    ragged flash read must reproduce the plain pallas engine's greedy
    streams with spec verify rows composed in."""
    a, b, ps = await _byte_identity({"attn_backend": "pallas"})
    assert ps["mixed_steps"] > 0
    assert a == b


async def test_budget_counts_spec_rows():
    """A spec decode row costs 1 + k budget tokens: the per-step budget
    cap must hold with verify windows riding along."""
    budget = 24
    engine = make_engine(
        mixed_batching=True, mixed_step_tokens=budget, spec_decode=True
    )
    held, streams = await _admission_wave(engine)
    ps = engine.phase_stats
    m = engine.metrics()
    await engine.close()
    assert ps["mixed_steps"] > 0
    assert 0 < ps["mixed_step_tokens_max"] <= budget
    assert m["mixed_spec_rows"] == ps["mixed_spec_rows"]
    assert len(held) == 48 and all(len(s) == 10 for s in streams)


async def test_standalone_spec_verify_pallas_routes_flash():
    """No mixed traffic: a spec engine on the pallas backend runs its
    standalone verify dispatches through the ragged flash kernel and
    matches the plain pallas engine's greedy stream byte-for-byte."""
    plain = make_engine(attn_backend="pallas")
    a = await collect(plain, greedy_request(REPETITIVE, 32))
    await plain.close()
    spec = make_engine(attn_backend="pallas", spec_decode=True)
    b = await collect(spec, greedy_request(REPETITIVE, 32))
    ps = spec.phase_stats
    await spec.close()
    assert ps["spec_dispatches"] > 0 and ps["spec_emitted"] > 0
    assert a == b


async def test_prefix_cache_sound_under_composition():
    """Re-serving the held prompt after a composed serve rides the
    prefix cache: a rejected verify tail's garbage page registered by
    mistake would diverge the cached continuation."""
    engine = make_engine(
        mixed_batching=True, mixed_step_tokens=64, spec_decode=True
    )
    held_1, _ = await _admission_wave(engine)
    t2 = await collect(engine, greedy_request(REPETITIVE, 48))
    ps = engine.phase_stats
    await engine.close()
    assert ps["spec_drafted"] >= ps["spec_accepted"]
    assert held_1 == t2
