"""Zero-stall step pipeline (`EngineConfig.step_pipeline`): mixed and
spec steps dispatched BEHIND in-flight dispatches via the carry the step
programs keep on the device (`StepState`), with slow-changing batch
state (block tables, sampling params) riding from host mirrors in each
dispatch's fused upload.

Contract under test (docs/architecture.md "Step pipeline"):

- greedy token streams are BYTE-IDENTICAL to the plain engine with the
  pipeline on (the default) across an admission wave arriving
  mid-decode, gather AND pallas backends — and the pipeline genuinely
  engaged (carry rows + overlapped syncs);
- `step_pipeline=False` (the serialized A/B baseline) is also
  byte-identical — the flag changes scheduling, never math;
- carry staleness: preemption under page pressure between a dispatch
  and its sync must re-arm the slot's carry override from host truth
  (a reused slot reading a dead sequence's device carry would diverge);
- spec fallback: carry rows whose acceptance gate is closed SHED their
  drafts (host history is stale — the proposer would continue the
  wrong suffix) but still advance at q_len=1;
- a failed mixed dispatch degrades to the contained normal paths and
  SAYS so: `Engine.metrics()["mixed_disabled"]` == 1 (the satellite:
  one log line is easy to miss, the /metrics scrape is not);
- block tables follow page growth (decode crossing page boundaries
  reads/writes through the rows the build snapshot from the host mirror);
- a dispatch is host arrays plus ONE launch: between `_kv_lock` acquire
  and release exactly one compiled program runs and no eager primitive;
- rows that join beside an in-flight dispatch, are preempted and
  re-armed, or are injected by a disagg prefill keep the tokens they
  emit alone (greedy, and seeded sampling).
"""

import asyncio
import contextlib
import threading

import numpy as np
import pytest

from dynamo_tpu.engine import EngineConfig, JaxEngine
from dynamo_tpu.engine.spec import NgramProposer
from dynamo_tpu.llm.protocols.common import (
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)
from dynamo_tpu.models import config as cfgmod
from dynamo_tpu.runtime.pipeline.context import Context

CFG = cfgmod.get_config("tiny")

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


async def _admission_wave(engine, held_tokens=48):
    """One held stream decoding + a 3-prompt admission wave arriving
    mid-decode (once the held stream's first decode dispatch has landed:
    a point in its stream, not a time on the clock), so decode rows and
    prefill chunks coexist and the mixed tick finds an in-flight
    dispatch to pipeline behind."""
    rng = np.random.RandomState(0)
    out = {"held": []}
    decoding = asyncio.Event()

    async def held():
        pre = greedy_request(REPETITIVE, held_tokens)
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


async def _plain_reference(backend_kw=None, **wave_kw):
    plain = make_engine(**(backend_kw or {}))
    ref = await _admission_wave(plain, **wave_kw)
    await plain.close()
    return ref


async def test_pipeline_byte_identical_mixed_gather():
    """Mixed steps pipelined behind in-flight dispatches (q_len=1 rows
    reading the device carry) emit exactly the plain engine's greedy
    streams — and the pipeline actually engaged."""
    ref = await _plain_reference()
    engine = make_engine(mixed_batching=True, mixed_step_tokens=64)
    assert engine.config.step_pipeline  # the default this PR ships
    got = await _admission_wave(engine)
    ps = engine.phase_stats
    await engine.close()
    assert ps["mixed_steps"] > 0
    assert ps["mixed_carry_rows"] > 0, "no build ever read the device carry"
    assert ps["pipeline_overlapped"] > 0, "no sync overlapped a dispatch"
    assert ps["mixed_holds"] == 0, "pipelined engines never park a tick"
    assert got == ref


async def test_pipeline_byte_identical_mixed_pallas():
    """Same contract through the pallas (interpret) backend: the in-jit
    carry read + device-table gather feed the ragged flash path."""
    ref = await _plain_reference({"attn_backend": "pallas"})
    engine = make_engine(
        attn_backend="pallas", mixed_batching=True, mixed_step_tokens=64
    )
    got = await _admission_wave(engine)
    ps = engine.phase_stats
    await engine.close()
    assert ps["mixed_steps"] > 0
    assert ps["mixed_carry_rows"] > 0
    assert got == ref


async def test_serialized_baseline_byte_identical():
    """step_pipeline=False restores the dispatch->fetch->sync steps (the
    bench A/B baseline): scheduling changes, streams must not."""
    ref = await _plain_reference()
    engine = make_engine(
        mixed_batching=True, mixed_step_tokens=64, step_pipeline=False
    )
    got = await _admission_wave(engine)
    ps = engine.phase_stats
    await engine.close()
    assert ps["mixed_steps"] > 0
    assert ps["mixed_carry_rows"] == 0, "serialized builds never use carry"
    assert ps["pipeline_overlapped"] == 0
    assert got == ref


async def test_preemption_rearms_carry(caplog):
    """Carry-staleness regression: under page pressure a sequence is
    preempted (possibly between a dispatch and its sync, mid-pipeline)
    and its slot reused. The preempt must revoke the carry license and
    re-admission must re-arm through the prefill override — a reused
    slot reading the dead tenant's device carry would diverge."""
    import logging

    ref = await _plain_reference({"num_pages": 24})
    engine = make_engine(
        num_pages=24, mixed_batching=True, mixed_step_tokens=64
    )
    with caplog.at_level(logging.INFO, logger="dynamo_tpu.engine"):
        got = await _admission_wave(engine)
    await engine.close()
    assert any("preempting" in r.message for r in caplog.records), (
        "workload never preempted — shrink num_pages"
    )
    assert got == ref


async def test_spec_stale_history_sheds_drafts(monkeypatch):
    """Spec fallback: a carry row whose gate is CLOSED cannot draft
    (host history is stale) — it must shed and still advance at
    q_len=1, never stall or abort the step."""
    ref = await _plain_reference()
    # gate every stream off: the sync-first escape (which trades the
    # overlap for drafting when the gate is open) stands down and every
    # spec-eligible carry row takes the shed path
    monkeypatch.setattr(NgramProposer, "gate_open", lambda self: False)
    monkeypatch.setattr(NgramProposer, "maybe_draft", lambda self, k: [])
    engine = make_engine(
        mixed_batching=True, mixed_step_tokens=64, spec_decode=True
    )
    got = await _admission_wave(engine)
    ps = engine.phase_stats
    await engine.close()
    assert ps["mixed_steps"] > 0
    assert ps["mixed_spec_shed"] > 0, "no carry row ever shed a draft"
    assert got == ref


async def test_spec_gate_open_syncs_first_and_drafts():
    """The other half of the trade: gate-OPEN carry rows give up one
    overlap to sync host history and DRAFT — steady pipelined flow must
    not silently lose the spec x mixed win."""
    ref = await _plain_reference()
    engine = make_engine(
        mixed_batching=True, mixed_step_tokens=64, spec_decode=True
    )
    got = await _admission_wave(engine)
    ps = engine.phase_stats
    await engine.close()
    assert ps["mixed_spec_rows"] > 0, "pipelining starved the composition"
    assert ps["spec_drafted"] > 0
    assert got == ref


async def test_pipelined_spec_sync_keeps_carried_row_position(monkeypatch):
    """Regression: a dlen=0 (shed) carry row in a PIPELINED spec-mode
    mixed step is advanced at build time, and the NEXT pipelined build
    may advance it again before the first step's sync runs — that sync
    must NOT rewind `device_pos` through `_emit_verify_row`'s absolute
    assignment (the non-spec branch already guards this with
    `if not pipelined`). Two repetitive held streams interleave
    drafting and carry-shedding IN THE SAME STEP: held A is repetitive
    and keeps its REAL gate (open — so a carried A takes the sync-first
    escape and drafts, making the step spec-mode and blocking A the
    following tick), while held B's gate is forced closed (a stream
    whose early drafts were rejected: ema under the gate, countdown
    armed) so B never drafts, always rides q_len=1, and is the shed
    carry row of every consecutive pipelined step."""
    held_b = list(range(60, 84))

    async def two_held_wave(engine):
        out = {"a": [], "b": []}
        decoding = asyncio.Event()

        async def held(name, prompt):
            pre = greedy_request(prompt, 64)
            async for f in await engine.generate(Context(pre.to_dict())):
                out[name] += f.get("token_ids") or []
                if min(len(out["a"]), len(out["b"])) > 1:
                    decoding.set()  # both decode: the wave's cue

        ta = asyncio.create_task(held("a", REPETITIVE))
        tb = asyncio.create_task(held("b", held_b))
        await decoding.wait()
        wave = [([11 + w, 29, 5, 60] * 12)[:45] for w in range(6)]
        streams = await asyncio.gather(
            *(collect(engine, greedy_request(p, 10)) for p in wave)
        )
        await ta
        await tb
        return out["a"], out["b"], streams

    # enough concurrent prefill rows (max_batch_size 8: both held + 6
    # wave prompts) that one mixed step cannot drain the queue — the
    # pipelined chain needs a NEXT step to build behind the last one
    big = dict(num_pages=128, max_batch_size=8)
    plain = make_engine(**big)
    ref = await two_held_wave(plain)
    await plain.close()
    # B's proposer: gate forced closed (no sync-first escape when B is
    # carried -> the shed path) and no proposals even when free (the
    # tiny model's looping continuation would otherwise hand B n-gram
    # hits after a few tokens). A and the wave keep real behavior.
    orig_gate = NgramProposer.gate_open
    orig_prop = NgramProposer.propose

    def _is_b(p):
        return p.history[:1] == [held_b[0]]

    monkeypatch.setattr(
        NgramProposer, "gate_open",
        lambda self: False if _is_b(self) else orig_gate(self),
    )
    monkeypatch.setattr(
        NgramProposer, "propose",
        lambda self, k: [] if _is_b(self) else orig_prop(self, k),
    )
    engine = make_engine(
        mixed_batching=True, mixed_step_tokens=64, spec_decode=True, **big
    )
    got = await two_held_wave(engine)
    ps = engine.phase_stats
    await engine.close()
    assert ps["mixed_spec_rows"] > 0, "no spec-mode mixed step ran"
    assert ps["mixed_spec_shed"] > 0, "no carry row ever shed"
    assert got == ref


async def test_mixed_dispatch_failure_degrades_and_reports(monkeypatch):
    """A failing mixed dispatch family must degrade to the contained
    normal paths (restoring prefill picks and pipelined row state) and
    surface it: metrics()['mixed_disabled'] == 1 for the /metrics
    scrape, matching the phase counter."""
    ref = await _plain_reference()
    engine = make_engine(mixed_batching=True, mixed_step_tokens=64)

    def boom(bld):
        raise RuntimeError("injected mixed dispatch failure")

    monkeypatch.setattr(engine, "_run_mixed_dispatch", boom)
    got = await _admission_wave(engine)
    m = engine.metrics()
    ps = engine.phase_stats
    await engine.close()
    assert engine._mixed_disabled
    assert m["mixed_disabled"] == 1
    assert ps["mixed_disabled"] == 1
    assert got == ref


async def test_healthy_engine_reports_mixed_enabled():
    engine = make_engine(mixed_batching=True)
    assert engine.metrics()["mixed_disabled"] == 0
    await engine.close()


async def test_device_tables_follow_page_growth():
    """The block table a decode program reads must follow page growth:
    a single stream decoding across several page boundaries exercises
    exactly the admit -> grow -> grow chain, each dispatch reading the
    row its build snapshot from the host mirror (regression for the
    stale-table bug: divergence a few tokens past the first boundary)."""
    prompt = [3, 14, 15, 92, 65, 35, 89, 79, 32, 38, 46]
    plain = make_engine(step_pipeline=False)
    ref = await collect(plain, greedy_request(prompt, 40))
    await plain.close()
    engine = make_engine()
    got = await collect(engine, greedy_request(prompt, 40))
    await engine.close()
    assert len(ref) == 40
    assert got == ref


# ---- one launch per dispatch ------------------------------------------


class _Launches:
    """Stands in for the engine's `_kv_lock` and lists, per critical
    section, the compiled programs its holder launched (`programs`) and
    which of them were eager primitives (`eager`)."""

    def __init__(self, engine):
        self.lock, engine._kv_lock = engine._kv_lock, self
        self.holder = None
        self.sections = []

    def acquire(self, *a, **kw):
        got = self.lock.acquire(*a, **kw)
        if got:
            self.holder = threading.get_ident()
            self.sections.append({"programs": [], "eager": []})
        return got

    def release(self):
        self.holder = None
        self.lock.release()

    def __enter__(self):
        self.acquire()

    def __exit__(self, *exc):
        self.release()

    def note(self, name, eager=False):
        if threading.get_ident() == self.holder:
            self.sections[-1]["programs"].append(name)
            if eager:
                self.sections[-1]["eager"].append(name)

    def of(self, step):
        """The sections that launched the step program `step`."""
        return [sec for sec in self.sections
                if any(step in name for name in sec["programs"])]


@contextlib.contextmanager
def _count_launches(engine):
    """Every launch passes one of two places in jax 0.9 (the
    installation pyproject.toml pins): a jit call served by the C++
    fast path ends in the post hook (`fun._fun._apply_primitive` marks
    an eager primitive's program); the first call of a program, and any
    call off the fast path, goes through `ExecuteReplicated.__call__`."""
    from jax._src import api
    from jax._src.interpreters import pxla

    rec = _Launches(engine)

    def fast(fun, args, kwargs, out):
        f = getattr(fun, "_fun", fun)
        rec.note(getattr(f, "__name__", str(f)),
                 eager=getattr(f, "_apply_primitive", False))

    slow = pxla.ExecuteReplicated.__call__

    def first(self, *args):
        rec.note(self.name)
        return slow(self, *args)

    api._post_hook_state.set_global(fast)
    pxla.ExecuteReplicated.__call__ = first
    try:
        yield rec
    finally:
        pxla.ExecuteReplicated.__call__ = slow
        api._post_hook_state.set_global(None)
        engine._kv_lock = rec.lock


async def _steady_decode(engine, seen):
    await collect(engine, greedy_request(REPETITIVE, 40))
    return "_decode_multi", lambda: True


async def _decode_joined_grown_overridden(engine, seen):
    """Beside a held stream: a prompt whose row becomes ready in the
    tick of a decode dispatch, page growth (every 8 tokens) and a disagg
    inject, whose first token is an integer override."""
    inject = greedy_request(list(range(100, 130)), 12)
    first, k, v, ks, vs = await engine.prefill_only(inject)

    async def injected():
        ctx = Context(inject.to_dict())
        return [f async for f in await engine.generate_remote(
            ctx, first, k, v, ks, vs)]

    await asyncio.gather(
        _admission_wave(engine), injected(),
    )
    return "_decode_multi", lambda: (
        any(b.rows_i[:, 3].any() for b in seen)           # an override
        and len({len(b.active) for b in seen}) > 1        # rows joined
        and len({b.rows_i[:, 6:].tobytes() for b in seen}) > 2  # tables moved
    )


async def _prefill_group(engine, seen):
    rng = np.random.RandomState(1)
    wave = [rng.randint(1, 200, size=45).tolist() for _ in range(3)]
    await asyncio.gather(*(collect(engine, greedy_request(p, 4)) for p in wave))
    return "_model_step", lambda: True


@pytest.mark.parametrize("traffic", [
    _steady_decode, _decode_joined_grown_overridden, _prefill_group,
], ids=lambda f: f.__name__.strip("_"))
async def test_a_dispatch_is_one_launch(traffic):
    """Between `_kv_lock` acquire and release a dispatch runs exactly
    one compiled program, its step, and no eager primitive: whatever
    else touched a device array there (a slice, an `.at[].set`, a key
    split) would be a launch of its own that the runtime cannot queue
    behind the running step (PERF.md, PR 24 / PR 25)."""
    engine = make_engine()
    seen = []
    run = engine._run_decode_dispatch_locked
    engine._run_decode_dispatch_locked = (
        lambda bld, rec: (seen.append(bld), run(bld, rec))[1])
    with _count_launches(engine) as launches:
        step, happened = await traffic(engine, seen)
        await engine.close()
    assert happened(), "the traffic never made the dispatch under test"
    sections = launches.of(step)
    assert len(sections) >= 2
    for sec in sections:
        assert len(sec["programs"]) == 1, sec
        assert not sec["eager"], sec
    # and nothing eager under the lock anywhere else (`_extract`,
    # `_inject` are programs of their own sections)
    assert not [sec for sec in launches.sections if sec["eager"]]


def _seeded(prompt, max_tokens, seed):
    return PreprocessedRequest(
        token_ids=list(prompt),
        stop_conditions=StopConditions(max_tokens=max_tokens, ignore_eos=True),
        sampling_options=SamplingOptions(
            temperature=0.9, top_k=40, seed=seed),
    )


def disturbed_requests(seeded: bool):
    """(held, wave of 3, inject): the requests of `disturbed_run`."""
    rng = np.random.RandomState(0)
    wave = [rng.randint(1, 200, size=45).tolist() for _ in range(3)]
    req = (lambda p, n, i: _seeded(p, n, 1000 + i)) if seeded else (
        lambda p, n, i: greedy_request(p, n))
    return (req(REPETITIVE, 48, 0),
            [req(p, 10, 1 + i) for i, p in enumerate(wave)],
            # a prefill_only row has no slot and samples on the plain
            # path: its first token is reproducible only when greedy
            greedy_request(list(range(100, 130)), 12))


async def disturbed_run(engine, seeded: bool):
    """A held stream; once it decodes, three prompts join beside the
    dispatch in flight (24 pages: someone is preempted and re-armed
    through a re-prefill) and a disagg inject enters with its integer
    first token. Public entry points only. Returns every stream."""
    held, wave, inject = disturbed_requests(seeded)
    first, k, v, ks, vs = await engine.prefill_only(inject)
    out = []
    decoding = asyncio.Event()

    async def hold():
        async for f in await engine.generate(Context(held.to_dict())):
            out.extend(f.get("token_ids") or [])
            if len(out) > 1:
                decoding.set()

    async def injected():
        frames = [f async for f in await engine.generate_remote(
            Context(inject.to_dict()), first, k, v, ks, vs)]
        return [t for f in frames for t in f.get("token_ids") or []]

    task = asyncio.create_task(hold())
    await decoding.wait()
    streams = await asyncio.gather(
        *(collect(engine, r) for r in wave), injected())
    await task
    return [out, *streams]


@pytest.mark.parametrize("seeded", [False, True], ids=["greedy", "seeded"])
async def test_disturbed_rows_keep_their_tokens(seeded, caplog):
    """A row that joins while a dispatch is in flight (its first token
    written into the carry by the prefill program), a preempted row
    (re-armed by its re-prefill) and a disagg inject (an integer
    override in the next decode program's upload) emit what each
    request emits alone on a serialized engine. (The same run gave the
    parent commit's tokens, stream for stream: CHANGES.md, PR 25.)"""
    import logging

    held, wave, inject = disturbed_requests(seeded)
    alone = make_engine(step_pipeline=False)
    ref = [await collect(alone, r) for r in (held, *wave, inject)]
    await alone.close()
    engine = make_engine(num_pages=24)
    with caplog.at_level(logging.INFO, logger="dynamo_tpu.engine"):
        got = await disturbed_run(engine, seeded)
    await engine.close()
    assert any("preempting" in r.message for r in caplog.records), (
        "workload never preempted — shrink num_pages"
    )
    assert got == ref
