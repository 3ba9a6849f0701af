"""The engine's tick on the profiler's clock (utils/tracing.py `phase`,
docs/observability.md "Host phases"): every `eng.*` phase shows up on the
host plane of a `jax.profiler` capture beside the four dispatch
annotations, the flight digests carry the tick's host-side columns, the
engine counts its preemptions, and a finish summary splits its TTFT."""

from __future__ import annotations

import asyncio
import glob
import os
import sys
import time

import jax
import pytest

from dynamo_tpu.engine import flight_recorder as flightmod
from dynamo_tpu.engine import profiler
from dynamo_tpu.utils import tracing

from .test_engine import collect, greedy_request, make_engine

LOOP_PHASES = {"eng.tick", "eng.admit", "eng.prefill.build",
               "eng.decode.build", "eng.fetch", "eng.emit", "eng.wait",
               "eng.join"}
WORKER_PHASES = {"eng.lock", "eng.upload", "eng.enqueue"}
DISPATCH = ("prefill", "decode", "mixed", "spec_verify")
REPETITIVE = [5, 17, 42, 9] * 6
# PR 38's digest columns: the worker's on every dispatch row, the loop's
# on the landings
WORKER_COLUMNS = ("lock_s", "upload_s", "enqueue_s")
TICK_COLUMNS = ("tick_s", "admit_s", "join_s", "unphased_s")
TICK_PARTS = ("fetch", "dispatch", "host", "unphased")
LIB = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "benchmark", "lib")


def _read(name, digests):
    """The benchmark's reader of one per-layer metric, on these digests."""
    sys.path.insert(0, LIB)
    try:
        import harness
        return harness.read_metric("layer_metrics", name, {"digests": digests})
    finally:
        sys.path.remove(LIB)


async def _capture(engine, tmp_path, prompts, max_tokens=24):
    """Serve `prompts` under a profiler capture (after a warm-up that
    compiles outside it); returns {line index: [(name, start, end, stats)]}
    of the host plane."""
    await collect(engine, greedy_request(prompts[0], max_tokens=max_tokens))
    await asyncio.sleep(0.2)  # the pipeline's overshoot dispatch drains
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    async def later(p):  # prompts that arrive beside running decodes
        await asyncio.sleep(0.1)
        return await collect(engine, greedy_request(p, max_tokens=max_tokens))
    try:
        await asyncio.gather(
            collect(engine, greedy_request(prompts[0], max_tokens=max_tokens)),
            *(later(p) for p in prompts[1:]))
        await asyncio.sleep(0.05)  # the loop goes idle: eng.wait
        await collect(engine, greedy_request(prompts[-1], max_tokens=4))
        await asyncio.sleep(0.2)  # no dispatch open when the capture stops
    finally:
        jax.profiler.stop_trace()
    await engine.close()
    files = glob.glob(os.path.join(
        str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
    assert files, "the capture wrote no xplane"
    data = jax.profiler.ProfileData.from_file(files[0])
    plane = next(p for p in data.planes if p.name == "/host:CPU")
    lines = {}
    for i, line in enumerate(plane.lines):
        evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                dict(e.stats)) for e in line.events
               if e.name.startswith("eng.") or e.name in DISPATCH]
        if evs:
            lines[i] = evs
    return lines


def _inside(child, parents) -> bool:
    return any(p[1] <= child[1] and child[2] <= p[2] for p in parents)


async def test_every_phase_is_on_the_host_plane(tmp_path):
    lines = await _capture(
        make_engine(), tmp_path, [list(range(3, 40)), [9, 8, 7, 6]])
    names = {e[0] for evs in lines.values() for e in evs}
    assert LOOP_PHASES | WORKER_PHASES <= names, sorted(names)
    # the dispatch annotations keep their exact names (the accepted
    # reducer matches them) and carry no attribute
    assert {"prefill", "decode"} <= names
    for evs in lines.values():
        assert all(not e[3] for e in evs if e[0] in DISPATCH)
    # one thread runs the loop: every tick and the loop's own phases
    loop = [evs for evs in lines.values()
            if any(e[0] == "eng.tick" for e in evs)]
    assert len(loop) == 1
    ticks = [e for e in loop[0] if e[0] == "eng.tick"]
    assert len(ticks) >= 3
    first, last = min(t[1] for t in ticks), max(t[2] for t in ticks)
    for e in loop[0]:
        # the loop task's children lie inside a tick (a first-token
        # fetch is a task of its own and may straddle two; the tick open
        # when the capture starts or stops is not recorded)
        if e[0] in ("eng.admit", "eng.prefill.build", "eng.decode.build",
                    "eng.wait") and first <= e[1] and e[2] <= last:
            assert _inside(e, ticks), e
    assert all(e[0] in LOOP_PHASES for e in loop[0]
               if e[0].startswith("eng.") and e[0] != "eng.prefill.build")
    # workers: lock, upload and the jit call inside a dispatch annotation
    for evs in lines.values():
        spans = [e for e in evs if e[0] in DISPATCH]
        for e in evs:
            if e[0] in WORKER_PHASES:
                assert _inside(e, spans), e
    # a phase says where the time went and carries nothing else: no
    # attribute that no reader wants
    assert all(not e[3] for evs in lines.values() for e in evs)


@pytest.mark.parametrize("kind,config", [
    ("mixed", dict(mixed_batching=True, mixed_step_tokens=64)),
    ("spec_verify", dict(spec_decode=True)),
])
async def test_other_dispatch_kinds_keep_their_annotation(
        tmp_path, kind, config):
    lines = await _capture(
        make_engine(max_model_len=256, **config), tmp_path,
        [REPETITIVE, REPETITIVE[2:] + [7, 7] + REPETITIVE, REPETITIVE[1:] * 2],
        max_tokens=60)
    names = {e[0] for evs in lines.values() for e in evs}
    assert kind in names, sorted(names)
    # its jit call is an eng.enqueue inside the annotation
    for evs in lines.values():
        spans = [e for e in evs if e[0] == kind]
        if spans:
            assert any(e[0] == "eng.enqueue" and _inside(e, spans)
                       for e in evs)


def test_phase_feeds_the_ring_when_armed():
    # one helper: the engine's name for it is the frontend's object, and
    # importing the profiler module put its annotations on it
    assert profiler.phase is tracing.phase
    assert tracing.annotation is jax.profiler.TraceAnnotation
    tracing.clear()
    tracing.enable()
    try:
        with profiler.phase("eng.admit") as ph:
            ph.set(admitted=2)
        with tracing.phase("fe.stream", req="r-1"):
            pass
        evs = {e["name"]: e for e in tracing.export()["traceEvents"]
               if e["ph"] == "X"}
    finally:
        tracing.disable()
        tracing.clear()
    assert evs["eng.admit"]["args"] == {"admitted": 2}
    assert evs["fe.stream"]["args"]["request_id"] == "r-1"
    # off: nothing recorded, nothing raised
    with profiler.phase("eng.admit"), tracing.phase("fe.stream"):
        pass
    assert not [e for e in tracing.export()["traceEvents"] if e["ph"] != "M"]


async def test_digest_columns():
    engine = make_engine()
    await asyncio.gather(
        collect(engine, greedy_request(list(range(3, 43)), max_tokens=20)),
        collect(engine, greedy_request([9, 8, 7], max_tokens=20)))
    rows = engine.flight.snapshot()
    await engine.close()
    assert flightmod.FIELDS[12:16] == (
        "build_s", "emit_s", "starved", "preempted")
    assert flightmod.FIELDS[:12] == (  # the accepted columns, in place
        "ts_unix", "step", "kind", "rows", "tokens", "wall_s", "budget_fill",
        "queue_depth", "slots_active", "kv_frac", "degrade_mask", "outlier")
    by = {}
    for r in rows:
        by.setdefault(r["kind"], []).append(r)
    assert all(r["build_s"] > 0 and r["starved"] in (0, 1)
               for r in by["decode"] + by["prefill"])
    landed = by.get("sync", []) + by.get("overlap", [])
    assert landed and all(r["emit_s"] > 0 for r in landed)
    assert all(r["emit_s"] == 0 for r in by["decode"])
    assert all(r["preempted"] == 0 for r in rows)
    # the host's clock by phase (PR 38), appended: a dispatch row holds
    # its worker's lock, uploads and launch, a landing the loop's tick
    # (then PR 39's two, an expert layer's pass by blocks of rows, and
    # PR 41's two, a model with state pools: zero on a dense model's rows)
    # (and PR 47's four, a model generated by diffusion over blocks, and
    # PR 48's one, its block kernel's work items)
    assert flightmod.FIELDS[-16:-9] == WORKER_COLUMNS + TICK_COLUMNS
    assert flightmod.FIELDS[-9:] == (
        "moe_row_blocks", "moe_pairs_held",
        "state_slots_held", "state_rows_advanced",
        "dlm_passes", "dlm_row_passes", "dlm_filled", "dlm_committed",
        "dlm_work_items")
    assert all(r["state_slots_held"] == r["state_rows_advanced"] == 0
               for r in rows)
    for r in by["decode"] + by["prefill"]:
        assert r["upload_s"] > 0 and r["enqueue_s"] > 0 and r["lock_s"] >= 0
        assert (r["lock_s"] + r["upload_s"] + r["enqueue_s"]
                <= r["wall_s"] + 1e-5)  # each is rounded to a microsecond
        assert all(r[c] == 0 for c in TICK_COLUMNS)
    assert all(r[c] == 0 for r in landed for c in WORKER_COLUMNS)
    # the first landing opens no tick; every later one closes a tick that
    # holds its own fetch and landing, and its parts fit inside it
    assert landed[0]["tick_s"] == 0 and len(landed) >= 3
    for r in landed[1:]:
        assert r["tick_s"] >= r["wall_s"] + r["emit_s"] - 1e-5
        assert 0 <= r["unphased_s"] <= r["tick_s"]
        assert 0 <= r["admit_s"] < r["tick_s"] and r["join_s"] >= 0


async def test_phase_totals_grow_with_no_capture_and_no_ring():
    """The host's clock is always on: with the ring disarmed and no
    profiler capture, every phase of a served request adds its seconds
    and its count to `phase_totals()`, `compile_stats()` carries them,
    and `Engine.metrics()` renders them as one labelled series."""
    from dynamo_tpu.engine import telemetry
    from dynamo_tpu.llm.http.metrics import EngineMetrics

    assert not tracing.enabled()
    before = tracing.phase_totals()
    engine = make_engine()
    await collect(engine, greedy_request([5, 6, 7], max_tokens=20))
    after = tracing.phase_totals()
    stats = telemetry.compile_stats()
    text = list(EngineMetrics(engine).render())
    flat = engine.metrics()
    await engine.close()
    assert not [e for e in tracing.export()["traceEvents"] if e["ph"] != "M"]
    for name in (LOOP_PHASES | WORKER_PHASES
                 | {"eng.init.weights", "eng.init.pools"}) - {"eng.wait"}:
        was = before.get(name, [0.0, 0])
        assert after[name][1] > was[1], name
        assert after[name][0] > was[0], name
        assert stats["phase_s"][name] >= round(after[name][0], 4) - 1e-4
    assert stats["trace_s"] > 0 and stats["lower_s"] > 0
    assert stats["at_s"] <= time.monotonic()
    # the operator's view: seconds by phase, no stamp, nothing nested
    assert "at_s" not in flat and "phase_s" not in flat
    assert flat["phase_seconds_total"]["eng.fetch"] > 0
    assert any(line.startswith(
        'dynamo_tpu_engine_phase_seconds_total{phase="eng.fetch"} ')
        for line in text)
    assert "# TYPE dynamo_tpu_engine_phase_seconds_total counter" in text


def test_phase_totals_lose_no_count_across_threads():
    """Every thread adds to a table of its own, so threads that close
    phases at once, more of them than cores and switched often, lose no
    count while another thread sums the tables; a thread that has ended
    leaves its seconds behind."""
    import threading

    n_threads, n_phases = 4 * (os.cpu_count() or 4), 2000
    name = "test.stress_phase"
    base = tracing.phase_totals().get(name, [0.0, 0])[1]

    def work():
        for _ in range(n_phases):
            with tracing.phase(name):
                pass

    threads = [threading.Thread(target=work) for _ in range(n_threads)]
    was = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for t in threads:
            t.start()
        deadline = time.monotonic() + 60
        while any(t.is_alive() for t in threads):
            seen = tracing.phase_totals().get(name, [0.0, 0])[1]
            assert base <= seen <= base + n_threads * n_phases
            assert time.monotonic() < deadline
        for t in threads:
            t.join(timeout=10)
            assert not t.is_alive()
    finally:
        sys.setswitchinterval(was)
    for _ in range(2):  # the ended threads' tables are folded in once
        seconds, count = tracing.phase_totals()[name]
        assert count == base + n_threads * n_phases and seconds > 0


class _Clock:
    """`time.perf_counter` as the program reads it (the engine's stamps and
    `tracing.phase`'s tables both call it through the `time` module): the
    real clock plus a skew the test puts in. A stop is then as long as the
    test says, not as long as this machine's other work lets it look."""

    def __init__(self):
        self.real, self.skew = time.perf_counter, 0.0

    def __call__(self) -> float:
        return self.real() + self.skew

    def stop(self, seconds: float) -> None:
        self.skew += seconds


STOP = 10.0  # seconds a stop takes on the program's clock
# what is not the stop: an absolute ceiling over the 0.45-0.55 s this
# machine's six test workers stretched a tick by (the driver's run of PR
# 39's tree), so that a real fault of a second or two in another phase
# still fails
OTHER = 1.5


async def test_a_stop_is_named_by_thread_and_phase(monkeypatch):
    """One decode launch that takes STOP longer shows in its dispatch
    row's `enqueue_s` and in the `join_s` of the landing that waited for
    it; one fetch that takes STOP longer in its landing's `wall_s`. The
    benchmark's readers (`lib/host_clock.py`) then say how long the stop
    was and on which thread it sat.

    The stop is put on a clock the test controls (`_Clock`): 0.1 s of real
    sleep, so that the loop has come to wait for the late worker, then
    STOP seconds of skew, which every open phase and stamp sees at once.
    Ten seconds are more than six test workers on one machine stretch any
    other tick of this 3 ms loop by (the driver's run of PR 39's tree read
    0.45 s of host and 0.55 s of no phase beside a 0.3 s stop that slept
    for real, and failed)."""
    clock = _Clock()
    monkeypatch.setattr(time, "perf_counter", clock)
    engine = make_engine()
    slow = {"decode": 0, "fetch": 0}  # launches / fetches until the stop
    launch = engine._decode_fn

    def stop():
        time.sleep(0.1)
        clock.stop(STOP)

    def decode_fn(*args, **kw):
        slow["decode"] -= 1
        if slow["decode"] == 0:
            stop()
        return launch(*args, **kw)

    to_thread = asyncio.to_thread

    async def fetch_to_thread(fn, *args, **kw):
        if "_fetch.<locals>" in getattr(fn, "__qualname__", ""):
            slow["fetch"] -= 1
            if slow["fetch"] == 0:
                def late():
                    stop()
                    return fn(*args, **kw)
                return await to_thread(late)
        return await to_thread(fn, *args, **kw)

    engine._decode_fn = decode_fn
    monkeypatch.setattr(asyncio, "to_thread", fetch_to_thread)
    request = greedy_request([5, 6, 7], max_tokens=100)
    await collect(engine, request)  # every program this request meets
    got = {}
    for part in ("decode", "fetch"):
        await asyncio.sleep(0.2)  # the overshoot dispatch drains
        n = len(engine.flight.snapshot())
        slow[part] = 6
        await collect(engine, request)
        got[part] = engine.flight.snapshot()[n:]
    await engine.close()

    # (what is not the stop is held under OTHER, whatever STOP is)
    rows = got["decode"]
    late = max((r for r in rows if r["kind"] == "decode"),
               key=lambda r: r["enqueue_s"])
    assert STOP <= late["enqueue_s"] < 1.5 * STOP
    assert late["lock_s"] < OTHER
    joined = max((r for r in rows if r["kind"] in ("sync", "overlap")),
                 key=lambda r: r["join_s"])
    assert STOP <= joined["join_s"] <= joined["tick_s"]
    assert joined["wall_s"] < OTHER and joined["unphased_s"] < OTHER
    rows = got["fetch"]
    late = max((r for r in rows if r["kind"] in ("sync", "overlap")),
               key=lambda r: r["wall_s"])
    assert STOP <= late["wall_s"] <= late["tick_s"]
    assert late["join_s"] < OTHER
    for part, want in (("decode", "dispatch"), ("fetch", "fetch")):
        # the readers on the dozen ticks around the stop (the exact sums
        # are the synthetic cases of tests/test_benchmark_tracing.py)
        column, kinds = (("enqueue_s", ("decode",)) if part == "decode"
                         else ("wall_s", ("sync", "overlap")))
        at = max(range(len(got[part])), key=lambda i: (
            got[part][i]["kind"] in kinds) * got[part][i][column])
        near = got[part][max(at - 24, 0):at + 24]
        assert _read("stall_s", near) >= STOP - 0.01
        assert _read("tick_max_ms", near) >= STOP * 1e3
        assert _read("tick_p50_ms", near) < OTHER * 1e3
        parts = {name: _read(f"tick_max_{name}_ms", near)
                 for name in TICK_PARTS}
        assert max(parts, key=parts.get) == want, (part, parts)
        assert parts[want] >= (STOP - 0.01) * 1e3, (part, parts)
        assert all(ms < OTHER * 1e3 for name, ms in parts.items()
                   if name != want), (part, parts)
    # and a program from before the columns gives the readers nothing
    old = [{k: v for k, v in r.items()
            if k not in WORKER_COLUMNS + TICK_COLUMNS} for r in rows]
    for name in ("stall_s", "tick_p50_ms", "tick_max_ms",
                 *(f"tick_max_{p}_ms" for p in TICK_PARTS)):
        assert _read(name, old) is None, name


async def test_decode_digests_count_the_kv_pages(tmp_path):
    """A pallas engine books, on every decode row, the KV pages one
    layer's kernel copies in over the dispatch's steps beside the pages
    its rows hold; the two are equal (the kernel reads what a sequence
    holds), 0 on every other row, and 0 where the gather path serves."""
    # columns are only ever appended: these were the first two of PR 32's
    # six, and PRs 36, 37 and 38 put theirs behind
    assert flightmod.FIELDS[16:18] == ("kv_pages_streamed", "kv_pages_held")
    engine = make_engine(attn_backend="pallas")
    ps, steps = engine.page_size, engine.config.decode_steps
    # one request alone: its first decode dispatch attends 4, 5, ...
    # positions over the scan's steps
    await collect(engine, greedy_request([5, 6, 7], max_tokens=12))
    first = next(
        r for r in engine.flight.snapshot() if r["kind"] == "decode")
    assert first["rows"] == 1
    assert first["kv_pages_held"] == sum(
        -(-(4 + j) // ps) for j in range(steps))
    await asyncio.gather(
        collect(engine, greedy_request(list(range(3, 43)), max_tokens=20)),
        collect(engine, greedy_request([9, 8, 7], max_tokens=20)))
    rows = engine.flight.snapshot()
    await engine.close()
    decodes = [r for r in rows if r["kind"] == "decode"]
    assert max(r["rows"] for r in decodes) == 2
    for r in decodes:
        assert r["kv_pages_streamed"] == r["kv_pages_held"] > 0
        # a row attends 1..max_model_len positions in each of the steps
        assert r["rows"] * steps <= r["kv_pages_held"]
        assert r["kv_pages_held"] <= r["rows"] * steps * -(-128 // ps)
    assert all(r["kv_pages_streamed"] == r["kv_pages_held"] == 0
               for r in rows if r["kind"] != "decode")
    # a model of one kind of layer has no window layer's work items
    assert all(r["kv_win_items"] == 0 for r in rows)

    gather = make_engine()
    await collect(gather, greedy_request([5, 6, 7], max_tokens=12))
    rows = gather.flight.snapshot()
    await gather.close()
    assert any(r["kind"] == "decode" for r in rows)
    assert all(r["kv_pages_held"] == 0 for r in rows)


def test_amend_fills_the_newest_digest_of_its_kind():
    """A sync row is booked before its tokens land (its pool and queue
    columns are the step's, not the landing's) and gets `emit_s` after;
    a dispatch worker may have booked rows in between."""
    rec = flightmod.FlightRecorder(capacity=8)
    rec.amend("sync", emit_s=1.0)  # nothing to amend: no-op
    rec.record("sync", 0.01, rows=3)
    rec.record("sync", 0.02, rows=4)
    rec.record("decode", 0.1, rows=4, build_s=0.5)
    rec.amend("sync", emit_s=0.25)
    rows = rec.snapshot()
    assert [r["emit_s"] for r in rows] == [0.0, 0.25, 0.0]
    assert rows[2]["build_s"] == 0.5 and rec.count == 3
    for _ in range(7):  # wrapped around: the sync rows are gone
        rec.record("decode", 0.1)
    rec.amend("sync", emit_s=9.0)
    assert all(r["emit_s"] == 0.0 for r in rec.snapshot())


async def test_preemptions_are_counted():
    # 15 usable pages, two long sequences: someone is preempted
    engine = make_engine(num_pages=16, max_model_len=96, max_batch_size=2)
    prompts = [list(range(20, 52)), list(range(60, 92))]
    await asyncio.gather(
        *(collect(engine, greedy_request(p, max_tokens=24)) for p in prompts))
    n = engine.metrics()["preemptions_total"]
    rows = engine.flight.snapshot()
    await engine.close()
    assert n >= 1
    assert max(r["preempted"] for r in rows) == n
    assert [r["preempted"] for r in rows] == sorted(
        r["preempted"] for r in rows)


async def test_serialized_engine_starves_every_decode_dispatch():
    """Without the step pipeline a dispatch is fetched before the next is
    built, so under decode-only traffic each decode program is enqueued
    on a drained device."""
    engine = make_engine(step_pipeline=False)
    await collect(engine, greedy_request([5, 6, 7], max_tokens=40))
    decodes = [r for r in engine.flight.snapshot() if r["kind"] == "decode"]
    await engine.close()
    # the first follows the prefill dispatch, which may still be running
    assert len(decodes) >= 3
    assert all(r["starved"] == 1 for r in decodes[1:])


async def test_summary_splits_its_ttft():
    engine = make_engine(max_batch_size=2)
    got = []
    engine.subscribe_requests(got.append)
    prompts = [list(range(3, 73)), [9, 8, 7], list(range(40, 80))]
    await asyncio.gather(
        *(collect(engine, greedy_request(p, max_tokens=6)) for p in prompts))
    await engine.close()
    assert len(got) == 3
    for s in got:
        assert s["ttft_s"] == pytest.approx(
            s["queue_wait_s"] + s["prefill_s"] + s["first_emit_s"], abs=1e-9)
        assert s["prefill_s"] > 0 and s["first_emit_s"] >= 0
        # chunks of at most 32 tokens
        assert s["prefill_chunks"] == -(-s["prompt_tokens"] // 32)
