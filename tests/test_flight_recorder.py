"""Forensics plane (docs/observability.md "Forensics plane"):
flight-recorder ring bounds, trigger dedup + rate limit, artifact
schema round trip, anomaly EMA math (boundary = not an outlier),
/debug/profile single-capture gate + no-op path, /debug/trace
track filtering + response cap, /debug/snapshot manual dumps."""

from __future__ import annotations

import asyncio
import contextlib
import json

import aiohttp
import pytest

from dynamo_tpu.engine import flight_recorder as flightmod
from dynamo_tpu.engine import profiler
from dynamo_tpu.engine.flight_recorder import (
    FIELDS,
    FlightRecorder,
    PhaseBaseline,
    digest_to_dict,
)
from dynamo_tpu.llm.http.metrics import SloTracker
from dynamo_tpu.llm.http.service import HttpService
from dynamo_tpu.utils import tracing


@pytest.fixture
def traced():
    tracing.clear()
    tracing.enable()
    yield
    tracing.disable()
    tracing.clear()


@pytest.fixture
def clock():
    class _Clock:
        t = 1000.0

        def __call__(self):
            return self.t

    return _Clock()


def make_recorder(tmp_path, clock=None, **kw):
    kw.setdefault("capacity", 64)
    kw.setdefault("cooldown_s", 30.0)
    return FlightRecorder(
        directory=str(tmp_path),
        clock=clock or __import__("time").monotonic,
        **kw,
    )


# ------------------------------------------------------------------- ring


def test_ring_bounds_under_sustained_steps(tmp_path):
    rec = make_recorder(tmp_path, capacity=64)
    for i in range(500):
        rec.record("decode", 0.001, rows=1, tokens=8, step=i)
    assert rec.count == 64
    rows = rec.snapshot_rows()
    assert len(rows) == 64
    # newest win, oldest first: steps 436..499 in order
    steps = [int(r[FIELDS.index("step")]) for r in rows]
    assert steps == list(range(436, 500))
    # `last` slices the newest N
    assert len(rec.snapshot_rows(last=8)) == 8
    assert [d["step"] for d in rec.snapshot(last=2)] == [498, 499]


def test_digest_fields_round_trip(tmp_path):
    rec = make_recorder(tmp_path)
    rec.record(
        "mixed", 0.25, rows=3, tokens=96, budget_fill=0.375,
        queue_depth=5, slots_active=2, kv_frac=0.5, degrade_mask=0b10,
        step=7,
    )
    d = digest_to_dict(rec.snapshot_rows()[-1])
    assert d["kind"] == "mixed"
    assert d["rows"] == 3 and d["tokens"] == 96
    assert d["budget_fill"] == pytest.approx(0.375)
    assert d["queue_depth"] == 5 and d["slots_active"] == 2
    assert d["kv_frac"] == pytest.approx(0.5)
    assert d["degrade_mask"] == 0b10 and d["step"] == 7
    assert d["wall_s"] == pytest.approx(0.25)


@pytest.mark.parametrize("kind,pages", [
    ("decode", (1356, 1164)), ("decode", (0, 0)),
    ("prefill", None), ("sync", None),
])
def test_kv_page_columns_round_trip(tmp_path, kind, pages):
    """The decode digest's two page counts ride the row, the snapshot and
    the artifact under their names; a row booked without them reads 0."""
    rec = make_recorder(tmp_path)
    host = {} if pages is None else dict(
        kv_pages_streamed=pages[0], kv_pages_held=pages[1])
    rec.record(kind, 0.01, rows=4, tokens=32, **host)
    want = pages or (0, 0)
    d = rec.snapshot()[-1]
    assert (d["kv_pages_streamed"], d["kv_pages_held"]) == want
    # columns are only ever appended: PR 36's five (a model with two
    # kinds of pool) follow the six that were the tail before it, PR 37's
    # one (a window layer's work items) follows them, then PR 38's seven
    # (the host's clock by phase on dispatch and on sync / overlap rows),
    # then PR 39's two (an expert layer's pass by blocks of rows), then
    # PR 41's two (a model with state pools), then PR 47's four (a model
    # generated by diffusion over blocks), then PR 48's one (its block
    # kernel's work items)
    assert FIELDS[16:] == ("kv_pages_streamed", "kv_pages_held",
                           "moe_experts_hit", "moe_load_max",
                           "frames", "gc_s",
                           "kv_frac_full", "kv_frac_win",
                           "kv_pages_held_full", "kv_win_pages_held",
                           "kv_win_pages_released", "kv_win_items",
                           "lock_s", "upload_s", "enqueue_s", "tick_s",
                           "admit_s", "join_s", "unphased_s",
                           "moe_row_blocks", "moe_pairs_held",
                           "state_slots_held", "state_rows_advanced",
                           "dlm_passes", "dlm_row_passes", "dlm_filled",
                           "dlm_committed", "dlm_work_items")
    assert d["state_slots_held"] == d["state_rows_advanced"] == 0
    assert d["dlm_passes"] == d["dlm_row_passes"] == d["dlm_filled"] == 0
    assert d["dlm_work_items"] == 0
    assert d["kv_frac_win"] == d["kv_win_pages_held"] == 0
    assert d["kv_win_items"] == 0
    assert d["moe_experts_hit"] == d["moe_load_max"] == 0
    assert d["moe_row_blocks"] == d["moe_pairs_held"] == 0
    with open(rec.trigger("manual")) as f:
        art = json.load(f)
    row = dict(zip(art["digest_fields"], art["digests"][-1]))
    assert (row["kv_pages_streamed"], row["kv_pages_held"]) == want
    assert row["rows"] == 4 and row["tokens"] == 32


@pytest.mark.parametrize("kind", ["sync", "overlap"])
def test_an_expert_pass_s_blocks_ride_the_landing_s_row(tmp_path, kind):
    """What an expert model's decode program returns with its tokens (the
    four means of `models/moe.py` `stats`) is amended onto the landing's
    row, the newest of its kind, and rides the artifact by name."""
    rec = make_recorder(tmp_path)
    rec.record(kind, 0.09, rows=184, tokens=1472)
    rec.record("decode", 0.002, rows=184, tokens=1472)
    rec.amend(kind, moe_experts_hit=15.75, moe_load_max=12.5,
              moe_row_blocks=1.0, moe_pairs_held=92.25)
    landing, dispatch = rec.snapshot()[-2:]
    assert (landing["moe_row_blocks"], landing["moe_pairs_held"]) == (
        1.0, 92.25)
    assert landing["moe_experts_hit"] == 15.75
    assert dispatch["moe_row_blocks"] == dispatch["moe_pairs_held"] == 0
    with open(rec.trigger("manual")) as f:
        art = json.load(f)
    row = dict(zip(art["digest_fields"], art["digests"][-2]))
    assert (row["moe_row_blocks"], row["moe_pairs_held"]) == (1.0, 92.25)


# --------------------------------------------------- trigger + rate limit


def test_trigger_rate_limit_dedups_a_storm(tmp_path, clock):
    rec = make_recorder(tmp_path, clock=clock, cooldown_s=30.0)
    rec.record("decode", 0.001)
    p1 = rec.trigger("slo_breach:t/ttft", request_id="r-1")
    assert p1 is not None
    # the storm: every further trigger inside the cooldown suppresses
    for _ in range(50):
        assert rec.trigger("slo_breach:t/ttft") is None
    assert rec.dumps_total == 1
    assert rec.suppressed_total == 50
    assert len(list(tmp_path.glob("flight_recorder_*.json"))) == 1
    # cooldown expiry re-arms
    clock.t += 31.0
    assert rec.trigger("watchdog:decode.dispatch") is not None
    assert rec.dumps_total == 2
    # force bypasses the limit (the manual /debug/snapshot path)
    assert rec.trigger("manual", force=True) is not None
    assert rec.dumps_total == 3


def test_artifact_schema_round_trip(tmp_path, clock, traced):
    rec = make_recorder(tmp_path, clock=clock, context_fn=lambda: {
        "metrics": {"kv_pages_free": 3}, "waiting": 2,
    })
    with tracing.request_scope("req-abc"):
        tracing.instant("seq.submit", cat="lifecycle")
        with tracing.span("prefill.wait"):
            pass
    tracing.instant("other", req="req-zzz")
    for i in range(10):
        rec.record("prefill", 0.002, rows=2, tokens=64, step=i)
    path = rec.trigger("slo_breach:default/ttft", request_id="req-abc")
    with open(path) as f:
        art = json.load(f)
    assert art["kind"] == "flight_recorder"
    assert art["trigger"] == "slo_breach"
    assert art["reason"] == "slo_breach:default/ttft"
    assert art["request_id"] == "req-abc"
    assert art["digest_fields"] == list(FIELDS)
    assert len(art["digests"]) == 10
    decoded = [digest_to_dict(r) for r in art["digests"]]
    assert all(d["kind"] == "prefill" for d in decoded)
    assert art["context"]["metrics"]["kv_pages_free"] == 3
    # the embedded trace is the SLICE for the offending request id
    evs = [e for e in art["trace"]["traceEvents"] if e["ph"] != "M"]
    assert evs, "trace slice empty"
    assert all(
        e["args"].get("request_id") == "req-abc" for e in evs
    )
    assert {"n", "p50_s", "p99_s", "threshold_s"} <= set(
        art["anomaly_baselines"]["prefill"]
    )


# ------------------------------------------------------------ anomaly EMA


def test_anomaly_boundary_is_not_an_outlier():
    base = PhaseBaseline(alpha=0.05, warmup=4, outlier_mult=3.0,
                         min_wall_s=0.0)
    for _ in range(4):
        assert base.observe(0.010) is False  # warmup absorbs silently
    assert base.p50 == pytest.approx(0.010)
    assert base.p99 == pytest.approx(0.010)
    th = base.threshold()
    assert th == pytest.approx(0.030)
    # exactly AT the threshold attains the baseline — NOT an outlier
    assert base.observe(th) is False
    # strictly above the (now-updated) threshold IS one
    assert base.observe(base.threshold() * 1.01) is True


def test_outlier_absorbs_at_reduced_weight():
    base = PhaseBaseline(alpha=0.05, warmup=2, outlier_mult=3.0,
                         min_wall_s=0.0)
    base.observe(0.010)
    base.observe(0.010)
    p99_before = base.p99
    assert base.observe(1.0) is True  # 100x spike
    # an outlier must not absolve the next spike: p99 moved by the
    # reduced weight (0.5 * 0.1), not the full fast-absorb 0.5
    assert base.p99 == pytest.approx(
        p99_before + 0.05 * (1.0 - p99_before)
    )
    assert base.observe(1.0) is True  # still an outlier


def test_warmup_never_flags(tmp_path):
    rec = make_recorder(
        tmp_path, baseline_kw={"warmup": 32, "min_wall_s": 0.0}
    )
    # wildly varying walls inside the warmup window: zero anomalies
    for i in range(31):
        assert rec.record("decode", 0.001 * (1 + (i % 7))) is False
    assert rec.anomalies_total == 0


def test_sustained_anomaly_arms_the_trigger(tmp_path, clock, traced):
    rec = make_recorder(
        tmp_path, clock=clock, cooldown_s=300.0, sustain=3,
        baseline_kw={"warmup": 4, "min_wall_s": 0.0, "alpha": 0.05},
    )
    for i in range(8):
        rec.record("decode", 0.001, step=i)
    # sustained spikes: outliers tick the counter, the THIRD consecutive
    # one dumps; later ones in the same run stay suppressed-free (the
    # run counter only fires at == sustain) and the rate limit holds
    for i in range(5):
        rec.record("decode", 1.0, step=100 + i)
    assert rec.anomalies_total == 5
    assert rec.dumps_total == 1
    with open(rec.last_artifact) as f:
        art = json.load(f)
    assert art["trigger"] == "anomaly"
    assert art["reason"] == "anomaly:decode"
    # the outlier digests carry the flag
    flagged = [d for d in rec.snapshot() if d["outlier"]]
    assert len(flagged) == 5
    # latency.outlier instants landed on the anomaly track
    names = {
        e["name"] for e in tracing.export()["traceEvents"]
        if e["ph"] != "M"
    }
    assert "latency.outlier" in names
    # recovery: normal walls reset the run counter
    rec.record("decode", 0.001)
    assert rec._outlier_run["decode"] == 0


def test_sync_kinds_skip_anomaly_detection(tmp_path):
    rec = make_recorder(
        tmp_path, baseline_kw={"warmup": 1, "min_wall_s": 0.0}
    )
    rec.record("sync", 0.001)
    assert rec.record("sync", 100.0) is False  # no baseline for syncs
    assert rec.anomalies_total == 0


# ------------------------------------------------------------- shed burst


def test_deadline_shed_burst_triggers_once(tmp_path, clock):
    rec = make_recorder(
        tmp_path, clock=clock, cooldown_s=300.0, shed_burst=8,
        shed_window_s=10.0,
    )
    rec.note_shed(3)
    assert rec.dumps_total == 0
    clock.t += 20.0  # the window expires the earlier sheds
    rec.note_shed(3)
    assert rec.dumps_total == 0
    rec.note_shed(5)  # 8 within the window -> burst
    assert rec.dumps_total == 1
    with open(rec.last_artifact) as f:
        assert json.load(f)["trigger"] == "deadline_shed_burst"


# ----------------------------------------------------------- SLO breach


def test_slo_breach_hook_dumps_with_request_id(tmp_path, clock):
    rec = make_recorder(tmp_path, clock=clock, cooldown_s=300.0)
    rec.record("decode", 0.001)
    slo = SloTracker({"default": {"ttft_s": 0.5}})
    slo.on_breach = rec.on_slo_breach
    slo.observe({"tenant": "default", "ttft_s": 0.1,
                 "request_id": "ok-1"})
    assert rec.dumps_total == 0  # attained: no trigger
    slo.observe({"tenant": "default", "ttft_s": 2.0,
                 "request_id": "slow-1"})
    assert rec.dumps_total == 1
    with open(rec.last_artifact) as f:
        art = json.load(f)
    assert art["trigger"] == "slo_breach"
    assert art["request_id"] == "slow-1"
    # the storm: further breaches suppress, not dump
    for i in range(20):
        slo.observe({"tenant": "default", "ttft_s": 2.0,
                     "request_id": f"slow-{i + 2}"})
    assert rec.dumps_total == 1
    assert rec.suppressed_total == 20


# ---------------------------------------------------------------- metrics


def test_prometheus_counters_zero_series_and_totals(tmp_path):
    rec = make_recorder(tmp_path)
    text = "\n".join(rec.render_prom())
    # zero-series at registration: every phase + trigger row renders
    # BEFORE any event (the check_prom contract)
    for phase in ("prefill", "decode", "spec_verify", "mixed"):
        assert (
            f'dynamo_tpu_engine_step_anomalies_total{{phase="{phase}"}} 0.0'
            in text
        )
    for trigger in flightmod.TRIGGERS:
        assert (
            f'dynamo_tpu_flight_recorder_dumps_total{{trigger="{trigger}"}}'
            in text
        )
        assert (
            "dynamo_tpu_flight_recorder_suppressed_total"
            f'{{trigger="{trigger}"}}' in text
        )


# -------------------------------------------------------- HTTP endpoints


@contextlib.asynccontextmanager
async def http_service():
    svc = HttpService()
    await svc.start("127.0.0.1", 0)
    async with aiohttp.ClientSession(
        f"http://127.0.0.1:{svc.port}"
    ) as session:
        yield svc, session
    await svc.stop()


async def test_debug_snapshot_dumps_registered_recorders(tmp_path):
    rec = make_recorder(tmp_path)
    for i in range(12):
        rec.record("decode", 0.001, rows=1, step=i)
    before = rec.dumps_total
    async with http_service() as (_svc, session):
        r = await session.get("/debug/snapshot")
        assert r.status == 200
        body = await r.json()
    assert body["recorders"] >= 1
    assert rec.dumps_total == before + 1  # force path: no rate limit
    with open(rec.last_artifact) as f:
        art = json.load(f)
    assert art["trigger"] == "manual"
    assert len(art["digests"]) == 12
    mine = [a for a in body["artifacts"]
            if a["path"] == rec.last_artifact]
    assert mine and mine[0]["digests"] == 12


async def test_debug_trace_track_filter_and_cap(traced):
    for i in range(30):
        tracing.instant("step", track="engine.steps", i=i)
    tracing.instant("other", track="engine.sync")
    async with http_service() as (_svc, session):
        r = await session.get(
            "/debug/trace", params={"track": "engine.steps", "limit": "5"}
        )
        assert r.status == 200
        body = await r.json()
        evs = [e for e in body["traceEvents"] if e["ph"] != "M"]
        assert len(evs) == 5
        assert all(e["name"] == "step" for e in evs)
        # newest win: the tail of the timeline survives the cap
        assert [e["args"]["i"] for e in evs] == list(range(25, 30))
        assert body["truncatedEvents"] == 25
        # limit=0 lifts the cap
        r = await session.get("/debug/trace", params={"limit": "0"})
        assert len([e for e in (await r.json())["traceEvents"]
                    if e["ph"] != "M"]) == 31
        r = await session.get("/debug/trace", params={"limit": "bogus"})
        assert r.status == 400


# ------------------------------------------------------------- profiler


class _StubJprof:
    """Deterministic jax.profiler stand-in: records start/stop calls."""

    def __init__(self, fail_start=False):
        self.calls = []
        self.fail_start = fail_start

    def start_trace(self, logdir):
        if self.fail_start:
            raise RuntimeError("no profiler backend")
        self.calls.append(("start", logdir))

    def stop_trace(self):
        self.calls.append(("stop",))

    def TraceAnnotation(self, name):  # noqa: N802 — jax API shape
        return contextlib.nullcontext()

    def StepTraceAnnotation(self, name, **kw):  # noqa: N802
        return contextlib.nullcontext()


@pytest.fixture
def stub_profiler(monkeypatch, tmp_path):
    stub = _StubJprof()
    monkeypatch.setattr(profiler, "_jprof", stub)
    monkeypatch.setattr(profiler, "_active_dir", None)
    monkeypatch.setenv("DYN_PROFILE_DIR", str(tmp_path / "prof"))
    monkeypatch.delenv("DYN_PROFILE", raising=False)
    return stub


async def test_debug_profile_capture_and_gate(stub_profiler):
    async with http_service() as (_svc, session):
        # in-flight capture holds the single-capture gate
        t1 = asyncio.create_task(
            session.post("/debug/profile", params={"duration_ms": "400"})
        )
        await asyncio.sleep(0.1)
        assert profiler.active() is not None
        r2 = await session.post(
            "/debug/profile", params={"duration_ms": "10"}
        )
        assert r2.status == 409
        r1 = await t1
        assert r1.status == 200
        body = await r1.json()
        assert body["dir"].startswith(profiler.profile_dir())
        assert body["duration_ms"] >= 400
    # exactly one start/stop pair despite the concurrent attempt
    assert [c[0] for c in stub_profiler.calls] == ["start", "stop"]
    assert profiler.active() is None


async def test_debug_profile_rejects_bad_duration(stub_profiler):
    async with http_service() as (_svc, session):
        r = await session.post(
            "/debug/profile", params={"duration_ms": "soon"}
        )
        assert r.status == 400


async def test_debug_profile_noop_path(monkeypatch):
    # DYN_PROFILE=0 (or a missing jax.profiler) answers a clean 501 —
    # the capture endpoint must never 500 on a CPU-only or disabled rig
    monkeypatch.setenv("DYN_PROFILE", "0")
    assert profiler.available() is False
    async with http_service() as (_svc, session):
        r = await session.post(
            "/debug/profile", params={"duration_ms": "10"}
        )
        assert r.status == 501


def test_profiler_gate_direct(stub_profiler):
    d = profiler.start()
    with pytest.raises(profiler.ProfilerBusy):
        profiler.start()
    info = profiler.stop()
    assert info["dir"] == d
    with pytest.raises(profiler.ProfilerUnavailable):
        profiler.stop()  # nothing in flight
    # a failing backend surfaces as unavailable AND releases the gate
    stub_profiler.fail_start = True
    with pytest.raises(profiler.ProfilerUnavailable):
        profiler.start()
    assert profiler.active() is None


def test_annotations_are_noop_safe(monkeypatch):
    # with jax.profiler absent the annotations are shared no-op CMs —
    # the dispatch hot path must not pay for a missing profiler
    monkeypatch.setattr(profiler, "_jprof", None)
    with profiler.annotate("decode"):
        with profiler.step_annotation(7):
            pass
    assert profiler.available() is False
