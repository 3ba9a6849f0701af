"""The benchmark's reader of the program's host phases and operation
scopes (benchmark/lib/trace_host.py) and the per-layer metrics PR 24
added: the idle split on a hand-made trace and on a recording cut from a
chip run of `mistral-7b-int8.decode-saturate` (TPU v5e, PR 24), and a
rehearsal that prints the new metrics and leaves the old ones as they
were. Since PR 38 also the readers of the host's always-on clock
(benchmark/lib/host_clock.py): the tick, its stops and set-up's split."""

from __future__ import annotations

import copy
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LIB = os.path.join(ROOT, "benchmark", "lib")
DATA = os.path.join(ROOT, "benchmark", "tests", "data",
                    "v5e_int8_dsat_220ms.json.gz")
KINDS = ("in_programs", "enqueue", "host", "frontend", "dry")


@pytest.fixture(scope="module")
def libs():
    sys.path.insert(0, LIB)
    try:
        import harness
        import trace_host
        import trace_reduce
        yield trace_host, trace_reduce, harness
    finally:
        sys.path.remove(LIB)


def _table(loop, worker, ops=None, mods=None):
    ops = ops or [["%fusion.1", 0, 40, {"scope": "mlp.down"}],
                  ["%copy-done.2", 50, 50, {}],
                  ["%fused_attn.3", 200, 100, {"scope": "attn.kernel"}]]
    mods = mods or [["jit__decode_multi(7)", 0, 100, {}],
                    ["jit__decode_multi(7)", 200, 100, {}]]
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": mods},
            {"name": "XLA Ops", "events": ops}]},
        {"name": "/host:CPU", "lines": [
            {"name": "python", "events": worker},
            {"name": "python", "events": loop}]}]}


def test_idle_is_split_by_overlap_in_priority_order(libs):
    th, tr, _ = libs
    worker = [["decode", 90, 70, {}], ["eng.lock", 90, 10, {}],
              ["eng.upload", 100, 20, {}],
              ["eng.enqueue", 120, 30, {}]]
    loop = [["eng.emit", 140, 30, {}], ["fe.stream", 165, 15, {}],
            ["eng.fetch", 180, 150, {}],
            ["eng.wait", 360, 40, {}]]
    table = _table(loop, worker)
    got = th.split(table)
    ns = {k: round(got[k] * 1e9) for k in KINDS}
    # module 1 holds a 10 ns hole; [100, 200) and [300, 400) lie between
    # programs: enqueue 100-150, emit to 170, fe.stream to 180, fetch to
    # 330, nothing open on the loop's thread to 360, eng.wait to 400
    assert ns == {"in_programs": 10, "enqueue": 50, "host": 20,
                  "frontend": 40, "dry": 90}
    reduced = tr.reduce(table)
    idle = reduced["window_s"] - reduced["busy_s"]
    assert sum(got[k] for k in KINDS) == pytest.approx(idle, rel=1e-12)
    assert got["window"] == pytest.approx(reduced["window_s"])
    assert got["busy"] == pytest.approx(reduced["busy_s"])


def test_before_the_first_recorded_phase_bare_time_is_dry(libs):
    th, _, _ = libs
    # the fetch that was open when the capture began is not recorded:
    # the loop's first phase opens at 170
    loop = [["eng.emit", 170, 10, {}], ["eng.wait", 390, 10, {}]]
    got = th.split(_table(loop, []))
    ns = {k: round(got[k] * 1e9) for k in KINDS}
    assert ns == {"in_programs": 10, "enqueue": 0, "host": 10,
                  "frontend": 20 + 90, "dry": 70 + 10}


def test_a_dispatch_open_when_the_capture_began_counts_as_enqueue(libs):
    th, _, _ = libs
    # the worker's first recorded event is a jit call with no dispatch
    # annotation before it: its annotation, eng.lock and eng.upload opened
    # before the capture and are not in it
    loop = [["eng.fetch", 100, 250, {}], ["eng.wait", 360, 40, {}]]
    worker = [["eng.enqueue", 150, 20, {}], ["decode", 320, 60, {}],
              ["eng.lock", 320, 10, {}], ["eng.upload", 330, 20, {}]]
    got = th.split(_table(loop, worker))
    ns = {k: round(got[k] * 1e9) for k in KINDS}
    # [100, 170): the unrecorded upload and the jit call; then the fetch
    # alone to 200; [300, 320) fetch, lock + upload to 350, bare to 360,
    # eng.wait to 400
    assert ns == {"in_programs": 10, "enqueue": 70 + 30, "host": 0,
                  "frontend": 10, "dry": 30 + 20 + 40}
    # a worker whose first event opens a dispatch was idle before it
    worker = [["decode", 320, 60, {}], ["eng.lock", 320, 10, {}],
              ["eng.upload", 330, 20, {}]]
    ns = {k: round(v * 1e9) for k, v in th.split(_table(loop, worker)).items()
          if k in KINDS}
    assert ns["enqueue"] == 30 and ns["dry"] == 100 + 20 + 40


def test_without_phases_only_the_device_side_is_known(libs):
    th, _, _ = libs
    got = th.split(_table([["decode", 90, 70, {}]], []))
    assert got["in_programs"] == pytest.approx(10e-9)
    assert got["between"] == pytest.approx(100e-9)
    assert [got[k] for k in KINDS[1:]] == [None] * 4
    assert th.split({"planes": [{"name": "/host:CPU", "lines": []}]}) is None


def test_scope_times_are_self_times_per_program(libs):
    th, _, _ = libs
    ops = [["%while.1", 0, 100, {}],
           ["%fusion.1", 0, 40, {"scope": "mlp.down"}],
           ["%copy-done.2", 50, 50, {"scope": "attn.qkv"}],
           ["%fused_attn.3", 200, 100, {"scope": "attn.kernel"}]]
    got = th.scope_times(_table([], [], ops=ops))
    assert got["programs"] == {"jit__decode_multi": 2}
    t = {k: round(v * 1e9) for k, v in got["times"]["jit__decode_multi"].items()}
    assert t == {"": 10, "mlp.down": 40, "attn.qkv": 50, "attn.kernel": 100}
    assert th.scope_of("jit(_decode_multi)/while/body/attn.o/dot_general:") \
        == "attn.o"
    assert th.scope_of("jit(f)/norm/attn.rope/mul") == "attn.rope"
    assert th.scope_of("jit(f)/transpose") == ""


def test_interval_arithmetic(libs):
    th, _, _ = libs
    a = th.union([(5, 9), (0, 3), (2, 4), (9, 12), (20, 20)])
    assert a == [[0, 4], [5, 12]]
    assert th.intersect(a, [[3, 6], [11, 30]]) == [[3, 4], [5, 6], [11, 12]]
    assert th.subtract(a, [[1, 2], [3, 7], [12, 13]]) == [
        [0, 1], [2, 3], [7, 12]]
    assert th.total(a) == 11


def test_wire_reader_finds_the_scope_a_profile_hides(libs, tmp_path):
    """An xplane written here on the CPU backend: `_fields` walks the
    file's planes, and a jitted function's named scope is read from the
    event metadata where the backend writes one (the TPU's does, PR 24's
    chip runs; the CPU's carries none and the table is then empty)."""
    import glob

    import jax
    import jax.numpy as jnp

    th, _, _ = libs

    @jax.jit
    def f(x):
        with jax.named_scope("mlp.down"):
            return x @ x

    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("eng.tick"):
        f(x).block_until_ready()
    jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))[0]
    with open(path, "rb") as fh:
        planes = [v for k, v in th._fields(memoryview(fh.read())) if k == 1]
    names = {bytes(v).decode() for p in planes
             for k, v in th._fields(p) if k == 2}
    assert "/host:CPU" in names
    # no operation with a scope on the CPU (a process that has loaded
    # libtpu writes an empty `/device:CUSTOM` plane of its own)
    assert not any(th._op_scopes(path).values())
    table = th.load(path)
    ticks = [e for p in table["planes"] for ln in p["lines"]
             for e in ln["events"] if e[0] == "eng.tick"]
    assert ticks and ticks[0][3] == {}
    lo, hi = table["span"]
    assert lo <= ticks[0][1] and ticks[0][1] + ticks[0][2] <= hi
    assert th.split(table) is None  # nothing ran on a device


@pytest.fixture(scope="module")
def recording(libs):
    th, tr, _ = libs
    return th.load(DATA), tr.reduce(tr.load(DATA))


def test_recording_shares_sum_to_the_reducers_idle(libs, recording):
    th, _, _ = libs
    table, reduced = recording
    got = th.split(table)
    idle = reduced["window_s"] - reduced["busy_s"]
    assert all(got[k] is not None and got[k] >= 0 for k in KINDS)
    assert sum(got[k] for k in KINDS) == pytest.approx(idle, rel=1e-9)
    assert got["window"] == pytest.approx(reduced["window_s"], rel=1e-12)
    # what this recording holds (PERF.md section 5): the chip waits
    # between programs, not inside them, while a dispatch worker issues
    # the small eager operations around the decode program
    assert got["in_programs"] / idle < 0.01
    assert got["enqueue"] / idle > 0.9
    assert idle / got["window"] == pytest.approx(0.2381, abs=1e-4)


def test_recording_scopes_cover_the_decode_program(libs, recording):
    th, tr, _ = libs
    table, reduced = recording
    got = th.scope_times(table)
    dec = got["times"]["jit__decode_multi"]
    assert got["programs"]["jit__decode_multi"] == \
        reduced["programs"]["jit__decode_multi"]["count"]
    # the same self times as the accepted reducer's, by scope
    inside = reduced["ops_in_program"]["jit__decode_multi"]
    assert sum(dec.values()) == pytest.approx(
        sum(v["self_s"] for v in inside.values()), rel=1e-9)
    kernel = sum(v["self_s"] for n, v in inside.items()
                 if tr.op_family(n) == "fused_paged_decode_attention")
    assert dec["attn.kernel"] >= kernel
    # unnamed: the scan's own copies of the KV scale pools (`*-done`)
    named = sum(v for k, v in dec.items() if k)
    assert 0.75 < named / sum(dec.values()) < 0.9


# ------------------------------------------- the decode kernel's page count


def _digest(kind, streamed=None, held=None):
    d = {"kind": kind, "rows": 4, "tokens": 32}
    if streamed is not None:
        d.update(kv_pages_streamed=streamed, kv_pages_held=held)
    return d


@pytest.mark.parametrize("digests,want", [
    # sums over the window's decode rows, not a mean of ratios
    ([_digest("decode", 1356, 1164), _digest("decode", 300, 300),
      _digest("prefill", 0, 0), _digest("sync", 0, 0)], 1656 / 1464),
    ([_digest("decode", 512, 512), _digest("mixed", 0, 0)], 1.0),
    # the gather path books 0 pages: nothing to read
    ([_digest("decode", 0, 0), _digest("prefill", 0, 0)], None),
    # a program from before the columns: nothing to read, nothing raised
    ([_digest("decode"), _digest("prefill"), _digest("sync")], None),
    ([], None),
])
def test_decode_kv_read_amp(libs, digests, want):
    _, _, harness = libs
    got = harness.read_metric(
        "layer_metrics", "decode_kv_read_amp", {"digests": digests})
    assert got == (want if want is None else pytest.approx(want, rel=1e-12))


def test_decode_kv_read_amp_is_declared():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    m = next(m for m in bench["per_layer"]
             if m["name"] == "decode_kv_read_amp")
    assert m == {
        "name": "decode_kv_read_amp", "unit": "x", "better": "lower",
        "source": "program_counter", "layer": "kernels",
        "moves": "tpot_p95_ms",
        # every cell whose decode dispatch is the one-token scan, which
        # books the page counts the reader sums: a configuration whose
        # file states a `block_length` is generated by diffusion over
        # blocks, and its block dispatch (digest kind `dlm`) books none
        "workloads": [w["name"] for w in bench["workloads"]
                      if "block_length" not in _config_file(w["config"])]}


def _config_file(name):
    with open(os.path.join(ROOT, "benchmark", "configs", name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("digests,want", [
    # rows x steps over a window layer's work items, summed over the
    # window's decode rows: 186 rows in items of 4, the last one partial
    ([dict(_digest("decode"), tokens=186 * 8, kv_win_items=47 * 8),
      dict(_digest("decode"), tokens=32, kv_win_items=8),
      dict(_digest("sync"), tokens=900)], (186 * 8 + 32) / (47 * 8 + 8)),
    # a uniform model books 0, the parent no such column: nothing to read
    ([dict(_digest("decode"), kv_win_items=0)], None),
    ([_digest("decode"), _digest("prefill")], None),
    ([], None),
])
def test_swa_rows_per_item(libs, digests, want):
    _, _, harness = libs
    got = harness.read_metric(
        "layer_metrics", "swa_rows_per_item", {"digests": digests})
    assert got == (want if want is None else pytest.approx(want, rel=1e-12))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    m = next(m for m in bench["per_layer"]
             if m["name"] == "swa_rows_per_item")
    assert m == {
        "name": "swa_rows_per_item", "unit": "rows", "better": "higher",
        "source": "program_counter", "layer": "kernels",
        "moves": "tpot_p95_ms",
        "workloads": ["mimo-v2-flash-l7.reason-wide"]}


# ------------------------------------------------------------- a rehearsal

NEW_COUNTS = ("kv_preemptions", "true_compiles_in_window")
NEW_TIMES = ("host_ms_per_tick", "starved_dispatch_pct",
             "prefill_span_p95_ms")
NEW_TRACE = ("idle_in_programs_pct", "idle_enqueue_pct", "idle_host_pct",
             "idle_frontend_pct", "idle_dry_pct", "decode_attn_block_ms",
             "decode_mlp_ms", "ops_scoped_pct")
NEW_COLUMNS = ("build_s", "emit_s", "starved", "preempted")


@pytest.fixture(scope="module")
def rehearsal():
    cell = "mistral-7b-int8.chat-steady"
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run_cell.py"),
         "--workload", cell, "--seed", "2147483701", "--seconds", "5",
         "--trace", "1", "--rehearse", "--keep"],
        cwd=ROOT, env={**os.environ, "PYTHONPATH": ROOT},
        capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-2000:]
    with open(os.path.join(ROOT, ".bench_work", cell, "artefacts.json")) as f:
        art = json.load(f)
    return json.loads(p.stdout.strip().splitlines()[-1]), art


def test_rehearsal_prints_the_new_metrics(rehearsal):
    line, _ = rehearsal
    got = line["metrics"]
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        declared = {m["name"]: m for m in json.load(f)["per_layer"]}
    assert set(NEW_COUNTS + NEW_TIMES + NEW_TRACE) <= set(declared)
    for name in NEW_COUNTS:  # a CPU run gives counts ...
        assert isinstance(got[name]["value"], int), name
        assert got[name]["unit"] == "count"
    for name in NEW_TIMES:  # ... and no time, rate or share
        assert name in got and got[name]["value"] is None, name
    # the CPU backend writes no device plane: nothing for these to read
    assert not set(NEW_TRACE) & set(got)
    assert got["kv_preemptions"]["value"] == got["preemptions"]["value"]
    assert (got["true_compiles_in_window"]["value"]
            <= got["compiles_in_window"]["value"])


def test_old_metrics_do_not_see_the_new_columns(libs, rehearsal):
    _, _, harness = libs
    _, art = rehearsal
    assert set(NEW_COLUMNS) <= set(art["digests"][0])
    old = copy.deepcopy(art)
    for d in old["digests"]:
        for k in NEW_COLUMNS:
            del d[k]
    for s in old["summaries"].values():
        for k in ("prefill_s", "first_emit_s", "prefill_chunks"):
            del s[k]
    for stats in old["compile"].values():
        if isinstance(stats, dict):
            for k in ("backend_compiles", "cache_read_s"):
                del stats[k]
    for name in ("ttft_p50_ms", "ttft_p95_ms", "gen_lag_p99_ms",
                 "frontend_p50_ms", "queue_wait_p95_ms", "decode_rows_mean",
                 "compiles_in_window", "kv_pool_peak_pct", "preemptions",
                 "device_idle_pct", "decode_step_ms", "prefill_dev_tok_s"):
        assert (harness.read_metric("layer_metrics", name, old)
                == harness.read_metric("layer_metrics", name, art)), name
    # and the new readers leave a line from before PR 24 alone
    for name in NEW_COUNTS + NEW_TIMES:
        assert harness.read_metric("layer_metrics", name, old) is None, name


# ------------------------------------- the host's clock in an untraced run

TICK_METRICS = ("tick_p50_ms", "tick_max_ms", "stall_s", "tick_max_fetch_ms",
                "tick_max_dispatch_ms", "tick_max_host_ms",
                "tick_max_unphased_ms")
SETUP_METRICS = ("setup_build_s", "setup_probe_s", "setup_warm_s",
                 "setup_trace_lower_s", "setup_cache_read_s",
                 "setup_weights_s")
CLOCK_COLUMNS = ("lock_s", "upload_s", "enqueue_s", "tick_s", "admit_s",
                 "join_s", "unphased_s")
CLOCK_KEYS = ("trace_s", "lower_s", "phase_s", "at_s")


@pytest.mark.parametrize("name", TICK_METRICS + SETUP_METRICS)
def test_host_clock_metric_is_declared(name):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    m = next(m for m in bench["per_layer"] if m["name"] == name)
    setup = name in SETUP_METRICS
    assert m == {
        "name": name, "unit": "ms" if name.endswith("_ms") else "s",
        "better": "lower", "source": "program_span",
        "layer": "set-up" if setup else "engine loop, scheduler",
        "moves": "setup_s" if setup else "out_tok_s",
        "workloads": [w["name"] for w in bench["workloads"]]}
    assert os.path.isfile(os.path.join(
        ROOT, "benchmark", "layer_metrics", name + ".py"))


def _tick(tick_s, wall_s=0.14, join_s=0.0, unphased_s=0.002, emit_s=0.004):
    return {"kind": "sync", "tick_s": tick_s, "wall_s": wall_s,
            "join_s": join_s, "unphased_s": unphased_s, "emit_s": emit_s,
            "admit_s": 0.001, "gc_s": 0.0}


def _launch(enqueue_s=0.001, kind="decode"):
    return {"kind": kind, "lock_s": 0.0, "upload_s": 0.001,
            "enqueue_s": enqueue_s, "build_s": 0.002}


@pytest.mark.parametrize("ticks,want", [
    # a steady window: no tick passes twice the median
    ([0.150, 0.148, 0.152, 0.170, 0.150, 0.290], 0.0),
    # one stop: what it took beyond the median tick
    ([0.150] * 9 + [2.650], 2.5),
    # two: their sum, each less the median
    ([0.150] * 9 + [2.650, 0.150, 3.150], 5.5),
    # the first landing (and the first after an idle loop) opens no tick
    ([0.0, 0.150, 0.150, 0.0, 0.150, 0.450], 0.3),
])
def test_stall_s_sums_what_stops_took_beyond_the_median(libs, ticks, want):
    _, _, harness = libs
    digests = [d for t in ticks for d in (_launch(), _tick(t))]
    got = harness.read_metric("layer_metrics", "stall_s", {"digests": digests})
    assert got == pytest.approx(want, abs=1e-9)
    assert harness.read_metric(
        "layer_metrics", "tick_max_ms", {"digests": digests}
    ) == pytest.approx(max(ticks) * 1e3)


@pytest.mark.parametrize("stop,part", [
    # the loop awaited the device's tokens 3 s longer
    (dict(tick=_tick(3.15, wall_s=3.14)), "fetch"),
    # a worker sat 3 s in its launch, the loop 3 s awaiting the worker
    (dict(tick=_tick(3.15, join_s=3.0), launch=_launch(3.0)), "dispatch"),
    # the prefill worker alone (its row lies in the tick it stopped)
    (dict(tick=_tick(3.15, join_s=3.0), launch=_launch(3.0, "prefill")),
     "dispatch"),
    # the loop's own landing took 3 s
    (dict(tick=_tick(3.15, emit_s=3.004)), "host"),
    # the loop's thread was in no phase of the engine's for 3 s
    (dict(tick=_tick(3.15, unphased_s=3.002)), "unphased"),
])
def test_the_longest_ticks_excess_names_its_part(libs, stop, part):
    _, _, harness = libs
    steady = [d for _ in range(8) for d in (_launch(), _tick(0.15))]
    digests = steady + [stop.get("launch", _launch()), stop["tick"]] + steady
    got = {p: harness.read_metric(
        "layer_metrics", f"tick_max_{p}_ms", {"digests": digests})
        for p in ("fetch", "dispatch", "host", "unphased")}
    assert got.pop(part) == pytest.approx(3000, abs=1)
    assert all(abs(v) < 1 for v in got.values()), got
    # a steady window reads ~0 in all four
    for p in got:
        assert harness.read_metric(
            "layer_metrics", f"tick_max_{p}_ms", {"digests": steady}) == 0


def test_rehearsal_prints_the_host_clock_metrics(libs, rehearsal):
    _, _, harness = libs
    line, art = rehearsal
    # declared in every cell, so printed by this one; a CPU run gives
    # counts and never a time, so each value is null here
    for name in TICK_METRICS + SETUP_METRICS:
        assert line["metrics"][name]["value"] is None, name
    read = {n: harness.read_metric("layer_metrics", n, art)
            for n in TICK_METRICS + SETUP_METRICS}
    assert all(v is not None for v in read.values()), read
    # the three stretches of set-up are set-up, to the clock's last digit
    assert (read["setup_build_s"] + read["setup_probe_s"]
            + read["setup_warm_s"]) == pytest.approx(art["setup_s"], abs=1e-6)
    assert min(read[n] for n in SETUP_METRICS[:3]) > 0
    before = art["compile"]["before"]
    assert read["setup_trace_lower_s"] == pytest.approx(
        before["trace_s"] + before["lower_s"]) and before["trace_s"] > 0
    assert read["setup_weights_s"] == before["phase_s"]["eng.init.weights"] > 0
    assert read["setup_weights_s"] < read["setup_build_s"]
    assert 0 < read["tick_p50_ms"] <= read["tick_max_ms"]
    assert read["stall_s"] >= 0
    # the four snapshots are stamped in the order they were taken
    stamps = [art["compile"][k]["at_s"]
              for k in ("build", "probe", "before", "after")]
    assert stamps == sorted(stamps) and stamps[2] <= art["window"][0] + 1


def test_older_readers_do_not_see_the_host_clock(libs, rehearsal):
    _, _, harness = libs
    _, art = rehearsal
    assert set(CLOCK_COLUMNS) <= set(art["digests"][0])
    old = copy.deepcopy(art)
    for d in old["digests"]:
        for k in CLOCK_COLUMNS:
            del d[k]
    for stats in old["compile"].values():
        if isinstance(stats, dict):
            for k in CLOCK_KEYS:
                del stats[k]
    for name in ("host_ms_per_tick", "host_stall_max_ms",
                 "starved_dispatch_pct", "device_idle_pct",
                 "idle_in_programs_pct", "idle_enqueue_pct", "idle_host_pct",
                 "idle_frontend_pct", "idle_dry_pct", "decode_rows_mean",
                 "compiles_in_window", "true_compiles_in_window",
                 "kv_pool_peak_pct", "decode_kv_read_amp"):
        assert (harness.read_metric("layer_metrics", name, old)
                == harness.read_metric("layer_metrics", name, art)), name
    # and a program from before PR 38 gives the new readers nothing to
    # read (`cache_read_s` is older than they are)
    for name in TICK_METRICS + SETUP_METRICS:
        got = harness.read_metric("layer_metrics", name, old)
        assert (got is None) == (name != "setup_cache_read_s"), name
