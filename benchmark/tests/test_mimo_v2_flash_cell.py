"""The `mimo-v2-flash-l7.reason-wide` cell on the CPU: a rehearsal of a
whole run (the family's own tiny model, judged by
`references/mimo_v2_flash.py`), the four readers this cell brought on a
hand-made `art` (a known share reads that share, whole and cut; nothing to
read reads `None`), and the five controls at the rehearsal's size."""
import importlib.util
import os

import numpy as np
import pytest

import harness
import trace_host
import trace_reduce as tr
from conftest import BENCH, ROOT, run_cell

CELL = "mimo-v2-flash-l7.reason-wide"
NEW = ("hybrid_decode_attn_roofline", "hybrid_decode_step_roofline",
       "kv_window_held_ratio", "moe_held_load_max_over_mean")


def test_rehearsal_prints_one_whole_line(bench_json):
    # the seed is one whose toy model (top-2 of 8 experts) has no pair at
    # the selection threshold on the 64 judged positions: where bf16 and
    # float32 pick another expert there, one position reads ~1 and the
    # file's limits, set for top-8 of 256, refuse it (2147483659 and
    # 3600000001 read so with the bias at N(0, 0.02))
    rc, line, err = run_cell(ROOT, "--workload", CELL, "--seed", "2147483693",
                             "--seconds", "20", "--trace", "1", "--rehearse",
                             timeout=1500)
    assert rc == 0, err[-2000:]
    assert line["check"]["ok"] is True, line["check"]
    assert line["check"]["positions"] == 64
    assert line["device"]["platform"] == "cpu"
    got = line["metrics"]
    want = {m["name"] for m in bench_json["per_layer"]
            if CELL in m.get("workloads", [CELL])}
    assert set(got) <= want
    assert got["decode_rows_mean"]["value"] > 8
    assert got["preemptions"]["value"] == 0
    assert got["kv_preemptions"]["value"] == 0
    # shares and ratios are no counts: the CPU run names them, no value
    for name, unit in (("kv_window_held_ratio", "x"),
                       ("moe_held_load_max_over_mean", "x")):
        assert got[name] == {"value": None, "unit": unit}
    assert not [n for n in line["compiled_in_window"]
                if "_model_step" in n or "_decode_multi" in n]


# ------------------------------------------------------- the four readers

STEPS, PEAK = 8, 819e9
EXEC_NS = 96_000_000           # one decode execution: 8 steps of 12 ms
SHARES = {"attn.kernel": 0.10, "attn.swa_kernel": 0.15}


def _table(executions: float, scopes=SHARES) -> dict:
    """A device plane of `executions` decode executions (the last one cut
    where the count is fractional): a full-layer kernel operation, a
    window-layer one, and the expert matmuls for the rest."""
    ops, mods, t = [], [], 0
    whole = int(executions)
    for i in range(whole + (executions > whole)):
        part = 1.0 if i < whole else executions - whole
        mods.append(["jit__decode_multi(7)", t, EXEC_NS, {}])
        at, rest = t, 1.0
        for scope, share in scopes.items():
            ns = int(EXEC_NS * share * part)
            ops.append([f"%k.{i}", at, ns, {"scope": scope}])
            at, rest = at + ns, rest - share
        ops.append([f"%experts.{i}", at, int(EXEC_NS * rest * part),
                    {"scope": "mlp.moe_experts"}])
        t += EXEC_NS
    return {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": tr.OPS_LINE, "events": ops},
        {"name": tr.MODULES_LINE, "events": mods}]}]}


def _art(bench_json, table, hit=16.0):
    config = harness.load_json(
        ROOT, harness.find(bench_json["configs"], "mimo-v2-flash-l7",
                           "configuration")["file"])
    return {
        "cell": {"name": CELL}, "config": config,
        "engine": {"decode_steps": STEPS},
        "peaks": {"hbm_bytes_per_s": PEAK},
        "trace": {**tr.reduce(table), "slice": [10.0, 10.5]},
        # two requests decoding all through the slice: one of 1,000 tokens
        # of context at its middle, one of 100 (under the window)
        "requests": [
            {"t_first": 0.0, "t_last": 20.5, "prompt_tokens": 500.0,
             "tokens": 1000},
            {"t_first": 0.0, "t_last": 20.5, "prompt_tokens": 90.0,
             "tokens": 20}],
        "digests": [
            {"kind": "decode", "rows": 192, "kv_pages_held_full": 1600,
             "kv_win_pages_held": 400, "moe_experts_hit": 0.0,
             "moe_load_max": 0.0},
            {"kind": "overlap", "rows": 192, "moe_experts_hit": hit,
             "moe_load_max": 15.0},
        ],
    }


@pytest.mark.parametrize("executions", [3.0, 2.4], ids=["whole", "cut"])
def test_new_readers_read_the_known_share(bench_json, monkeypatch, executions):
    import shapes_hybrid as sh

    table = _table(executions)
    monkeypatch.setattr(trace_host, "scopes",
                        lambda art: trace_host.scope_times(table))
    art = _art(bench_json, table)
    hf = {k: v for k, v in art["config"].items() if k != "benchmark"}
    # the issue's bytes a token a layer, and the layers of each kind as run
    assert sh.kv_bytes_per_token(hf, sh.FULL) == 2_560
    assert sh.kv_bytes_per_token(hf, sh.WINDOW) == 5_120
    assert (sh.layers_of(hf, sh.FULL), sh.layers_of(hf, sh.WINDOW)) == (2, 5)
    full, win = sh.resident(art)
    assert full == pytest.approx(1000.0 + 100.0, rel=1e-3)
    assert win == pytest.approx(128.0 + 100.0, rel=1e-3)
    kv = full * 2_560 * 2 + win * 5_120 * 5
    assert sh.decode_kv_bytes(hf, full, win) == pytest.approx(kv)
    step_s = EXEC_NS / 1e9 / STEPS
    got = harness.read_metric("layer_metrics", NEW[0], art)
    assert got == pytest.approx(kv / PEAK / (step_s * 0.25) * 100, rel=1e-6)
    weights = sh.decode_weight_bytes(hf, 16.0)
    # the issue's count: 6.70 GB a step with all 16 held experts hit,
    # 6.86 GB held with the embedding (read a row a token: left out)
    assert weights == pytest.approx(6.70e9, rel=0.01)
    assert weights + 19072 * 4096 * 2 == pytest.approx(6.86e9, rel=0.005)
    got = harness.read_metric("layer_metrics", NEW[1], art)
    assert got == pytest.approx((weights + kv) / PEAK / step_s * 100,
                                rel=1e-6)
    assert harness.read_metric("layer_metrics", NEW[2], art) == 0.25
    # 15 on the fullest held expert over 192 rows x 8 / 256 = 6 on average
    assert harness.read_metric(
        "layer_metrics", NEW[3], art) == pytest.approx(2.5)
    # fewer held experts hit need fewer bytes
    assert weights - sh.decode_weight_bytes(hf, 10.0) == (
        6 * 6 * sh.expert_params(hf) * 2)
    assert sh.expert_params(hf) == 3 * 4096 * 2048


def test_new_readers_find_nothing_in_a_program_without_the_mechanism(
        bench_json, monkeypatch):
    """The parent commit's program has no `attn.swa_kernel` scope, no
    window-pool digest column and no router width: each new reader returns
    None, none raises. Without a trace the two rooflines do the same."""
    table = _table(3.0, scopes={"attn.kernel": 0.25})
    monkeypatch.setattr(trace_host, "scopes",
                        lambda art: trace_host.scope_times(table))
    art = _art(bench_json, table)
    art["digests"] = [{"kind": "decode", "rows": 64,
                       "kv_pages_held": 512, "kv_pages_streamed": 512},
                      {"kind": "overlap", "rows": 64,
                       "moe_experts_hit": 60.0, "moe_load_max": 20.0}]
    art["config"] = {k: v for k, v in art["config"].items()
                     if k != "router_width"}
    for name in NEW:
        assert harness.read_metric("layer_metrics", name, art) is None, name
    art = _art(bench_json, _table(3.0))
    art["trace"] = None
    for name in NEW[:2]:
        assert harness.read_metric("layer_metrics", name, art) is None


# ------------------------------------------------------------ the controls


@pytest.fixture(scope="module")
def control_readings():
    path = os.path.join(BENCH, "controls", "mimo_v2_flash.py")
    spec = importlib.util.spec_from_file_location("control_mimo", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.readings("mimo-v2-flash-l7", [1], rehearse=True,
                        controls=("float32",) + mod.CONTROLS)


@pytest.mark.parametrize("control", [
    "float32", "no_window", "no_sink", "no_value_scale", "no_selection_bias",
    "int8_weights"])
def test_control_reads_a_gap(control_readings, control):
    """Nothing changed reads exactly 0 over the 64 judged positions; each
    control reads a finite gap above it, and each mechanism taken away
    reads more than int8 weights do."""
    got = control_readings[control]["1"]
    assert got["positions"] == 64 and np.isfinite(got["gap_max"])
    if control == "float32":
        assert got["gap_max"] == 0.0 and got["ok"]
        return
    assert 0.0 < got["gap_mean"] <= got["gap_max"]
    if control != "int8_weights":
        assert got["gap_mean"] > control_readings["int8_weights"]["1"][
            "gap_mean"]
