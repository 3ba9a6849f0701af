"""The `deepseek-v2-lite-l9.decode-wide` cell on the CPU: a rehearsal of a
whole run (the family's own tiny model, judged by `references/deepseek_v2.py`),
and the two roofline readers this cell brought, on a hand-made `art` (a
known share reads that share; a slice that cuts the last execution reads
the same)."""
import pytest

import harness
import trace_host
import trace_reduce as tr
from conftest import ROOT, run_cell

CELL = "deepseek-v2-lite-l9.decode-wide"


def test_rehearsal_prints_one_whole_line(bench_json):
    rc, line, err = run_cell(ROOT, "--workload", CELL, "--seed", "2147483659",
                             "--seconds", "5", "--trace", "1", "--rehearse")
    assert rc == 0, err[-2000:]
    assert line["correct"] is True, line["check"]
    assert line["check"]["positions"] == 64
    assert line["attempted"] > 0 and line["device"]["platform"] == "cpu"
    got = line["metrics"]
    want = {m["name"] for m in bench_json["per_layer"]
            if CELL in m.get("workloads", [CELL])}
    assert set(got) <= want
    # counts are printed; the expert load's units are no counts, so the CPU
    # run names them and prints no value
    assert got["decode_rows_mean"]["value"] > 8
    assert got["compiles_in_window"]["value"] <= 2
    assert got["preemptions"]["value"] == 0
    for name in ("moe_experts_hit_mean", "moe_load_max_over_mean"):
        assert got[name] == {"value": None, "unit": {
            "moe_experts_hit_mean": "experts",
            "moe_load_max_over_mean": "x"}[name]}
    assert not [n for n in line["compiled_in_window"]
                if "_model_step" in n or "_decode_multi" in n]


# ------------------------------------------------------- the two rooflines

STEPS, PEAK = 8, 819e9
EXEC_NS = 160_000_000          # one decode execution: 8 steps of 20 ms
KERNEL_SHARE = 0.25            # of its self time under attn.mla_kernel


def _table(executions: float) -> dict:
    """A device plane of `executions` decode executions (the last one cut
    where the share is fractional), each an `attn.mla_kernel` operation of
    KERNEL_SHARE of it and an `mlp.moe_experts` operation of the rest."""
    ops, mods, t = [], [], 0
    whole = int(executions)
    for i in range(whole + (executions > whole)):
        part = 1.0 if i < whole else executions - whole
        mods.append(["jit__decode_multi(7)", t, EXEC_NS, {}])
        # the cut execution keeps the operations that started in the slice
        k = int(EXEC_NS * KERNEL_SHARE * part)
        rest = int(EXEC_NS * (1 - KERNEL_SHARE) * part)
        ops.append([f"%mla.{i}", t, k, {"scope": "attn.mla_kernel"}])
        ops.append([f"%experts.{i}", t + k, rest,
                    {"scope": "mlp.moe_experts"}])
        t += EXEC_NS
    return {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": tr.OPS_LINE, "events": ops},
        {"name": tr.MODULES_LINE, "events": mods}]}]}


def _art(bench_json, table, resident=100_000.0, hit=64.0):
    config = harness.load_json(
        ROOT, harness.find(bench_json["configs"], "deepseek-v2-lite-l9",
                           "configuration")["file"])
    return {
        "cell": {"name": CELL}, "config": config,
        "engine": {"decode_steps": STEPS},
        "peaks": {"hbm_bytes_per_s": PEAK},
        "trace": {**tr.reduce(table), "slice": [10.0, 10.5]},
        # one request, decoding all through the slice, resident at its middle
        "requests": [{"t_first": 0.0, "t_last": 20.5,
                      "prompt_tokens": resident - 500.0, "tokens": 1000}],
        "digests": [
            {"kind": "decode", "rows": 100, "moe_experts_hit": 0.0,
             "moe_load_max": 0.0},
            {"kind": "overlap", "rows": 100, "moe_experts_hit": hit,
             "moe_load_max": 18.75},
        ],
    }


@pytest.mark.parametrize("executions", [3.0, 2.4], ids=["whole", "cut"])
def test_rooflines_read_the_known_share(bench_json, monkeypatch, executions):
    import shapes_mla
    import shapes_moe

    table = _table(executions)
    monkeypatch.setattr(trace_host, "scopes",
                        lambda art: trace_host.scope_times(table))
    art = _art(bench_json, table)
    hf = {k: v for k, v in art["config"].items() if k != "benchmark"}
    step_s = EXEC_NS / 1e9 / STEPS
    resident = shapes_mla.resident_tokens(art)
    assert resident == pytest.approx(100_000.0, rel=1e-3)
    # 9 layers x 576 values x 2 bytes a token
    assert shapes_mla.latent_bytes_per_token(hf) == 10_368
    latent = resident * 10_368
    want_mla = latent / PEAK / (step_s * KERNEL_SHARE) * 100
    got = harness.read_metric("layer_metrics", "mla_decode_attn_roofline", art)
    assert got == pytest.approx(want_mla, rel=1e-6)
    weights = shapes_moe.decode_weight_bytes(hf, 64.0)
    # the issue's count: 9.94 GB a step with every expert hit
    assert weights == pytest.approx(9.94e9, rel=0.01)
    got = harness.read_metric("layer_metrics", "decode_step_roofline", art)
    assert got == pytest.approx((weights + latent) / PEAK / step_s * 100,
                                rel=1e-6)
    assert harness.read_metric(
        "layer_metrics", "moe_experts_hit_mean", art) == 64.0
    # 18.75 on the fullest expert over 100 rows x 6 / 64 = 9.375 on average
    assert harness.read_metric(
        "layer_metrics", "moe_load_max_over_mean", art) == pytest.approx(2.0)


def test_fewer_experts_hit_need_fewer_bytes(bench_json):
    import shapes_moe

    hf = harness.load_json(ROOT, "benchmark", "configs",
                           "deepseek-v2-lite-l9.json")
    full = shapes_moe.decode_weight_bytes(hf, 64)
    assert full - shapes_moe.decode_weight_bytes(hf, 32) == (
        8 * 32 * shapes_moe.expert_params(hf) * 2)
    assert shapes_moe.expert_params(hf) == 3 * 2048 * 1408


def test_readers_find_nothing_in_a_program_without_the_scopes(
        bench_json, monkeypatch):
    """The parent commit's program has no `attn.mla_kernel` scope and no
    expert-load digest column: each new reader returns None, none raises."""
    table = _table(3.0)
    for line in table["planes"][0]["lines"]:
        for ev in line["events"]:
            ev[3] = {"scope": "attn.kernel"} if ev[3] else ev[3]
    monkeypatch.setattr(trace_host, "scopes",
                        lambda art: trace_host.scope_times(table))
    art = _art(bench_json, table)
    art["digests"] = [{"kind": "decode", "rows": 64}]
    for name in ("mla_decode_attn_roofline", "decode_step_roofline",
                 "moe_experts_hit_mean", "moe_load_max_over_mean"):
        assert harness.read_metric("layer_metrics", name, art) is None
    art["trace"] = None
    for name in ("mla_decode_attn_roofline", "decode_step_roofline"):
        assert harness.read_metric("layer_metrics", name, art) is None
