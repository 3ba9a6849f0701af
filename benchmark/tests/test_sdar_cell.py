"""The `sdar-30b-a3b-l6.decode-wide` cell on the CPU: the files load and the
configuration with its one cut put back IS the preset, the parameter count
by arithmetic alone and against the program's own tree, the five readers
this cell brought on a hand-made `art` (a known share reads that share;
another configuration's cell, the parent's program and a run without a
trace read None), every control refused at the rehearsal's size, and a
rehearsal of a whole run judged by `references/sdar_moe.py`."""
import functools
import importlib.util
import json
import os

import pytest

import harness
import shapes_dlm as sh
import trace_host
import trace_reduce as tr
from conftest import BENCH, ROOT, run_cell

CELL = "sdar-30b-a3b-l6.decode-wide"
CONFIG = "sdar-30b-a3b-l6"
NEW = ("dlm_pass_ms", "dlm_tokens_per_pass", "dlm_block_attn_roofline",
       "dlm_step_roofline", "dlm_head_sample_ms")
PARAMS = 4_361_055_744     # ISSUE 47's arithmetic: 6 x 623,120,640 + 622,331,904


def _config(bench_json):
    return harness.load_json(
        ROOT, harness.find(bench_json["configs"], CONFIG,
                           "configuration")["file"])


def test_the_files_load_and_the_preset_holds(bench_json):
    hf, cb = harness.split_config(_config(bench_json), CONFIG)
    assert cb["reduced"] == {"num_hidden_layers": 48}
    assert cb["reference"] == "sdar_moe"
    harness.check_preset(hf, cb["reduced"], cb["preset"])
    entry = harness.find(bench_json["configs"], CONFIG, "configuration")
    assert entry["reduced"] == list(cb["reduced"])
    assert len(entry["why"]) <= 200 and entry["why"].isascii()
    # every published width, as run, and the block keys beside them
    assert (hf["hidden_size"], hf["num_attention_heads"],
            hf["num_key_value_heads"], hf["head_dim"], hf["num_experts"],
            hf["num_experts_per_tok"], hf["moe_intermediate_size"],
            hf["vocab_size"], hf["num_hidden_layers"]) == (
                2048, 32, 4, 128, 128, 8, 768, 151936, 6)
    assert (hf["block_length"], hf["denoising_steps"],
            hf["remasking_strategy"], hf["mask_token_id"]) == (
                4, 2, "sequential", 151669)
    flags = dict(zip(cb["engine_flags"][::2], cb["engine_flags"][1::2]))
    assert int(flags["--decode-steps"]) % (hf["denoising_steps"] + 1) == 0
    assert [p % 4 for p in cb["check_prompts"]] == [3, 2, 1, 0]
    rehearsal = {**hf, **cb["rehearsal_model"]}
    assert rehearsal["mask_token_id"] < rehearsal["vocab_size"] == 512
    cell = harness.find(bench_json["workloads"], CELL, "cell")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "decode-wide", 1)
    assert len(cell["why"]) <= 200 and cell["why"].isascii()
    params = harness.load_json(BENCH, "cells", CELL + ".json")
    assert params["clients"] <= params["decode_rows_max"] == 256
    assert str(params["clients"]) + " clients" in cell["why"]
    harness.load_by_name("references", cb["reference"], "token_logprobs")
    for name in NEW:
        entry = harness.find(bench_json["per_layer"], name, "metric")
        assert entry["workloads"] == [CELL]
        harness.load_by_name("layer_metrics", name, "read")
    with pytest.raises(harness.Refusal, match="no preset named"):
        harness.check_preset(hf, cb["reduced"], "sdar-30b-a3b-v0")


def test_parameter_count_by_arithmetic_and_by_the_program(bench_json):
    import jax

    from dynamo_tpu.models import llama
    from dynamo_tpu.models.config import PRESETS, ModelConfig

    hf, _ = harness.split_config(_config(bench_json), CONFIG)
    assert sh.attention_params(hf) == 18_874_368 + 256 + 4096
    assert sh.expert_params(hf) == 4_718_592
    assert sh.param_count(hf) == PARAMS
    assert sh.kv_bytes_per_token(hf) == 12_288
    # every expert hit: a pass reads everything but the embedding
    assert sh.pass_weight_bytes(hf, 128) == 2 * (
        PARAMS - hf["vocab_size"] * hf["hidden_size"])
    for cfg, keys in (
            (ModelConfig.from_hf_config(hf, name=CONFIG), hf),
            (PRESETS["tiny-sdar"], {
                "hidden_size": 64, "num_attention_heads": 4,
                "num_key_value_heads": 2, "head_dim": 16,
                "moe_intermediate_size": 32, "num_hidden_layers": 2,
                "num_experts": 8, "vocab_size": 256})):
        tree = jax.eval_shape(
            functools.partial(llama.init_params, cfg), jax.random.PRNGKey(0))
        assert llama.param_count(tree) == sh.param_count(keys)


# -------------------------------------------------------- the five readers

PASSES, LAYERS = 9, 6
EXEC_NS = 135_000_000          # one block execution: 9 passes of 15 ms
KERNEL_NS = 400_000            # the block attention kernel, a layer a pass
HEAD_SHARE = 0.2


def _table(executions: int = 3, program="jit__dlm_multi(7)",
           scope="attn.block"):
    """A device plane of whole block executions: a pass is, a layer, the
    block attention's kernel with a copy that waits beside it under the
    same scope, then the experts; then the head and the sampling."""
    ops, mods, t = [], [], 0
    pass_ns = EXEC_NS // PASSES
    head = int(pass_ns * HEAD_SHARE)
    wait = 100_000
    rest = (pass_ns - head - LAYERS * (KERNEL_NS + wait)) // LAYERS
    for _ in range(executions):
        mods.append([program, t, EXEC_NS, {}])
        at = t
        for _ in range(PASSES):
            for layer in range(LAYERS):
                for name, dur, sc in (
                        (f"%flash_prefill_attention.{layer}", KERNEL_NS, scope),
                        (f"%copy-done.{layer}", wait, scope),
                        (f"%gmm.{layer}", rest, "mlp.moe_experts")):
                    ops.append([name, at, dur, {"scope": sc}])
                    at += dur
            ops.append(["%fusion.90", at, head // 2, {"scope": "head"}])
            ops.append(["%fusion.91", at + head // 2, head - head // 2,
                        {"scope": "sample"}])
            at = t + (at - t + head + pass_ns - 1) // pass_ns * pass_ns
        t += EXEC_NS
    return {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": tr.OPS_LINE, "events": ops},
        {"name": tr.MODULES_LINE, "events": mods}]}]}


def _art(bench_json, table, rows=250, hit=120.0, kind="dlm"):
    return {
        "cell": {"name": CELL}, "config": _config(bench_json),
        "engine": {"decode_steps": PASSES, "max_batch": 256},
        "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12},
        "trace": {**tr.reduce(table), "slice": [10.0, 10.5]},
        # one request, decoding all through the slice: 264,500 resident
        # tokens at its middle
        "requests": [{"t_first": 0.0, "t_last": 20.5,
                      "prompt_tokens": 264_000.0, "tokens": 1000}],
        "digests": [
            {"kind": kind, "rows": rows, "dlm_passes": PASSES},
            {"kind": "overlap", "rows": rows, "moe_experts_hit": hit,
             "dlm_row_passes": rows * PASSES, "dlm_filled": rows * 12,
             "dlm_committed": rows * 3},
            {"kind": "prefill", "rows": 1, "moe_experts_hit": 0.0},
        ],
    }


def _patched(monkeypatch, table):
    monkeypatch.setattr(trace_host, "scopes",
                        lambda art: trace_host.scope_times(table))
    monkeypatch.setattr(trace_host, "find", lambda art: "a.json")
    monkeypatch.setattr(trace_host, "load", lambda path: table)


def test_new_readers_read_the_known_share(bench_json, monkeypatch):
    table = _table()
    _patched(monkeypatch, table)
    art = _art(bench_json, table)
    hf = sh.config(art)
    pass_s = EXEC_NS / 1e9 / PASSES
    got = {n: harness.read_metric("layer_metrics", n, art) for n in NEW}
    assert got["dlm_pass_ms"] == pytest.approx(15.0)
    assert got["dlm_tokens_per_pass"] == pytest.approx(4 / 3)
    # the kernel's own events, not the copy that waits under its scope
    attn = 264_500 * 12_288 + 6 * 250 * 4 * 2 * 32 * 128 * 2
    assert got["dlm_block_attn_roofline"] == pytest.approx(
        attn / 819e9 / (LAYERS * KERNEL_NS / 1e9) * 100, rel=1e-6)
    weights = sh.pass_weight_bytes(hf, 120.0)
    need = (weights + 264_500 * 12_288) / 819e9
    ops = sh.pass_ops(hf, 250, 264_500) / 197e12
    assert need > ops > 0.2 * need      # bandwidth first, the MXU close
    assert got["dlm_step_roofline"] == pytest.approx(
        need / pass_s * 100, rel=1e-6)
    assert got["dlm_step_roofline"] < 100
    assert got["dlm_head_sample_ms"] == pytest.approx(
        15.0 * HEAD_SHARE, rel=0.01)
    # a slice that cuts an execution after 4 of its 9 passes reads the same
    cut = _table(executions=2)
    for line in cut["planes"][0]["lines"]:
        line["events"] = [e for e in line["events"]
                          if e[1] < EXEC_NS + 4 * (EXEC_NS // PASSES)]
    _patched(monkeypatch, cut)
    art = _art(bench_json, cut)
    assert sh.passes_in_slice(art) == 13
    assert harness.read_metric(
        "layer_metrics", "dlm_pass_ms", art) == pytest.approx(15.0, rel=0.01)
    # at 256 rows of every expert the operations pass the bytes' time
    assert (sh.pass_ops(hf, 512, 500_000) / 197e12
            > 0.6 * (sh.pass_weight_bytes(hf, 128) + 500_000 * 12_288) / 819e9)


def test_new_readers_find_nothing_elsewhere(bench_json, monkeypatch):
    """The parent commit's program (a decode scan, no block program, no
    `dlm` digests), another configuration's cell and a run without a trace:
    each new reader returns None, none raises."""
    table = _table(program="jit__decode_multi(7)", scope="attn.kernel")
    _patched(monkeypatch, table)
    art = _art(bench_json, table, kind="decode")
    for d in art["digests"]:
        for k in [k for k in d if k.startswith("dlm_")]:
            del d[k]
    for name in NEW:
        assert harness.read_metric("layer_metrics", name, art) is None, name
    other = harness.load_json(ROOT, harness.find(
        bench_json["configs"], "deepseek-v2-lite-l9", "configuration")["file"])
    _patched(monkeypatch, _table())
    art = {**_art(bench_json, _table()), "config": other}
    for name in NEW:
        if name not in ("dlm_pass_ms", "dlm_tokens_per_pass",
                        "dlm_head_sample_ms"):   # these read no shape
            assert harness.read_metric(
                "layer_metrics", name, art) is None, name
    art = _art(bench_json, _table())
    art["trace"] = None
    for name in NEW:
        if name != "dlm_tokens_per_pass":        # a counter: no trace read
            assert harness.read_metric(
                "layer_metrics", name, art) is None, name


def test_every_control_is_refused_at_the_rehearsals_size():
    spec = importlib.util.spec_from_file_location(
        "sdar_controls", os.path.join(BENCH, "controls", "sdar_moe.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    got = mod.readings(CONFIG, [1], rehearse=True, controls=[
        "causal_in_block", "no_commit", "no_qk_norm", "no_renorm"],
        n_prompts=2)
    for control, by_seed in got.items():
        assert not by_seed["1"]["ok"], (control, by_seed)


def test_rehearsal_prints_counts(bench_json):
    rc, line, err = run_cell(ROOT, "--workload", CELL, "--seed", "3000000011",
                             "--seconds", "5", "--trace", "1", "--rehearse",
                             "--keep")
    assert rc == 0, err[-2000:]
    # (well over a hundred clients on this CPU: a request that starts inside
    # the window may not finish in it, so `attempted` and with it `correct`
    # are the chip's to read; the comparison itself is judged here, through
    # the block step, at a toy size in bf16: finite at all 64 positions)
    assert line["check"]["positions"] == 64
    assert line["check"]["gap_mean"] < 0.5
    clients = harness.load_json(BENCH, "cells", CELL + ".json")["clients"]
    assert line["counts"]["sent"] >= clients
    assert line["device"]["platform"] == "cpu"
    # a request that starts in the window may wait past the cut-off for its
    # first token here (a dispatch of 9 passes takes seconds on this CPU),
    # which `failed` counts: a CPU's speed, not the program. No request may
    # be refused, break or end without a token
    with open(os.path.join(ROOT, ".bench_work", CELL, "artefacts.json")) as f:
        statuses = {r["status"] for r in json.load(f)["requests"]}
    assert statuses <= {"ok", "cut"}, statuses
    got = line["metrics"]
    want = {m["name"] for m in bench_json["per_layer"]
            if CELL in m.get("workloads", [CELL])}
    assert set(got) <= want and "dlm_tokens_per_pass" in got
    assert got["preemptions"]["value"] == 0
    assert not [n for n in line["compiled_in_window"]
                if "_model_step" in n or "_dlm_multi" in n]
