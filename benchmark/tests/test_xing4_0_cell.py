"""The `xing4.0-29b-a4b-l6.decode-wide` cell on the CPU: the files load and
the configuration with its two cuts put back IS the preset, the parameter
count by arithmetic alone and against the program's own tree, the three
readers this cell brought on a hand-made `art` (a known share reads that
share, whole executions and a cut one; a cell of one stream reads None),
and a rehearsal of a whole run judged by `references/xing4_0.py`."""
import functools

import pytest

import harness
import shapes_xing as sh
import trace_host
import trace_reduce as tr
from conftest import BENCH, ROOT, run_cell

CELL = "xing4.0-29b-a4b-l6.decode-wide"
CONFIG = "xing4.0-29b-a4b-l6"
NEW = ("decode_mhc_ms", "mhc_mix_roofline", "xing_decode_step_roofline")
# ISSUE 43 wrote 4,792,849,540: it counted the two small norms of a
# layer's attention (768 + 512) twice, in the matrices and in the norms
PARAMS = 4_792_841_860


def _config(bench_json):
    return harness.load_json(
        ROOT, harness.find(bench_json["configs"], CONFIG,
                           "configuration")["file"])


def test_the_files_load_and_the_preset_holds(bench_json):
    hf, cb = harness.split_config(_config(bench_json), CONFIG)
    assert cb["reduced"] == {"num_hidden_layers": 40,
                             "first_k_dense_replace": 2}
    assert cb["reference"] == "xing4_0"
    harness.check_preset(hf, cb["reduced"], cb["preset"])
    entry = harness.find(bench_json["configs"], CONFIG, "configuration")
    assert entry["reduced"] == list(cb["reduced"])
    # every published width, as run
    assert (hf["hidden_size"], hf["num_attention_heads"], hf["q_lora_rank"],
            hf["kv_lora_rank"], hf["qk_nope_head_dim"],
            hf["qk_rope_head_dim"], hf["v_head_dim"]) == (
                3584, 32, 768, 512, 128, 64, 128)
    assert (hf["n_routed_experts"], hf["moe_intermediate_size"],
            hf["num_experts_per_tok"], hf["n_shared_experts"],
            hf["intermediate_size"], hf["vocab_size"], hf["hc_mult"],
            hf["hc_sinkhorn_iters"]) == (64, 1024, 4, 1, 9216, 131072, 4, 20)
    cell = harness.find(bench_json["workloads"], CELL, "cell")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "decode-wide", 1)
    assert len(cell["why"]) <= 200
    params = harness.load_json(BENCH, "cells", CELL + ".json")
    assert params["clients"] <= params["decode_rows_max"] == 256
    harness.load_by_name("references", cb["reference"], "token_logprobs")
    for name in NEW:
        entry = harness.find(bench_json["per_layer"], name, "metric")
        assert entry["workloads"] == [CELL]
        harness.load_by_name("layer_metrics", name, "read")
    # an unknown preset is refused before anything is built
    with pytest.raises(harness.Refusal, match="no preset named"):
        harness.check_preset(hf, cb["reduced"], "xing4.0-29b-a4b-v0")


def test_parameter_count_by_arithmetic_and_by_the_program(bench_json):
    import jax

    from dynamo_tpu.models import llama
    from dynamo_tpu.models.config import PRESETS, ModelConfig

    hf, cb = harness.split_config(_config(bench_json), CONFIG)
    # a layer's pieces, from the configuration's keys alone
    assert sh.attention_params(hf) == 28_409_856 + 768 + 512 + 2 * 3584
    assert sh.boundary_params(hf) == 14336 * 24 + 14336 + 27
    assert sh.expert_params(hf) == 11_010_048
    assert sh.param_count(hf) == PARAMS
    # every expert hit: the step reads everything but the embedding
    assert sh.decode_weight_bytes(hf, 64) == 2 * (
        PARAMS - hf["vocab_size"] * hf["hidden_size"])
    assert sh.mhc_mix_bytes(hf, 256) == 256 * 12 * 2 * 14336 * 2
    # the program's own tree, at the published widths (shapes only) and at
    # the tiny size
    for cfg, keys in (
            (ModelConfig.from_hf_config(hf, name=CONFIG), hf),
            (PRESETS["tiny-xing"], {
                "hidden_size": 64, "num_attention_heads": 4,
                "qk_nope_head_dim": 32, "qk_rope_head_dim": 16,
                "kv_lora_rank": 32, "v_head_dim": 32, "q_lora_rank": 24,
                "hc_mult": 4, "moe_intermediate_size": 32,
                "num_hidden_layers": 3, "first_k_dense_replace": 1,
                "intermediate_size": 128, "n_routed_experts": 8,
                "n_shared_experts": 1, "vocab_size": 256})):
        tree = jax.eval_shape(
            functools.partial(llama.init_params, cfg), jax.random.PRNGKey(0))
        assert llama.param_count(tree) == sh.param_count(keys)


# ------------------------------------------------------- the three readers

STEPS, PEAK = 8, 819e9
EXEC_NS = 160_000_000          # one decode execution: 8 steps of 20 ms
MHC_SHARE = 0.125              # attn.mhc + mlp.mhc


def _table(executions: float, maps="attn.mhc", post="mlp.mhc"):
    """A device plane of `executions` decode executions (the last one cut
    where the count is fractional): an execution is one operation outside
    the scan, then 8 steps of the boundaries' operations, the latent
    kernel and the experts, each instruction under its own numbered name."""
    ops, mods, t = [], [], 0
    whole = int(executions)
    step = (EXEC_NS - EXEC_NS // 100) // STEPS
    a = int(step * MHC_SHARE) // 2
    k = step // 4
    body = [("%fusion.1", a, maps), ("%fusion.2", a, post),
            ("%mla.3 = custom-call()", k, "attn.mla_kernel"),
            ("%gmm.4 = custom-call()", step - 2 * a - k, "mlp.moe_experts")]
    for i in range(whole + (executions > whole)):
        end = t + int(EXEC_NS * (1.0 if i < whole else executions - whole))
        mods.append(["jit__decode_multi(7)", t, end - t, {}])
        ops.append(["%embed.0", t, EXEC_NS // 100, {"scope": "embed"}])
        at = t + EXEC_NS // 100
        for _ in range(STEPS):
            for name, dur, scope in body:
                if at + dur <= end:
                    ops.append([name, at, dur, {"scope": scope}])
                at += dur
        t += EXEC_NS
    return {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": tr.OPS_LINE, "events": ops},
        {"name": tr.MODULES_LINE, "events": mods}]}]}


def _art(bench_json, table, rows=250, hit=60.0):
    return {
        "cell": {"name": CELL}, "config": _config(bench_json),
        "engine": {"decode_steps": STEPS, "max_batch": 256},
        "peaks": {"hbm_bytes_per_s": PEAK},
        "trace": {**tr.reduce(table), "slice": [10.0, 10.5]},
        # one request, decoding all through the slice: 264,500 resident
        # tokens at its middle
        "requests": [{"t_first": 0.0, "t_last": 20.5,
                      "prompt_tokens": 264_000.0, "tokens": 1000}],
        "digests": [
            {"kind": "decode", "rows": rows, "moe_experts_hit": hit},
            {"kind": "prefill", "rows": 1, "moe_experts_hit": 0.0},
        ],
    }


@pytest.mark.parametrize("executions", [3.0, 2.375], ids=["whole", "cut"])
def test_new_readers_read_the_known_share(bench_json, monkeypatch, executions):
    import shapes_mla

    table = _table(executions)
    monkeypatch.setattr(trace_host, "scopes",
                        lambda art: trace_host.scope_times(table))
    art = _art(bench_json, table)
    hf = {k: v for k, v in art["config"].items() if k != "benchmark"}
    step_s = (EXEC_NS - EXEC_NS // 100) / 1e9 / STEPS
    # (the cut falls inside a step, after the boundaries' operations and
    # before the long one: with four instructions a step that part-step
    # reads the boundaries up to 19 / 18.4 high; a real step has hundreds)
    rel = 0.02 if executions == int(executions) else 0.05
    got = harness.read_metric("layer_metrics", NEW[0], art)
    assert got == pytest.approx(step_s * 1e3 * MHC_SHARE, rel=rel)
    mix = 250 * 12 * 2 * 14336 * 2
    got = harness.read_metric("layer_metrics", NEW[1], art)
    assert got == pytest.approx(
        mix / PEAK / (step_s * MHC_SHARE) * 100, rel=rel)
    weights = sh.decode_weight_bytes(hf, 60.0)
    latent = shapes_mla.decode_latent_bytes(hf, 264_500.0)
    assert latent == 264_500 * 6 * 576 * 2
    got = harness.read_metric("layer_metrics", NEW[2], art)
    assert got == pytest.approx(
        (weights + latent + mix) / PEAK / (step_s / 0.99) * 100, rel=0.02)
    assert got < 100
    # ISSUE 43's arithmetic: the boundaries are ~2% of such a step's bytes
    assert mix / (weights + latent + mix) == pytest.approx(0.017, abs=0.005)


def test_new_readers_find_nothing_without_the_streams(bench_json, monkeypatch):
    """The parent commit's program, and every other configuration's, has no
    `*.mhc` scope and no `hc_mult`: each new reader returns None, none
    raises; without a trace the same."""
    table = _table(3.0, maps="attn.mla_absorb", post="norm")
    monkeypatch.setattr(trace_host, "scopes",
                        lambda art: trace_host.scope_times(table))
    art = _art(bench_json, table)
    for name in NEW[:2]:
        assert harness.read_metric("layer_metrics", name, art) is None, name
    other = harness.load_json(ROOT, harness.find(
        bench_json["configs"], "deepseek-v2-lite-l9", "configuration")["file"])
    art = {**_art(bench_json, _table(3.0)), "config": other}
    for name in NEW:
        assert harness.read_metric("layer_metrics", name, art) is None, name
    art = _art(bench_json, _table(3.0))
    art["trace"] = None
    for name in NEW:
        assert harness.read_metric("layer_metrics", name, art) is None, name


def test_rehearsal_prints_counts(bench_json):
    rc, line, err = run_cell(ROOT, "--workload", CELL, "--seed", "3000000011",
                             "--seconds", "5", "--trace", "1", "--rehearse")
    assert rc == 0, err[-2000:]
    # (some 200 clients on this CPU: a request that starts inside the window
    # may not finish in it, so `attempted` and with it `correct` are the
    # chip's to read; the comparison itself is judged here)
    assert line["check"]["ok"] is True, line["check"]
    assert line["check"]["positions"] == 64
    clients = harness.load_json(BENCH, "cells", CELL + ".json")["clients"]
    assert line["counts"]["sent"] >= clients
    assert line["failed"] == 0 and line["device"]["platform"] == "cpu"
    got = line["metrics"]
    want = {m["name"] for m in bench_json["per_layer"]
            if CELL in m.get("workloads", [CELL])}
    assert set(got) <= want
    assert got["decode_rows_mean"]["value"] > 8
    assert got["preemptions"]["value"] == 0
    assert not [n for n in line["compiled_in_window"]
                if "_model_step" in n or "_decode_multi" in n]
