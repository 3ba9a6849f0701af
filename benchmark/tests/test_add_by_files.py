"""A later PR adds a configuration, a traffic mix, a cell and a per-layer
metric as NEW FILES AND ENTRIES ONLY. This test does exactly that to a copy
of the benchmark and runs the new cell. The throw-away configuration serves
float32, so the same run shows that the plain reference agrees with the
engine to float32 rounding on the tiny model."""
import json
import os
import shutil

from conftest import BENCH, ROOT, run_cell


def test_new_cell_from_new_files_only(tmp_path):
    root = str(tmp_path)
    shutil.copytree(BENCH, os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {
        os.path.join(d, f): os.path.getmtime(os.path.join(d, f))
        for d, _, fs in os.walk(os.path.join(root, "benchmark")) for f in fs}
    b = os.path.join(root, "benchmark")
    with open(os.path.join(BENCH, "configs", "mistral-7b-int8.json")) as f:
        cfg = json.load(f)
    cfg["benchmark"]["engine_flags"] = [
        "--max-batch-size", "16", "--max-model-len", "1024", "--page-size",
        "16", "--dtype", "float32", "--num-pages", "600"]
    cfg["benchmark"]["engine_args"]["prefill_group_tokens"] = 64
    cfg["benchmark"]["tolerance"] = {"gap_mean": 2e-4, "gap_max": 2e-3,
                                     "why": "float32 on both sides"}
    with open(os.path.join(b, "configs", "throwaway-f32.json"), "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(b, "traffic", "throwaway-burst.json"), "w") as f:
        json.dump({
            "loop": "open", "schedule_seed": 5,
            "prompt_tokens": {"dist": "loguniform", "min": 64, "max": 512},
            "output_tokens": {"dist": "fixed", "value": 64},
            "arrivals": {"process": "poisson",
                         "burst": {"every_s": 2.0, "size": 4}},
            "sharing": {"prefix_groups": 2,
                        "prefix_tokens": {"dist": "uniform", "min": 256,
                                          "max": 512}},
            "warmup_s": 3.0, "cutoff_s": 5.0}, f)
    with open(os.path.join(b, "cells", "throwaway-f32.burst.json"), "w") as f:
        json.dump({"rate_rps": 2.0}, f)
    with open(os.path.join(b, "layer_metrics", "prefix_hit_share.py"), "w") as f:
        f.write('"""Share of prompt blocks the prefix cache served."""\n\n\n'
                "def read(art):\n"
                "    s = [v for v in art['summaries'].values()\n"
                "         if v['request_id'].split('-')[-1].startswith('m')]\n"
                "    ps = art['engine']['page_size']\n"
                "    return 100.0 * sum(v['prefix']['reused_blocks'] * ps\n"
                "                       for v in s) / max(\n"
                "        sum(v['prompt_tokens'] for v in s), 1)\n")
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "throwaway-f32", "source": cfg["benchmark"]["deployment"][:100],
        "file": "benchmark/configs/throwaway-f32.json", "reduced": [],
        "why": "test"})
    bench["workloads"].append({
        "name": "throwaway-f32.burst", "config": "throwaway-f32",
        "traffic": "throwaway-burst", "chips": 1, "why": "test"})
    bench["per_layer"].append({
        "name": "prefix_hit_share", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "KV manager",
        "moves": "ttft_p50_ms", "workloads": ["throwaway-f32.burst"]})
    for m in bench["end_to_end"]:
        if "workloads" in m:
            m["workloads"].append("throwaway-f32.burst")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)

    rc, line, err = run_cell(root, "--workload", "throwaway-f32.burst",
                             "--seed", "7", "--seconds", "4", "--trace", "1",
                             "--rehearse")
    assert rc == 0, err[-2000:]
    assert line["correct"] is True, line["check"]
    # the reference and the engine agree to float32 rounding
    assert line["check"]["positions"] == 64
    assert line["check"]["gap_max"] < 2e-3
    # 8 base arrivals + 2 bursts of 4, none failed
    assert line["attempted"] == 16 and line["failed"] == 0
    # the new metric was found by its name and read the shared prefixes
    assert line["metrics"]["prefix_hit_share"]["value"] > 10.0
    # nothing that was there was edited
    after = {p: os.path.getmtime(p) for p in before}
    assert after == before
