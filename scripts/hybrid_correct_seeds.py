"""Served log-probabilities against the float32 reference at the
`mimo-v2-flash-l7` configuration's published widths (or, with `--config`,
any other configuration's), for many seeds in one call: what `correct` judges in a cell run (16 greedy tokens with their
log-probabilities after each of the configuration's `check_prompts`, through
chunked prefill then decode on the engine's normal tick), without a window.
The readings behind the configuration's `tolerance` (PERF.md section 6, PR
36). One engine at a time (its arrays are deleted before the next); random
prompts; `lib/reference.compare` is the harness's. Exits non-zero without a
TPU; results go to `chiprun_out/hybrid_correct_seeds.json`.

    chiprun -- python scripts/hybrid_correct_seeds.py --seeds 101 102 ...
        [--config xing4.0-29b-a4b-l6] [--set num_experts_per_tok=64]
"""

from __future__ import annotations

import argparse
import asyncio
import gc
import json
import os
import sys

import jax
import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "benchmark", "lib")]

import harness  # noqa: E402
from reference import compare  # noqa: E402

CONFIG = "mimo-v2-flash-l7"


async def one_seed(hf, cb, token_logprobs, seed: int,
                   config: str = CONFIG) -> dict:
    from dynamo_tpu.engine.config import EngineConfig
    from dynamo_tpu.engine.engine import JaxEngine
    from dynamo_tpu.llm.protocols.common import (
        PreprocessedRequest,
        SamplingOptions,
        StopConditions,
    )
    from dynamo_tpu.models.config import ModelConfig
    from dynamo_tpu.runtime.pipeline.context import Context

    flags = dict(zip(cb["engine_flags"][::2], cb["engine_flags"][1::2]))
    engine = JaxEngine(EngineConfig(
        model=ModelConfig.from_hf_config(hf, name=config),
        max_batch_size=int(flags["--max-batch-size"]),
        max_model_len=int(flags["--max-model-len"]),
        prefill_chunk=int(flags["--prefill-chunk"]),
        decode_steps=int(flags["--decode-steps"]),
        page_size=int(flags["--page-size"]), seed=seed, **cb["engine_args"]))
    rng = np.random.RandomState(seed)
    served, want = [], []
    n = harness.CHECK_TOKENS
    for p in cb["check_prompts"]:
        prompt = [int(t) for t in rng.randint(8, hf["vocab_size"] - 256, p)]
        pre = PreprocessedRequest(
            token_ids=prompt,
            stop_conditions=StopConditions(max_tokens=n, ignore_eos=True),
            sampling_options=SamplingOptions(greedy=True))
        pre.sampling_options.logprobs = True
        frames = [f async for f in await engine.generate(
            Context(pre.to_dict()))]
        toks = [t for f in frames for t in f.get("token_ids") or []]
        lps = [lp for f in frames for lp in f.get("log_probs") or []]
        assert len(toks) == len(lps) == n, (len(toks), len(lps))
        served.append(lps)
        ids = prompt + toks
        want.append(token_logprobs(
            engine.params, hf, ids, n, -(-len(ids) // 128) * 128))
    out = compare(served, want, cb["tolerance"])
    out["preemptions"] = engine.metrics()["preemptions_total"]
    await engine.close()
    for leaf in jax.tree.leaves((engine.params, engine.kv)):
        leaf.delete()
    del engine
    gc.collect()
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--config", default=CONFIG,
                    help="any configuration of benchmark/configs")
    ap.add_argument("--set", nargs="*", default=[], metavar="KEY=INT",
                    help="a key of the configuration changed on BOTH sides, "
                         "program and reference: a witness, never a reading "
                         "behind a limit (num_experts_per_tok=64: every "
                         "expert chosen, so no selection can differ)")
    args = ap.parse_args()
    if jax.devices()[0].platform != "tpu":
        print("needs a TPU", file=sys.stderr)
        return 3
    hf, cb = harness.split_config(harness.load_json(
        ROOT, "benchmark", "configs", args.config + ".json"), args.config)
    token_logprobs = harness.load_by_name(
        "references", cb["reference"], "token_logprobs")
    changed = {k: int(v) for k, v in (kv.split("=") for kv in args.set)}
    hf.update(changed)
    res = {}
    for seed in args.seeds:
        res[str(seed)] = asyncio.run(
            one_seed(hf, cb, token_logprobs, seed, args.config))
        print(json.dumps({"seed": seed, **res[str(seed)]}), flush=True)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    name = ("hybrid_correct_seeds.json" if args.config == CONFIG
            else f"correct_seeds_{args.config}.json")
    if changed:
        name = name[:-5] + "".join(
            f".{k}-{v}" for k, v in changed.items()) + ".json"
    with open(os.path.join(ROOT, "chiprun_out", name), "w") as f:
        json.dump(res, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
