"""The controls behind `sdar-30b-a3b-l6`'s tolerance: the float32 reference
(`references/sdar_moe.py`) with ONE thing changed, put in the program's
place and judged by the harness's own comparison (`lib/reference.compare`)
under the configuration's own limits. A limit is sound while the served
program passes it and every one of these does not (the configuration's
`tolerance.why` has the readings).

    python3 benchmark/controls/sdar_moe.py [--config <name>] [--seeds 1 2]
        [--controls int8_weights ...] [--rehearse]
        [--out chiprun_out/sdar_controls.json]

- `int8_weights`: every matrix rounded to int8 with one scale per output
  channel, the nearest precision below the bf16 the configuration serves;
- the mechanism, each a program that got one piece of it wrong:
  `causal_in_block` (a causal line inside a generated block: a position
  does not see the block's later ones), `no_commit` (the commit pass left
  out: for the blocks after it the cache keeps what a block's last
  denoising pass wrote, the mask token at the positions that pass filled),
  `causal_prompt` (the prompt encoded causally, not by blocks),
  `no_qk_norm` (the norm a head on queries and keys dropped),
  `no_renorm` (the top-8 weights not renormalised to sum 1).

What is judged, as the harness judges a served run: 16 positions after
each of the configuration's `check_prompts`, weights from the seed as the
engine makes them (`llama.init_params`), prompts and the tokens before a
judged one random; the judged token at a position is the float32
reference's most likely one in the pass that fills it (a greedy server
emits that). A sum on float32 units, no time: through the chip tool at the
published widths, or `--rehearse` on the CPU.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [ROOT, os.path.join(BENCH, "lib")]

import harness  # noqa: E402
from reference import compare  # noqa: E402

# control -> the keywords of `forward_rows` that make it
CONTROLS = {
    "int8_weights": {},
    "causal_in_block": {"in_block": False},
    "no_commit": {"commit": False},
    "causal_prompt": {"prompt_block": False},
    "no_qk_norm": {"qk_norm": False},
    "no_renorm": {"renorm": False},
}


def _int8_weight(w):
    """`_f32` of the reference with a matrix rounded on the way."""
    w = w.astype(jnp.float32)
    if w.ndim < 2:
        return w
    s = (jnp.max(jnp.abs(w), axis=-2, keepdims=True) + 1e-30) / 127.0
    return jnp.round(w / s) * s


def changed(ref_path: str, control: str):
    """A fresh load of the reference's file (its jitted functions find
    what they call in their module at trace time, and a module of its own
    holds no other control's traces), with int8 weights where asked."""
    spec = importlib.util.spec_from_file_location("changed_" + control,
                                                  ref_path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    if control == "int8_weights":
        mod._f32 = _int8_weight
    elif control != "float32" and control not in CONTROLS:
        raise ValueError(f"unknown control {control!r}")
    return mod


def readings(config: str, seeds, rehearse: bool = False,
             controls=tuple(CONTROLS), n_prompts: int | None = None) -> dict:
    """{control: {seed: compare(...)}} under the configuration's limits
    (`n_prompts`: only the first few of `check_prompts`, for a quick test)."""
    from dynamo_tpu.models import llama
    from dynamo_tpu.models.config import ModelConfig

    hf, bench = harness.split_config(
        harness.load_json(BENCH, "configs", config + ".json"), config)
    prompts = bench["check_prompts"][:n_prompts]
    if rehearse:
        hf = {**hf, **bench["rehearsal_model"]}
        prompts = [max(p // 8, 4) for p in prompts]
    cfg = ModelConfig.from_hf_config(hf, name=config)
    ref_path = os.path.join(BENCH, "references", bench["reference"] + ".py")
    n = harness.CHECK_TOKENS
    out = {c: {} for c in controls}
    for seed in seeds:
        params = llama.init_params(cfg, jax.random.PRNGKey(seed),
                                   dtype=jnp.bfloat16)
        rng = np.random.RandomState(seed)
        seqs = [[int(t) for t in rng.randint(0, cfg.vocab_size, p + n)]
                for p in prompts]
        pad = -(-max(len(s) for s in seqs) // 128) * 128
        base_mod = changed(ref_path, "float32")
        base, judged = [], []
        for s in seqs:
            rows = base_mod.logprob_rows(params, hf, s, n, pad)
            tok = np.argmax(rows, axis=-1)
            judged.append(tok)
            base.append(rows[np.arange(n), tok].astype(np.float64))
        for control in controls:
            mod = changed(ref_path, control)
            got = [mod.logprob_rows(params, hf, s, n, pad,
                                    **CONTROLS[control])[np.arange(n), tok]
                   for s, tok in zip(seqs, judged)]
            out[control][str(seed)] = {
                **compare(got, base, bench["tolerance"]),
                # a prompt's 16 positions alone: the limits judge the 64
                # together, and a control may live in the short prompts only
                "by_prompt": {
                    str(p): {k: compare([g], [b], bench["tolerance"])[k]
                             for k in ("gap_mean", "gap_max")}
                    for p, g, b in zip(prompts, got, base)}}
            harness.log(f"control {control} seed {seed}: "
                        f"{out[control][str(seed)]}")
            del mod
            jax.clear_caches()
        del params
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="sdar-30b-a3b-l6")
    ap.add_argument("--seeds", type=int, nargs="+", default=[1, 2])
    ap.add_argument("--controls", nargs="+", default=list(CONTROLS),
                    choices=list(CONTROLS))
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--out", default=os.path.join(
        ROOT, "chiprun_out", "sdar_controls.json"))
    args = ap.parse_args()
    res = {"config": args.config, "rehearse": args.rehearse,
           "platform": jax.default_backend(),
           "readings": readings(args.config, args.seeds, args.rehearse,
                                controls=args.controls)}
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(res, f, indent=1)
    print(json.dumps(res))
    return 0


if __name__ == "__main__":
    sys.exit(main())
