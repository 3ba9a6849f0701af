"""The control behind `mimo-v2-flash-l7`'s tolerance: the float32 reference
(`references/mimo_v2_flash.py`) with ONE thing changed, put in the
program's place and judged by the harness's own comparison
(`lib/reference.compare`) under the configuration's own limits, at the
published widths. A limit is sound while the served program passes it and
none of these does (PERF.md section 6, PR 36 has the readings).

    python3 benchmark/controls/mimo_v2_flash.py [--config <name>]
        [--seeds 1 2] [--rehearse] [--out chiprun_out/mimo_controls.json]

Controls, each the whole reference with one mechanism of the family taken
away, or one precision lowered:

- `no_window`: window layers attend the whole context (the mask off);
- `no_sink`: the learned sink column left out of the window layers' softmax;
- `no_value_scale`: `attention_value_scale` (0.707) not applied;
- `no_selection_bias`: the router's correction bias left out of the top-k
  selection (the weights never held it);
- `int8_weights`: every matrix rounded to int8 with one scale per output
  channel, the nearest precision below the bf16 the configuration serves.

What is judged, as the harness judges a served run: 16 positions after
each of the configuration's `check_prompts` (the first lies under the
window, where `no_window` is the identity: the other three decide it).
Weights come from the seed with the names and shapes the reference reads,
made HERE (nothing is imported from `dynamo_tpu`): bf16, fan-in scaling,
sinks N(0, 1), correction bias N(0, 0.02), as the program seeds them.
Prompts are random; the judged token at a position is the float32
reference's most likely one (a greedy server emits that). A sum on float32
units, no time: through the chip tool at the published widths (~7 GB of
weights), or on the CPU with `--rehearse`.
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
sys.path[:0] = [os.path.join(BENCH, "lib")]

import harness  # noqa: E402
from reference import compare  # noqa: E402

SWITCHES = {
    "no_window": {"window": False},
    "no_sink": {"sink": False},
    "no_value_scale": {"value_scale": False},
    "no_selection_bias": {"selection_bias": False},
}
CONTROLS = (*SWITCHES, "int8_weights")


def int8_weight(w):
    """`_f32` of the reference with the matrix rounded to int8 on the way,
    one scale per output channel."""
    w = w.astype(jnp.float32)
    if w.ndim < 2:
        return w
    s = (jnp.max(jnp.abs(w), axis=-2, keepdims=True) + 1e-30) / 127.0
    return jnp.round(w / s) * s


def make_params(hf: dict, seed: int, dtype=jnp.bfloat16) -> dict:
    """A parameter tree of the reference's names and shapes from the seed:
    the share the file states (held experts, sliced vocabulary)."""
    d, heads = hf["hidden_size"], hf["num_attention_heads"]
    keys = iter(jax.random.split(jax.random.PRNGKey(seed),
                                 16 * hf["num_hidden_layers"] + 8))

    def dense(*shape):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                * shape[-2] ** -0.5).astype(dtype)

    layers = []
    for l in range(hf["num_hidden_layers"]):
        win = hf["hybrid_layer_pattern"][l]
        kh = hf["swa_num_key_value_heads" if win else "num_key_value_heads"]
        kd = hf["swa_head_dim" if win else "head_dim"]
        vd = hf["swa_v_head_dim" if win else "v_head_dim"]
        lp = {"attn_norm": jnp.ones((d,), dtype),
              "mlp_norm": jnp.ones((d,), dtype),
              "wq": dense(d, heads * kd), "wk": dense(d, kh * kd),
              "wv": dense(d, kh * vd), "wo": dense(heads * vd, d)}
        if win:
            lp["sink"] = jax.random.normal(next(keys), (heads,), jnp.float32)
        if hf["moe_layer_freq"][l]:
            held, f = hf["n_routed_experts"], hf["moe_intermediate_size"]
            width = hf.get("router_width", held)
            lp.update({
                "router": dense(d, width),
                "router_bias": 0.02 * jax.random.normal(
                    next(keys), (width,), jnp.float32),
                "we_gate": dense(held, d, f), "we_up": dense(held, d, f),
                "we_down": dense(held, f, d)})
        else:
            f = hf["intermediate_size"]
            lp.update({"w_gate": dense(d, f), "w_up": dense(d, f),
                       "w_down": dense(f, d)})
        layers.append(lp)
    return {
        "embed": (jax.random.normal(next(keys), (hf["vocab_size"], d),
                                    jnp.float32) * 0.02).astype(dtype),
        "layers": layers, "final_norm": jnp.ones((d,), dtype),
        "lm_head": dense(d, hf["vocab_size"]),
    }


def load_reference(ref_path: str, control: str):
    """A fresh load of the reference's file (a module of its own holds no
    other control's traces); `int8_weights` swaps its `_f32`."""
    spec = importlib.util.spec_from_file_location("ctl_" + control, ref_path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    if control == "int8_weights":
        mod._f32 = int8_weight
    elif control not in SWITCHES and control != "float32":
        raise ValueError(f"unknown control {control!r}")
    return mod


def readings(config: str, seeds, rehearse: bool = False,
             controls=CONTROLS) -> dict:
    """{control: {seed: compare(...)}} under the configuration's limits."""
    hf, bench = harness.split_config(
        harness.load_json(BENCH, "configs", config + ".json"), config)
    prompts = bench["check_prompts"]
    if rehearse:
        hf = {**hf, **bench["rehearsal_model"]}
        # past the rehearsal's window, as the real lengths are past 128
        prompts = [max(p // 8, 4) for p in prompts]
    ref_path = os.path.join(BENCH, "references", bench["reference"] + ".py")
    n = harness.CHECK_TOKENS
    out = {c: {} for c in controls}
    for seed in seeds:
        params = make_params(hf, seed)
        rng = np.random.RandomState(seed)
        seqs = [[int(t) for t in rng.randint(0, hf["vocab_size"], p + n)]
                for p in prompts]
        pads = [-(-len(s) // 128) * 128 for s in seqs]
        base_mod = load_reference(ref_path, "float32")
        base, judged = [], []
        for s, pad in zip(seqs, pads):
            rows = base_mod.logprob_rows(params, hf, s, n, pad)
            tok = jnp.argmax(rows, axis=-1)
            judged.append(tok)
            base.append(np.asarray(
                jnp.take_along_axis(rows, tok[:, None], axis=1)[:, 0],
                np.float64))
        for control in controls:
            mod = load_reference(ref_path, control)
            low = []
            for s, pad, tok in zip(seqs, pads, judged):
                rows = mod.logprob_rows(params, hf, s, n, pad,
                                        **SWITCHES.get(control, {}))
                low.append(np.asarray(
                    jnp.take_along_axis(rows, tok[:, None], axis=1)[:, 0]))
            out[control][str(seed)] = compare(low, base, bench["tolerance"])
            harness.log(f"control {control} seed {seed}: "
                        f"{out[control][str(seed)]}")
            del mod
            jax.clear_caches()
        del params
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="mimo-v2-flash-l7")
    ap.add_argument("--seeds", type=int, nargs="+", default=[1, 2])
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--out", default=os.path.join(
        ROOT, "chiprun_out", "mimo_controls.json"))
    args = ap.parse_args()
    res = {"config": args.config, "rehearse": args.rehearse,
           "platform": jax.default_backend(),
           "readings": readings(args.config, args.seeds, args.rehearse)}
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(res, f, indent=1)
    print(json.dumps(res))
    return 0


if __name__ == "__main__":
    sys.exit(main())
