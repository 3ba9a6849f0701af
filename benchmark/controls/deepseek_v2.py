"""The control behind `deepseek-v2-lite-l9`'s tolerance: the float32
reference (`references/deepseek_v2.py`) computed at a precision BELOW the
one the configuration states, put in the program's place and judged by the
harness's own comparison (`lib/reference.compare`) under the
configuration's own limits. A limit is sound while the served program
passes it and these do not (PERF.md section 6, PR 34 has the readings).

    python3 benchmark/controls/deepseek_v2.py [--config <name>] [--seeds 1 2]
        [--rehearse] [--out chiprun_out/lowprec.json]

Controls, each the whole reference with ONE thing lowered:

- `int8_weights`: every matrix rounded to int8 with one scale per output
  channel (what `--quantization int8` holds), the nearest precision below
  the bf16 the configuration serves;
- `fp8_weights`: the same in float8 e4m3;
- `int8_latent`: the cached row rounded to int8 as a quantized latent pool
  would hold it, one scale a token for the 512 latent values and one for
  the 64 rope values; keys and values are expanded from the rounded row;
- `bf16_softmax`: scores, softmax and the probabilities in bfloat16.

What is judged, as the harness judges a served run: 16 positions after
each of the configuration's `check_prompts`, weights from the seed as the
engine makes them (`llama.init_params`), prompts random. The judged token
at a position is the float32 reference's most likely one (a greedy server
emits that; a random token sits ~5 nats lower, where every gap reads
larger). It is a sum on the host's or the chip's float32 units, no time:
it may run on the CPU (~12 min a seed at the published widths, in ~25 GB)
or through the chip tool.
"""

from __future__ import annotations

import argparse
import functools
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

CONTROLS = ("int8_weights", "fp8_weights", "int8_latent", "bf16_softmax")


def rounded_weight(kind: str):
    """`_f32` of the reference with the matrix rounded on the way."""
    def f(w):
        w = w.astype(jnp.float32)
        if w.ndim < 2:
            return w
        amax = jnp.max(jnp.abs(w), axis=-2, keepdims=True) + 1e-30
        if kind == "fp8":
            s = amax / 448.0
            return (w / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
        s = amax / 127.0
        return jnp.round(w / s) * s
    return f


def _int8_rows(x):
    s = jnp.max(jnp.abs(x), axis=-1, keepdims=True) / 127.0 + 1e-30
    return jnp.round(x / s) * s


def lowered_attention(ref, *, int8_latent: bool, bf16_softmax: bool):
    """The reference's `_attention`, line for line, with the cached row
    and / or the softmax lowered."""
    @functools.partial(jax.jit, static_argnames=(
        "heads", "nope", "rope", "vd", "rank", "eps", "scale"))
    def attention(x, lp, cos, sin, *, heads, nope, rope, vd, rank, eps,
                  scale):
        t = x.shape[0]
        h = ref._rms_norm(x, lp["attn_norm"], eps)
        q = (h @ ref._f32(lp["wq"])).reshape(t, heads, nope + rope)
        kva = h @ ref._f32(lp["w_kva"])
        c = ref._rms_norm(kva[:, :rank], lp["kv_norm"], eps)
        q_r = ref._rope_pairs(q[..., nope:], cos, sin)
        k_r = ref._rope_pairs(kva[:, None, rank:], cos, sin)
        if int8_latent:
            c, k_r = _int8_rows(c), _int8_rows(k_r)
        kv = (c @ ref._f32(lp["w_kvb"])).reshape(t, heads, nope + vd)
        k = jnp.concatenate(
            [kv[..., :nope], jnp.broadcast_to(k_r, (t, heads, rope))],
            axis=-1)
        qf = jnp.concatenate([q[..., :nope], q_r], axis=-1)
        scores = jnp.einsum("thd,shd->hts", qf, k) * scale
        causal = jnp.tril(jnp.ones((t, t), bool))
        scores = jnp.where(causal[None], scores, -jnp.inf)
        if bf16_softmax:
            probs = jax.nn.softmax(scores.astype(jnp.bfloat16), axis=-1)
            probs = probs.astype(jnp.float32)
        else:
            probs = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("hts,shv->thv", probs, kv[..., nope:])
        return x + out.reshape(t, heads * vd) @ ref._f32(lp["wo"])
    return attention


def lowered(ref_path: str, control: str):
    """A fresh load of the reference's file with `control` applied: its
    functions find `_f32` / `_attention` in their module at trace time,
    and a module of its own holds no other control's traces."""
    spec = importlib.util.spec_from_file_location("lowered_" + control,
                                                  ref_path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    if control.endswith("_weights"):
        mod._f32 = rounded_weight(control.split("_")[0])
    elif control in ("int8_latent", "bf16_softmax"):
        mod._attention = lowered_attention(
            mod, int8_latent=control == "int8_latent",
            bf16_softmax=control == "bf16_softmax")
    elif control != "float32":
        raise ValueError(f"unknown control {control!r}")
    return mod


def readings(config: str, seeds, rehearse: bool = False,
             controls=CONTROLS) -> dict:
    """{control: {seed: compare(...)}} under the configuration's limits."""
    from dynamo_tpu.models import llama
    from dynamo_tpu.models.config import ModelConfig

    hf, bench = harness.split_config(
        harness.load_json(BENCH, "configs", config + ".json"), config)
    prompts = bench["check_prompts"]
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
        pads = [-(-len(s) // 128) * 128 for s in seqs]
        base_mod = lowered(ref_path, "float32")
        base, judged = [], []
        for s, pad in zip(seqs, pads):
            rows = base_mod.logprob_rows(params, hf, s, n, pad)
            tok = jnp.argmax(rows, axis=-1)
            judged.append(tok)
            base.append(np.asarray(
                jnp.take_along_axis(rows, tok[:, None], axis=1)[:, 0],
                np.float64))
        for control in controls:
            mod = lowered(ref_path, control)
            low = []
            for s, pad, tok in zip(seqs, pads, judged):
                rows = mod.logprob_rows(params, hf, s, n, pad)
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
    ap.add_argument("--config", default="deepseek-v2-lite-l9")
    ap.add_argument("--seeds", type=int, nargs="+", default=[1, 2])
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--out", default=os.path.join(
        ROOT, "chiprun_out", "lowprec.json"))
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
