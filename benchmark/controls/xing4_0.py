"""The controls behind `xing4.0-29b-a4b-l6`'s tolerance: the float32
reference (`references/xing4_0.py`) with ONE thing changed, put in the
program's place and judged by the harness's own comparison
(`lib/reference.compare`) under the configuration's own limits. A limit is
sound while the served program passes it and every one of these does not
(the configuration's `tolerance.why` has the readings).

    python3 benchmark/controls/xing4_0.py [--config <name>] [--seeds 1 2]
        [--controls int8_weights ...] [--rehearse]
        [--out chiprun_out/xing_controls.json]

- `int8_weights`: every matrix rounded to int8 with one scale per output
  channel, the nearest precision below the bf16 the configuration serves;
- the mechanism, each a program that got one piece of it wrong:
  `h_res_identity` (the streams are not mixed: H_res = I), `row_softmax`
  (Sinkhorn replaced by one row normalisation: rows sum to 1, columns do
  not), `no_phi` (every map is its bias: nothing depends on the input),
  `h_post_1` (H_post without its factor 2), `no_q_norm` (the norm between
  the two query matrices dropped), `no_router_bias` (the selection bias
  dropped), `no_shared` (the shared expert dropped), `routed_scale_1`
  (routed weights x 1 for x 2).

What is judged, as the harness judges a served run: 16 positions after
each of the configuration's `check_prompts`, weights from the seed as the
engine makes them (`llama.init_params`), prompts random; the judged token
at a position is the float32 reference's most likely one (a greedy server
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

CONTROLS = (
    "int8_weights", "h_res_identity", "row_softmax", "no_phi", "h_post_1",
    "no_q_norm", "no_router_bias", "no_shared", "routed_scale_1",
)


def _int8_weight(w):
    """`_f32` of the reference with a matrix rounded on the way."""
    w = w.astype(jnp.float32)
    if w.ndim < 2:
        return w
    s = (jnp.max(jnp.abs(w), axis=-2, keepdims=True) + 1e-30) / 127.0
    return jnp.round(w / s) * s


def changed(ref_path: str, control: str):
    """A fresh load of the reference's file with `control` applied: its
    jitted functions find what they call in their module at trace time,
    and a module of its own holds no other control's traces."""
    spec = importlib.util.spec_from_file_location("changed_" + control,
                                                  ref_path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    maps, route = mod._hc_maps, mod._route

    def with_maps(edit):
        def hc_maps(X, hp, **kw):
            return edit(*maps(X, hp, **kw))
        mod._hc_maps = hc_maps

    if control == "int8_weights":
        mod._f32 = _int8_weight
    elif control == "h_res_identity":
        with_maps(lambda pre, post, res: (pre, post, jnp.broadcast_to(
            jnp.eye(res.shape[-1], dtype=res.dtype), res.shape)))
    elif control == "row_softmax":
        mod._sinkhorn = lambda m, iters, eps: m / (
            jnp.sum(m, axis=-1, keepdims=True) + eps)
    elif control == "no_phi":
        mod._hc_maps = lambda X, hp, **kw: maps(
            X, {**hp, "phi": jnp.zeros_like(hp["phi"])}, **kw)
    elif control == "h_post_1":
        with_maps(lambda pre, post, res: (pre, 0.5 * post, res))
    elif control == "no_q_norm":
        mod._query_latent = lambda h, lp, eps: h @ mod._f32(lp["w_qa"])
    elif control == "no_router_bias":
        mod._route = lambda h, lp, **kw: route(
            h, {**lp, "router_bias": jnp.zeros_like(lp["router_bias"])}, **kw)
    elif control == "no_shared":
        mod._shared = lambda h, lp: jnp.zeros_like(h)
    elif control == "routed_scale_1":
        mod._route = lambda h, lp, *, k, renorm, factor: route(
            h, lp, k=k, renorm=renorm, factor=1.0)
    elif control != "float32":
        raise ValueError(f"unknown control {control!r}")
    return mod


def readings(config: str, seeds, rehearse: bool = False,
             controls=CONTROLS, n_prompts: int | None = None) -> dict:
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
            tok = jnp.argmax(rows, axis=-1)
            judged.append(tok)
            base.append(np.asarray(
                jnp.take_along_axis(rows, tok[:, None], axis=1)[:, 0],
                np.float64))
        for control in controls:
            mod = changed(ref_path, control)
            got = []
            for s, tok in zip(seqs, judged):
                rows = mod.logprob_rows(params, hf, s, n, pad)
                got.append(np.asarray(
                    jnp.take_along_axis(rows, tok[:, None], axis=1)[:, 0]))
            out[control][str(seed)] = compare(got, base, bench["tolerance"])
            harness.log(f"control {control} seed {seed}: "
                        f"{out[control][str(seed)]}")
            del mod
            jax.clear_caches()
        del params
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="xing4.0-29b-a4b-l6")
    ap.add_argument("--seeds", type=int, nargs="+", default=[1, 2])
    ap.add_argument("--controls", nargs="+", default=list(CONTROLS),
                    choices=CONTROLS)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--out", default=os.path.join(
        ROOT, "chiprun_out", "xing_controls.json"))
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
