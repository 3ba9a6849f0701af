"""Time a Mamba-2 mixer ALONE on the chip, at the
`granite-4.0-h-micro.reason-wide` cell's decode shape: 128 rows of one
token, 12 layers chained (each with its own weights and its own two state
pools of 129 slots, bf16), 8 steps a call, with 128 / 124 / 64 of the rows
live (the others do not advance: `real` False). Two forms of the span
between the `w_in` and `w_out` matmuls: `xla`, the plain `jax.numpy` span
that is the CPU's lowering (`ops/pallas_ssm.py: reference_update`), and
`kernel`, the one pallas pass the TPU runs. For each: ms a layer, and that
time's share of the HBM peak on the live rows' state bytes (read + written)
and on all the bytes a layer must move (with the two weights). The two forms' outputs, states and
tails after one step are compared on the chip. Exits non-zero without a
TPU; results go to `chiprun_out/ssm_layer.json` (PERF.md section 6, PRs 41
and 42).

    chiprun -- python scripts/ssm_layer_tpu.py
"""

from __future__ import annotations

import functools
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from dynamo_tpu.models import mamba2  # noqa: E402
from dynamo_tpu.models.config import MAMBA, PRESETS  # noqa: E402
from dynamo_tpu.ops import pallas_ssm  # noqa: E402

LAYERS, ROWS, STEPS, RUNS = 12, 128, 8, 10
PEAK = 819e9
CFG = PRESETS["granite-4.0-h-micro"]
KERNEL = mamba2.ssm_state_update


def build(key):
    one = CFG.with_(layer_kinds=(MAMBA,), num_layers=1)
    layers = []
    for i in range(LAYERS):
        k = jax.random.fold_in(key, i)
        ssm, conv = mamba2.init_state_pools(one, ROWS + 1, jnp.bfloat16)
        layers.append((
            mamba2.init_mamba_params(CFG, k, jnp.bfloat16),
            jax.random.normal(k, ssm[0].shape, jnp.bfloat16),
            jax.random.normal(k, conv[0].shape, jnp.bfloat16),
        ))
    return layers


def layers(update, params, pools, u, real):
    """One decode step of the chained layers."""
    mamba2.ssm_state_update = update  # read when the step is traced
    fresh = jnp.zeros(ROWS, bool)
    out = []
    for lp, (ssm, conv) in zip(params, pools):
        y, ssm, conv = mamba2.mamba_mixer(
            lp, CFG, u, ssm, conv, None, real, fresh)
        u = u + y                                    # chain the layers
        out.append((ssm, conv))
    return out, u


def steps(update, params, pools, u, real):
    """STEPS decode steps in one program, as a tick's decode dispatch is
    (one call's dispatch is then ~1% of its time, not ~15%)."""
    def one(carry, _):
        pools, u = layers(update, params, *carry, real)
        return (pools, u * 0.5), None

    (pools, u), _ = jax.lax.scan(one, (pools, u), None, length=STEPS)
    return pools, u


def form_of(name: str):
    return pallas_ssm.reference_update if name == "xla" else KERNEL


def fresh_pools(built):
    return [(jnp.copy(s), jnp.copy(c)) for _, s, c in built]


def run(form: str, built, u, live: int):
    params = [lp for lp, _, _ in built]
    pools = fresh_pools(built)
    real = (jnp.arange(ROWS) < live)[:, None]
    fn = jax.jit(functools.partial(steps, form_of(form)), donate_argnums=(1,))
    pools, out = fn(params, pools, u, real)
    jax.block_until_ready((pools, out))
    times = []
    for _ in range(RUNS):
        t0 = time.perf_counter()
        pools, out = fn(params, pools, u, real)
        jax.block_until_ready((pools, out))
        times.append(time.perf_counter() - t0)
    ms = float(np.median(times)) * 1e3 / LAYERS / STEPS
    state = live * 2 * mamba2.state_bytes_per_slot(CFG, 2) // 36
    weights = 2 * (params[0]["w_in"].size + params[0]["w_out"].size)
    return {
        "form": form, "layers": LAYERS, "steps_a_call": STEPS,
        "rows_live": live, "ms_a_layer": ms, "state_bytes_a_layer": state,
        "weight_bytes_a_layer": weights,
        "state_pct_of_peak": 100 * state / (ms * 1e-3) / PEAK,
        "all_bytes_pct_of_peak": 100 * (state + weights) / (ms * 1e-3) / PEAK,
        "finite": bool(jnp.isfinite(out.astype(jnp.float32)).all()),
    }


def agree(built, u, live: int) -> dict:
    """The two forms after ONE step of the chain from the same pools: the
    FIRST layer's state and tails (the one layer both forms enter with the
    same input: a later layer's differs by the roundings of the output rows
    before it, and over more steps this chain of seeded mixers, which
    nothing damps, carries a flipped rounding to the first digit), the last
    layer's output rows, and the rows that do not advance, every layer."""
    params = [lp for lp, _, _ in built]
    real = (jnp.arange(ROWS) < live)[:, None]
    got = {}
    for form in ("xla", "kernel"):
        fn = jax.jit(functools.partial(layers, form_of(form)))
        got[form] = fn(params, fresh_pools(built), u, real)
    (px, ox), (pk, ok) = got["xla"], got["kernel"]
    ox, ok = (np.asarray(o, np.float32) for o in (ox, ok))
    return {
        "rows_live": live,
        "first_layer_state_share_unequal": float(
            (px[0][0] != pk[0][0]).mean()),
        "first_layer_tails_equal": bool((px[0][1] == pk[0][1]).all()),
        "later_layers_state_share_unequal": max(
            float((a[0] != b[0]).mean()) for a, b in zip(px[1:], pk[1:])),
        "out_max_abs_diff": float(np.abs(ox - ok).max()),
        "out_abs_max": float(np.abs(ox).max()),
        "unadvanced_rows_kept": all(
            bool((k[i][live:] == b[1 + i][live:]).all())
            for k, b in zip(pk, built) for i in (0, 1)),
    }


def main() -> int:
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"needs a TPU, found {dev.platform}", file=sys.stderr)
        return 3
    key = jax.random.PRNGKey(42)
    built = build(key)
    u = jax.random.normal(key, (ROWS, 1, CFG.hidden_size), jnp.bfloat16)
    result = {"device": dev.device_kind, "rows": ROWS, "state": [],
              "agree": agree(built, u, 124)}
    print(json.dumps(result["agree"]), flush=True)
    for live in (128, 124, 64):
        for form in ("xla", "kernel"):
            result["state"].append(run(form, built, u, live))
            print(json.dumps(result["state"][-1]), flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/ssm_layer.json", "w") as f:
        json.dump(result, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
