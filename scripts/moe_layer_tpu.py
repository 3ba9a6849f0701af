"""Time the routed-expert block ALONE on the chip at DeepSeek-V2-Lite's
widths (64 experts of 2,048 x 1,408, top-6), 8 expert layers chained with
their own weights (= their share of a step: 8.86 GB of bf16 to stream),
at a decode shape (128 tokens = 768 routed rows) and a prefill chunk's
(512 tokens = 3,072 rows). Variants: `jax.lax.ragged_dot` on sorted rows,
the megablox grouped-matmul kernel on sorted rows at several tilings, and
every expert over every token (batched matmul + a weighted sum over the
experts). Exits non-zero without a TPU; results go to
`chiprun_out/moe_layer_shapes.json` (PERF.md section 6, PR 34).

    chiprun -- python scripts/moe_layer_tpu.py
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

LAYERS, E, K, D, F = 8, 64, 6, 2048, 1408
PEAK = 819e9


def sort_rows(x, top_i):
    n = x.shape[0]
    expert_of = top_i.reshape(n * K)
    pair = jnp.arange(n * K, dtype=jnp.int32)
    _, order = jax.lax.sort((expert_of, pair), num_keys=1, is_stable=True)
    sizes = jnp.zeros((E,), jnp.int32).at[expert_of].add(1)
    back = jnp.zeros((n * K,), jnp.int32).at[order].set(pair)
    return x[order // K], sizes, order, back


def grouped(mm3, x, w, top_w, top_i):
    n = x.shape[0]
    xs, sizes, order, back = sort_rows(x, top_i)
    gate, up, down = w
    h = jax.nn.silu(mm3(xs, gate, sizes)) * mm3(xs, up, sizes)
    ys = mm3(h.astype(x.dtype), down, sizes).astype(jnp.float32)
    ys = ys * top_w.reshape(n * K)[order][:, None]
    return ys[back].reshape(n, K, D).sum(1).astype(x.dtype)


def ragged(xs, w, sizes):
    return jax.lax.ragged_dot(xs, w, sizes)


def dense(x, w, top_w, top_i):
    gate, up, down = w
    h = jax.nn.silu(jnp.einsum("nd,edf->enf", x, gate)) * jnp.einsum(
        "nd,edf->enf", x, up)
    y = jnp.einsum("enf,efd->end", h, down)
    weight = jnp.sum(jnp.where(
        top_i[..., None] == jnp.arange(E), top_w[..., None], 0.0), axis=1)
    return jnp.einsum("end,ne->nd", y, weight.astype(y.dtype))


def main() -> int:
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"needs a TPU, found {dev.platform}", file=sys.stderr)
        return 3
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    def mega(tiling):
        def mm3(xs, w, sizes):
            tm, tk, tn = tiling
            tk, tn = min(tk, w.shape[1]), min(tn, w.shape[2])
            return gmm(xs, w, sizes, preferred_element_type=xs.dtype,
                       tiling=(tm, tk, tn))
        return mm3

    variants = {"ragged_dot": functools.partial(grouped, ragged),
                "dense_all_experts": dense}
    for tiling in ((128, 1024, 1408), (128, 2048, 1408), (256, 1024, 1408),
                   (128, 512, 1408), (512, 1024, 1408), (128, 1408, 1024)):
        variants["gmm_%d_%d_%d" % tiling] = functools.partial(
            grouped, mega(tiling))

    key = jax.random.PRNGKey(0)
    ws = []
    for i in range(LAYERS):
        k1, k2, k3 = jax.random.split(jax.random.fold_in(key, i), 3)
        ws.append((jax.random.normal(k1, (E, D, F), jnp.bfloat16) * 0.02,
                   jax.random.normal(k2, (E, D, F), jnp.bfloat16) * 0.02,
                   jax.random.normal(k3, (E, F, D), jnp.bfloat16) * 0.02))
    weight_bytes = LAYERS * 3 * E * D * F * 2
    out = {"device": dev.device_kind, "weight_bytes": weight_bytes,
           "floor_ms": weight_bytes / PEAK * 1e3, "rows": []}
    for n in (128, 512):
        x = jax.random.normal(key, (n, D), jnp.bfloat16)
        logits = jax.random.normal(jax.random.fold_in(key, 99), (n, E))
        top_w, top_i = jax.lax.top_k(jax.nn.softmax(logits), K)
        ref = None
        for name, fn in variants.items():
            def step(ws, x, fn=fn):
                for w in ws:
                    x = x + fn(x, w, top_w, top_i.astype(jnp.int32))
                return x
            row = {"tokens": n, "variant": name}
            try:
                f = jax.jit(step)
                y = jax.block_until_ready(f(ws, x))
                times = []
                for _ in range(10):
                    t0 = time.perf_counter()
                    jax.block_until_ready(f(ws, x))
                    times.append(time.perf_counter() - t0)
                row["ms"] = float(np.median(times)) * 1e3
                row["pct_of_hbm_peak"] = out["floor_ms"] / row["ms"] * 100
                y = np.asarray(y, np.float32)
                if ref is None:
                    ref = y
                row["max_abs_diff_vs_first"] = float(np.abs(y - ref).max())
            except Exception as e:  # noqa: BLE001 — a tiling Mosaic refuses
                row["error"] = f"{type(e).__name__}: {e}"[:300]
            print(json.dumps(row), flush=True)
            out["rows"].append(row)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/moe_layer_shapes.json", "w") as f:
        json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
