"""Time the paged decode kernel ALONE on the chip at the
`mimo-v2-flash-l7.reason-wide` cell's shape: 192 live rows in the 256-wide
program, contexts as the cell draws them (prompt 128-768 log-uniform + a
uniform share of an answer of 1,024-2,048: 206k resident tokens), 64 heads
over 192-wide keys and 128-wide values in bf16 pages of 128; chained calls, one a layer = the
kind's share of a decode step: 2 full-attention layers (4 KV heads: pools
of 768 / 512 lanes, the whole context) and 5 window layers (8 KV heads:
1,536 / 1,024 lanes, a 128-token window from a start a row, a sink a head).
Variants: the per-sequence work list (`group` null: the rule of every
full-attention layer, and of a window layer before PR 37) with its
`nocompute` (copies, waits and the loop) and `empty` (launch, operand
copies and the prologue) ablations, and the grouped window item at 1 / 2 /
4 / 8 sequences an item over ring depths, with the same ablations at the
committed group and with a whole page sent back by the fused write.
`check` compares one window layer's output and written pools under both
rules on the chip.
Exits non-zero without a TPU; results go to
`chiprun_out/hybrid_kernel_cell_shape.json` (PERF.md section 6, PRs 36-37).

    chiprun -- python scripts/hybrid_kernel_tpu.py
"""

from __future__ import annotations

import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from dynamo_tpu.ops import pallas_attention  # noqa: E402
from dynamo_tpu.ops.pallas_attention import (  # noqa: E402
    WINDOW_GROUP,
    fused_paged_decode_attention,
)

WIDTH, LIVE, HEADS, KD, VD, PAGE, WINDOW = 256, 192, 64, 192, 128, 128, 128
KINDS = {"full": (2, 4), "window": (5, 8)}   # layers, KV heads
PEAK = 819e9


def window_args(lens, key, group):
    """A window layer's arguments: the per-sequence list takes the starts
    alone (`group` None), the grouped item the static window."""
    sink = jax.random.normal(key, (HEADS,), jnp.float32)
    if group is None:
        return dict(starts=jnp.maximum(lens - WINDOW, 0), sink=sink)
    return dict(window=WINDOW, window_group=group, sink=sink)


def check(q, lens, wpos, tables, num_pages, key):
    """One window layer under both rules on the same pools: the largest
    difference of the outputs, and whether the written pools are equal."""
    kh = KINDS["window"][1]
    kp = jax.random.normal(key, (num_pages * PAGE, kh * KD), jnp.bfloat16)
    vp = jax.random.normal(key, (num_pages * PAGE, kh * VD), jnp.bfloat16)
    nk = jax.random.normal(key, (WIDTH, kh * KD), jnp.bfloat16)
    nv = jax.random.normal(key, (WIDTH, kh * VD), jnp.bfloat16)
    res = [
        fused_paged_decode_attention(
            q, nk, nv, kp, vp, jnp.asarray(tables), lens, wpos,
            page_size=PAGE, **window_args(lens, key, group))
        for group in (None, WINDOW_GROUP)
    ]
    (o0, k0, v0), (o1, k1, v1) = res
    diff = jnp.abs(o0.astype(jnp.float32) - o1.astype(jnp.float32))
    return {"group": WINDOW_GROUP, "out_max_abs_diff": float(diff.max()),
            "out_finite": bool(jnp.isfinite(o1.astype(jnp.float32)).all()),
            "out_abs_mean": float(jnp.abs(o0.astype(jnp.float32)).mean()),
            "pools_equal": bool((k0 == k1).all() & (v0 == v1).all()),
            "rows_written": int((k1 != kp).any(axis=1).sum())}


def main() -> int:
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"needs a TPU, found {dev.platform}", file=sys.stderr)
        return 3
    rng = np.random.RandomState(36)
    lengths = np.zeros(WIDTH, np.int64)
    lengths[:LIVE] = (
        np.exp(rng.uniform(np.log(128), np.log(768), LIVE)).astype(int)
        + (rng.rand(LIVE) * rng.randint(1024, 2049, LIVE)).astype(int))
    pages_of = -(-lengths // PAGE)
    num_pages = int(pages_of.sum()) + 1
    tables = np.zeros((WIDTH, 4096 // PAGE), np.int32)
    nxt = 1
    for i, n in enumerate(pages_of):
        tables[i, :n] = np.arange(nxt, nxt + n)
        nxt += n
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (WIDTH, HEADS, KD), jnp.bfloat16) * 0.3
    lens = jnp.asarray(lengths, jnp.int32)
    wpos = jnp.where(lens > 0, lens - 1, -1)
    out = {"device": dev.device_kind, "rows": LIVE, "width": WIDTH,
           "resident_tokens": int(lengths.sum()), "variants": []}
    # (kind, sequences an item: None = the per-sequence list, ring depth,
    # ablation, rows a fused write sends back: 0 = the committed slab,
    # scoped-VMEM MiB where the default is too little)
    cases = [("full", None, 4, "", 0, 0), ("window", None, 4, "", 0, 0),
             ("window", None, 4, "nocompute", 0, 0),
             ("window", None, 4, "empty", 0, 0),
             ("window", 1, 4, "", 0, 0), ("window", 2, 4, "", 0, 0),
             ("window", 4, 2, "", 0, 0), ("window", 4, 4, "", 0, 0),
             ("window", 8, 2, "", 0, 96),
             ("window", WINDOW_GROUP, 4, "", PAGE, 0),
             ("window", WINDOW_GROUP, 4, "nocompute", 0, 0),
             ("window", WINDOW_GROUP, 4, "empty", 0, 0)]
    out["check"] = check(q, lens, wpos, tables, num_pages, key)
    print(json.dumps(out["check"]), flush=True)
    slab = pallas_attention.WRITE_BACK_ROWS
    for kind, group, nbuf, ablate, wb_rows, vmem_mib in cases:
        # module constants the kernel reads as it is traced
        jax.clear_caches()
        pallas_attention.DECODE_VMEM_LIMIT = (vmem_mib or 64) << 20
        pallas_attention.WRITE_BACK_ROWS = wb_rows or slab
        layers, kh = KINDS[kind]
        win = kind == "window"
        attended = np.minimum(lengths, WINDOW) if win else lengths
        need = float(attended.sum()) * layers * kh * (KD + VD) * 2
        kp = [jax.random.normal(jax.random.fold_in(key, i),
                                (num_pages * PAGE, kh * KD), jnp.bfloat16)
              for i in range(layers)]
        vp = [jax.random.normal(jax.random.fold_in(key, 100 + i),
                                (num_pages * PAGE, kh * VD), jnp.bfloat16)
              for i in range(layers)]
        nk = jax.random.normal(key, (WIDTH, kh * KD), jnp.bfloat16)
        nv = jax.random.normal(key, (WIDTH, kh * VD), jnp.bfloat16)
        extra = window_args(lens, key, group) if win else {}
        extra.update(nbuf=nbuf, ablate=ablate)

        def step(kp, vp, q, extra=extra, nk=nk, nv=nv):
            ko, vo = [], []
            for k, v in zip(kp, vp):
                o, k, v = fused_paged_decode_attention(
                    q, nk, nv, k, v, jnp.asarray(tables), lens, wpos,
                    page_size=PAGE, **extra)
                # chain the calls through the query
                q = q + jnp.pad(o, ((0, 0), (0, 0), (0, KD - VD)))[..., :1] * 0
                ko.append(k)
                vo.append(v)
            return ko, vo, q

        fn = jax.jit(step, donate_argnums=(0, 1))
        try:
            kp, vp, _ = fn(kp, vp, q)
            jax.block_until_ready(kp)
            times = []
            for _ in range(20):
                t0 = time.perf_counter()
                kp, vp, q2 = fn(kp, vp, q)
                jax.block_until_ready((kp, vp, q2))
                times.append(time.perf_counter() - t0)
            med = float(np.median(times))
            row = {"kind": kind, "layers": layers, "group": group,
                   "nbuf": nbuf, "ablate": ablate,
                   "write_back_rows": min(wb_rows or slab, PAGE) if group else PAGE,
                   "ms": med * 1e3,
                   "needed_bytes": need,
                   "roofline_pct": need / PEAK / med * 100}
        except Exception as e:  # noqa: BLE001 — a variant Mosaic refuses
            row = {"kind": kind, "group": group, "nbuf": nbuf,
                   "ablate": ablate, "error": f"{type(e).__name__}: {e}"[:300]}
        print(json.dumps(row), flush=True)
        out["variants"].append(row)
        del kp, vp
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/hybrid_kernel_cell_shape.json", "w") as f:
        json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
