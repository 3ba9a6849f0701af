"""Time the paged latent decode kernel ALONE on the chip, at the
`deepseek-v2-lite-l9.decode-wide` cell's shape: 128 rows, contexts as the
cell draws them (prompt 128-512 + a uniform share of an answer of
1,024-1,536), 9 chained calls (one a layer = its share of a decode step),
bf16 rows of 640 lanes in pages of 128. Variants: pages per work item and
DMA ring depth. Exits non-zero without a TPU; results go to
`chiprun_out/mla_kernel_cell_shape.json` (PERF.md section 6, PR 34).

    chiprun -- python scripts/mla_kernel_tpu.py
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

from dynamo_tpu.ops.pallas_mla import mla_paged_decode_attention  # noqa: E402

LAYERS, ROWS, HEADS, RANK, WIDTH, PAGE = 9, 128, 16, 512, 640, 128
VALUES = 576  # of the 640 lanes
PEAK = 819e9


def main() -> int:
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"needs a TPU, found {dev.platform}", file=sys.stderr)
        return 3
    rng = np.random.RandomState(34)
    lengths = (rng.randint(128, 513, ROWS)
               + (rng.rand(ROWS) * rng.randint(1024, 1537, ROWS)).astype(int))
    pages_of = -(-lengths // PAGE)
    num_pages = int(pages_of.sum()) + 1
    tables = np.zeros((ROWS, 4096 // PAGE), np.int32)
    nxt = 1
    for i, n in enumerate(pages_of):
        tables[i, :n] = np.arange(nxt, nxt + n)
        nxt += n
    key = jax.random.PRNGKey(0)
    pools = [jax.random.normal(jax.random.fold_in(key, i),
                               (num_pages * PAGE, WIDTH), jnp.bfloat16)
             for i in range(LAYERS)]
    qa = jax.random.normal(key, (ROWS, HEADS, WIDTH), jnp.bfloat16) * 0.05
    new = jax.random.normal(key, (ROWS, WIDTH), jnp.bfloat16)
    args = (jnp.asarray(tables), jnp.asarray(lengths, jnp.int32),
            jnp.asarray(lengths - 1, jnp.int32))
    need = float(lengths.sum()) * LAYERS * VALUES * 2
    out = {"device": dev.device_kind, "rows": ROWS,
           "resident_tokens": int(lengths.sum()),
           "needed_bytes_a_step": need, "variants": []}
    for ppb, nbuf in ((4, 4), (2, 4), (8, 4), (4, 2), (4, 8), (1, 8)):
        def step(pools, qa, ppb=ppb, nbuf=nbuf):
            outs = []
            for pool in pools:
                o, pool = mla_paged_decode_attention(
                    qa, new, pool, *args, rank=RANK, page_size=PAGE,
                    pages_per_block=ppb, nbuf=nbuf)
                qa = qa + o[..., :1].astype(qa.dtype) * 0  # chain the calls
                outs.append(pool)
            return outs, qa

        fn = jax.jit(step, donate_argnums=(0,))
        try:
            pools, _ = fn(pools, qa)
            jax.block_until_ready(pools)
            times = []
            for _ in range(20):
                t0 = time.perf_counter()
                pools, q2 = fn(pools, qa)
                jax.block_until_ready((pools, q2))
                times.append(time.perf_counter() - t0)
            med = float(np.median(times))
            row = {"pages_per_block": ppb, "nbuf": nbuf, "step_ms": med * 1e3,
                   "roofline_pct": need / PEAK / med * 100}
        except Exception as e:  # noqa: BLE001 — a variant Mosaic refuses
            row = {"pages_per_block": ppb, "nbuf": nbuf,
                   "error": f"{type(e).__name__}: {e}"[:300]}
        print(json.dumps(row), flush=True)
        out["variants"].append(row)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/mla_kernel_cell_shape.json", "w") as f:
        json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
