"""Time the block pass's attention kernel ALONE on the chip, at the shape of
the cell that runs it (`sdar-30b-a3b-l6.decode-wide`: decode width 256 with
188 live rows, a row's context as the cell draws it, prompt 128-512 + a
uniform share of an answer of 1,024-1,536, its block of 4 positions at the
end; 4 KV heads x 8 query heads x 128, bf16, pages of 128, 32 table
columns), one chained call a layer = the kernel's share of a pass.

Timed on the same inputs: `flash_prefill_attention` under `mask_block` 4
(the read of a block pass before PR 48, still that of every other ragged
call), `block_paged_attention` as shipped, its ablations `nocompute` (an
item's copies and waits only) and `empty` (launch, operand copies and the
ring's first fill), and the shipped kernel at other pages per work item /
ring depths; each at 1 pass a call and at 9 (the cell's `decode_steps`: a
call holds launch and operand copies that a pass in the cell does not
pay). Tables, positions, lengths and queries are ARGUMENTS of the timed
function (closed over they fold into constants: ROADMAP B0). Both kernels'
outputs are then compared on the chip with the gather oracle in float32 at
`highest` matmul precision, beside that oracle's own rounding to bf16.
Exits non-zero without a TPU; one JSON line a variant, all of them in
`chiprun_out/block_kernel.json` (PERF.md section 6, PR 48). `--rehearse`
walks the same code at a tiny size in interpret mode on a CPU: no time
there means anything.

    chiprun -- python scripts/block_kernel_tpu.py
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from dynamo_tpu.ops.attention import paged_attention  # noqa: E402
from dynamo_tpu.ops.pallas_block import block_paged_attention  # noqa: E402
from dynamo_tpu.ops.pallas_prefill import flash_prefill_attention  # noqa: E402

PEAK = 819e9
BLOCK = 4


def draw(rng, width: int, live: int, page: int, scale: int):
    """First positions of the rows' open blocks and block tables: `live`
    rows with the cell's contexts (divided by `scale` in a rehearsal) cut
    to whole blocks, the rest padding rows with every table entry on the
    trash page."""
    pos0 = np.zeros(width, np.int64)
    pos0[:live] = (
        rng.randint(128, 513, live)
        + (rng.rand(live) * rng.randint(1024, 1537, live)).astype(int)
    ) // scale // BLOCK * BLOCK
    q_lens = np.where(np.arange(width) < live, BLOCK, 0)
    pages_of = np.where(q_lens > 0, -(-(pos0 + BLOCK) // page), 0)
    tables = np.zeros((width, 4096 // scale // page), np.int32)
    nxt = 1
    for i, n in enumerate(pages_of):
        tables[i, :n] = np.arange(nxt, nxt + n)
        nxt += n
    return pos0, q_lens, tables, nxt


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--seed", type=int, default=48)
    ap.add_argument("--rehearse", action="store_true")
    opts = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not opts.rehearse:
        print(f"needs a TPU, found {dev.platform}", file=sys.stderr)
        return 3
    layers, width, live, kh, g, hd = 6, 256, 188, 4, 8, 128
    page, scale, reps, dtype = 128, 1, opts.reps, jnp.bfloat16
    if opts.rehearse:
        layers, width, live, kh, g, hd = 2, 8, 5, 2, 2, 16
        page, scale, reps = 8, 32, 1
    pos0, q_lens, tables, num_pages = draw(
        np.random.RandomState(opts.seed), width, live, page, scale)
    resident = int(np.where(q_lens > 0, pos0 + BLOCK, 0).sum())
    key = jax.random.PRNGKey(opts.seed)

    def pools(salt):
        return [jax.random.normal(jax.random.fold_in(key, salt + i),
                                  (num_pages * page, kh * hd), dtype)
                for i in range(layers)]

    k_pools, v_pools = pools(0), pools(100)
    q = jax.random.normal(
        jax.random.fold_in(key, 999), (width, BLOCK, kh * g, hd), dtype)
    ctx = (jnp.asarray(tables), jnp.asarray(pos0, jnp.int32),
           jnp.asarray(q_lens, jnp.int32))
    # what a pass must move: every resident token's keys and values once a
    # layer, each live row's block of queries in and outputs out
    need = layers * 2 * kh * hd * 2 * (resident + live * BLOCK * g)

    kernels = {
        "flash_prefill": lambda q, k, v, ctx, **static: (
            flash_prefill_attention(
                q, k, v, *ctx, page_size=page, mask_block=BLOCK,
                interpret=opts.rehearse, **static)),
        "block": lambda q, k, v, ctx, **static: block_paged_attention(
            q, k, v, *ctx, page_size=page, mask_block=BLOCK,
            interpret=opts.rehearse, **static),
    }

    def one_pass(kernel, q, k_pools, v_pools, ctx, outs=None, **static):
        for k, v in zip(k_pools, v_pools):
            o = kernel(q, k, v, ctx, **static)
            q = q + o * 0  # chain the calls
            if outs is not None:
                outs.append(o)
        return q

    def timed(kernel, passes, **static):
        def call(q, k_pools, v_pools, ctx):
            return jax.lax.fori_loop(
                0, passes,
                lambda _, q: one_pass(
                    kernel, q, k_pools, v_pools, ctx, **static), q)

        fn = jax.jit(call)
        jax.block_until_ready(fn(q, k_pools, v_pools, ctx))
        times = []
        for _ in range(reps):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(q, k_pools, v_pools, ctx))
            times.append(time.perf_counter() - t0)
        return float(np.median(times)) / passes

    variants = [
        ("flash_prefill", "shipped", {}),
        ("block", "shipped", {}),
        ("block", "nocompute", {"ablate": "nocompute"}),
        ("block", "empty", {"ablate": "empty"}),
        *(("block", f"pages {ppb} ring {nbuf}",
           {"pages_per_block": ppb, "nbuf": nbuf})
          for ppb, nbuf in ((2, 4), (8, 4), (4, 2), (4, 8))),
    ]
    out = {"device": dev.device_kind, "layers": layers, "rows": width,
           "live_rows": live, "resident_tokens": resident,
           "work_items_a_layer": int(np.where(
               q_lens > 0, -(-(pos0 + BLOCK) // (4 * page)), 0).sum()),
           "needed_bytes_a_pass": need, "variants": []}
    for kernel, name, static in variants:
        row = {"kernel": kernel, "variant": name}
        for passes in (1, 9):
            if passes == 9 and static.keys() & {"nbuf", "pages_per_block"}:
                continue
            try:
                sec = timed(kernels[kernel], passes, **static)
                row[f"pass_ms_at_{passes}_a_call"] = sec * 1e3
                row[f"roofline_pct_at_{passes}"] = need / PEAK / sec * 100
            except Exception as e:  # noqa: BLE001 — Mosaic refuses it
                row["error"] = f"{type(e).__name__}: {e}"[:300]
        print(json.dumps(row), flush=True)
        out["variants"].append(row)

    # what each kernel computes, against the gather oracle in float32 on
    # the first layer's pools (the live rows: a padding row gives zeros;
    # `highest`: XLA's own float32 matmuls round their operands to bf16
    # on a TPU otherwise, the probabilities among them). `rounded` is the
    # oracle's output in the kernels' dtype: what no kernel can be under
    smat = (ctx[0][:, :, None] * page + jnp.arange(page)).reshape(width, -1)
    with jax.default_matmul_precision("highest"):
        want = paged_attention(
            q.astype(jnp.float32), k_pools[0].astype(jnp.float32),
            v_pools[0].astype(jnp.float32), smat,
            ctx[1][:, None] + jnp.arange(BLOCK)[None], q_lens=ctx[2],
            mask_block=BLOCK)
    kernels["rounded"] = lambda *_: want.astype(dtype)
    for kernel in ("flash_prefill", "block", "rounded"):
        got = jax.jit(kernels[kernel])(q, k_pools[0], v_pools[0], ctx)
        err = jnp.abs(got.astype(jnp.float32) - want)
        row = {"kernel": kernel, "against": "gather oracle, float32",
               "max_abs_diff": float(jnp.max(err)),
               "mean_abs_diff": float(jnp.mean(err[:live])),
               "padding_rows_max_abs": float(jnp.max(jnp.abs(
                   got[live:].astype(jnp.float32)), initial=0.0))}
        print(json.dumps(row), flush=True)
        out.setdefault("compared", []).append(row)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/block_kernel.json", "w") as f:
        json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
