"""Time the paged latent decode kernel ALONE on the chip, at the shape of
one of the two cells that run it: a row's context as `decode-wide` draws
it (prompt 128-512 + a uniform share of an answer of 1,024-1,536), one
chained call a layer = the kernel's share of a decode step, bf16 rows of
640 lanes in pages of 128.

    --shape deepseek   `deepseek-v2-lite-l9.decode-wide`: 9 layers, 16
                       heads, 128 rows, all live
    --shape xing       `xing4.0-29b-a4b-l6.decode-wide`: 6 layers, 32
                       heads, decode width 256 with 178 live rows, the
                       rest at length 0

Timed: the kernel as shipped, `nomerge` (the fused cache update skipped:
no merge, no write-back; the outputs differ in the new row only, it is a
timing) and `nocompute` (an item's copies and waits only), each at 1 and
at 8 steps a call (8 = the cell's `decode_steps`; a call holds ~1 ms of
launch and operand copies that a step in the cell does not pay), and the
shipped kernel at other pages per work item / ring depths. Lengths,
tables, write positions, queries and new rows are ARGUMENTS of the timed
function (closed over they fold into constants: ROADMAP B0). Each
`--kernel-file <another tree's ops/pallas_mla.py>` is timed first on the
same inputs (as shipped), and its outputs and pools are compared with this
tree's bit for bit. Exits non-zero without a TPU; results go to
`chiprun_out/mla_kernel_<shape>.json` (PERF.md section 6, PRs 34 and 45).
`--rehearse` walks the same code at a tiny size in interpret mode on a
CPU: no time there means anything.

    chiprun -- python scripts/mla_kernel_tpu.py --shape deepseek
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import dynamo_tpu.ops.pallas_mla as this_tree  # noqa: E402

RANK, WIDTH, PAGE = 512, 640, 128
VALUES = 576  # of the 640 lanes
PEAK = 819e9
# layers, heads, decode width, live rows
SHAPES = {"deepseek": (9, 16, 128, 128), "xing": (6, 32, 256, 178)}


def draw(rng, width: int, live: int, page: int, scale: int):
    """Lengths (the new token included) and block tables: `live` rows with
    the cell's contexts (divided by `scale` in a rehearsal), the rest at
    length 0 with every table entry on the trash page."""
    lengths = np.zeros(width, np.int64)
    lengths[:live] = (
        rng.randint(128, 513, live)
        + (rng.rand(live) * rng.randint(1024, 1537, live)).astype(int)
    ) // scale
    pages_of = -(-lengths // page)
    tables = np.zeros((width, 4096 // scale // page), np.int32)
    nxt = 1
    for i, n in enumerate(pages_of):
        tables[i, :n] = np.arange(nxt, nxt + n)
        nxt += n
    return lengths, tables, nxt


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--shape", choices=sorted(SHAPES), default="deepseek")
    ap.add_argument("--kernel-file", action="append", default=[])
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--rehearse", action="store_true")
    opts = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not opts.rehearse:
        print(f"needs a TPU, found {dev.platform}", file=sys.stderr)
        return 3
    layers, heads, width, live = SHAPES[opts.shape]
    rank, lanes, page, scale, reps = RANK, WIDTH, PAGE, 1, opts.reps
    dtype = jnp.bfloat16
    if opts.rehearse:
        layers, heads, width, live = 2, 4, 8, 5
        rank, lanes, page, scale, reps = 32, 128, 32, 8, 1
    lengths, tables, num_pages = draw(
        np.random.RandomState(34), width, live, page, scale)
    key = jax.random.PRNGKey(0)

    def fresh_pools():
        return [jax.random.normal(jax.random.fold_in(key, i),
                                  (num_pages * page, lanes), dtype)
                for i in range(layers)]

    qa = jax.random.normal(key, (width, heads, lanes), dtype) * 0.05
    new = jax.random.normal(jax.random.fold_in(key, 99), (width, lanes), dtype)
    ctx = (jnp.asarray(tables), jnp.asarray(lengths, jnp.int32),
           jnp.asarray(np.where(lengths > 0, lengths - 1, -1), jnp.int32))
    need = float(lengths.sum()) * layers * VALUES * 2

    def one_step(mod, pools, qa, new, ctx, outs=None, **static):
        nxt = []
        for pool in pools:
            o, pool = mod.mla_paged_decode_attention(
                qa, new, pool, *ctx, rank=rank, page_size=page,
                interpret=opts.rehearse, **static)
            qa = qa + o[..., :1].astype(qa.dtype) * 0  # chain the calls
            nxt.append(pool)
            if outs is not None:
                outs.append(o)
        return nxt, qa

    def check(mod, **static):
        """One step from fresh pools: every layer's output and pool."""
        def step(pools, qa, new, ctx):
            outs = []
            pools, _ = one_step(mod, pools, qa, new, ctx, outs, **static)
            return jnp.stack(outs), pools

        return jax.block_until_ready(jax.jit(step)(fresh_pools(), qa, new, ctx))

    def timed(mod, steps, **static):
        def call(pools, qa, new, ctx):
            def body(_, carry):
                return one_step(mod, *carry, new, ctx, **static)

            pools, qa = jax.lax.fori_loop(0, steps, body, (pools, qa))
            return pools, qa

        fn = jax.jit(call, donate_argnums=(0,))
        pools, _ = fn(fresh_pools(), qa, new, ctx)
        jax.block_until_ready(pools)
        times = []
        for _ in range(reps):
            t0 = time.perf_counter()
            pools, q2 = fn(pools, qa, new, ctx)
            jax.block_until_ready((pools, q2))
            times.append(time.perf_counter() - t0)
        return float(np.median(times)) / steps

    mods = []
    for i, path in enumerate(opts.kernel_file):
        spec = importlib.util.spec_from_file_location(f"mla_other{i}", path)
        other = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(other)
        mods.append((path, other, [("shipped", {})]))
    mods.append(("this", this_tree, [
        ("shipped", {}), ("nomerge", {"ablate": "nomerge"}),
        ("nocompute", {"ablate": "nocompute"}),
        *((f"pages {ppb} ring {nbuf}", {"pages_per_block": ppb, "nbuf": nbuf})
          for ppb, nbuf in ((2, 4), (8, 4), (4, 2), (4, 8), (1, 8))),
    ]))
    out = {"device": dev.device_kind, "shape": opts.shape, "layers": layers,
           "heads": heads, "rows": width, "live_rows": live,
           "resident_tokens": int(lengths.sum()),
           "work_items_a_layer": int((-(-lengths // (4 * page))).sum()),
           "needed_bytes_a_step": need, "variants": []}
    for tree, mod, variants in mods:
        for name, static in variants:
            row = {"tree": tree, "variant": name}
            for steps in (1, 8):
                if steps == 8 and static.keys() & {"nbuf", "pages_per_block"}:
                    continue
                try:
                    sec = timed(mod, steps, **static)
                    row[f"step_ms_at_{steps}_a_call"] = sec * 1e3
                    row[f"roofline_pct_at_{steps}"] = need / PEAK / sec * 100
                except Exception as e:  # noqa: BLE001 — Mosaic refuses it
                    row["error"] = f"{type(e).__name__}: {e}"[:300]
            print(json.dumps(row), flush=True)
            out["variants"].append(row)
    # what each tree, and the `nomerge` timing, computes: against this
    # tree's shipped kernel, from the same pools
    want_o, want_pools = check(this_tree)
    others = [(tree, mod, {}) for tree, mod, _ in mods[:-1]]
    others.append(("this nomerge", this_tree, {"ablate": "nomerge"}))
    for tree, mod, static in others:
        got_o, got_pools = check(mod, **static)
        row = {"tree": tree, "against": "this shipped",
               "outputs_max_abs_diff": float(jnp.max(jnp.abs(got_o - want_o))),
               "pool_rows_that_differ": int(sum(
                   jnp.sum(jnp.any(g != w, axis=-1))
                   for g, w in zip(got_pools, want_pools)))}
        print(json.dumps(row), flush=True)
        out.setdefault("compared", []).append(row)
        del got_o, got_pools
    os.makedirs("chiprun_out", exist_ok=True)
    with open(f"chiprun_out/mla_kernel_{opts.shape}.json", "w") as f:
        json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
