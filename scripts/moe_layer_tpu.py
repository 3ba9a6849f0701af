"""Time the routed-expert block ALONE on the chip, the expert layers of
one step chained with their own weights, the routing an ARGUMENT (closed
over, XLA folds the sort into constants). Three shapes, a key each of
`chiprun_out/moe_layer_shapes.json`; the row `rule_<gate / up tile>_<down
tile>` (in `share`: `scatter`) is `models/moe.py: grouped_matmul` itself,
at what `gmm_tiles` gives today, the rows `gmm_...` megablox at the tiles
their names give (k x n of the gate and up calls, then of the down call).

`--shape deepseek` (PR 34): DeepSeek-V2-Lite's widths (64 experts of
2,048 x 1,408, top-6), 8 layers = 8.86 GB of bf16, at a decode shape (128
tokens = 768 routed rows) and a prefill chunk's (512 tokens = 3,072
rows): `jax.lax.ragged_dot` on sorted rows, every expert over every
token (a batched matmul + a weighted sum), PR 34's tilings (ONE (rows,
tk, tn) for all three calls, clamped: 2,048 / 1,024 / 512 / 1,408 of k
by 1,408 or 1,024 of n, 128 / 256 / 512 rows) and, since PR 44, the
rule's (the whole matrices, 2,048 x 1,408 | 1,408 x 2,048) beside a down
call of 1,408 x 1,024.

`--shape xing` (PR 44): Xing4.0-29B-A4B's widths (64 experts of 3,584 x
1,024, top-4), 5 layers = 7.05 GB, at 192 tokens (768 rows) and 512
(2,048 rows): PR 43's tiles (2,048 x 1,024 | 1,024 x 3,072: a ragged
second k tile, a second n tile of 512 real columns), each call repaired
alone, and tiles that divide both widths: 1,792 x 1,024, the whole k
3,584 x 512 or x 256, 896 x 1,024, 1,792 x 512 | down 1,024 x 1,792,
1,024 x 3,584 (the whole n), 1,024 x 896, 1,024 x 512, 512 x 3,584.

`--shape share` (PR 39): a layer that HOLDS A SHARE of the experts, at
MiMo-V2-Flash's widths as one chip of sixteen runs them (16 held of 256
scored, top-8, 4,096 x 2,048, 6 expert layers = 4.83 GB of bf16), at 256
tokens (the decode program: 2,048 pairs, ~128 of them held) and at 512 (a
prefill chunk). The layer's glue at the whole width (gather, weigh and
un-sort all 2,048 rows) against the held pairs a block at a time
(`models/moe.py: block_rows`), the block's rows added to their tokens by
a scatter-add or by a one-hot matmul at float32 precision, and the
layer as the program runs it (`moe_block`, its router included). Since
PR 44 the `scatter` form (the program's) also at PR 36's tiles (2,048 x
1,536 in all three calls), each call repaired alone, and 2,048 x 1,024,
the whole k 4,096 x 512, 1,024 x 2,048, 2,048 x 512 and 2,048 x 2,048
(which runs out of scoped VMEM).

Exits non-zero without a TPU; results in PERF.md section 6 (PRs 34, 39,
44) and `models/moe.py`'s docstring.

    chiprun -- python scripts/moe_layer_tpu.py [--shape deepseek|xing|share|all]
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from dynamo_tpu.models import moe  # noqa: E402
from dynamo_tpu.models.config import get_config  # noqa: E402

PEAK = 819e9

# the shapes whose experts are all held: (expert layers, experts, top-k, D, F,
# tokens of a decode step and of a prefill chunk)
ROUTED = {
    "deepseek": (8, 64, 6, 2048, 1408, (128, 512)),
    "xing": (5, 64, 4, 3584, 1024, (192, 512)),
}


def mega(gate_up, down, rows: int = moe.GMM_ROWS):
    """megablox at the tiles (tk, tn) given for the gate / up calls and for
    the down call (told apart by the call's output type: the layer asks
    float32 of its down call alone), each clamped to the matrix."""
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    def mm3(xs, w, sizes, out_dtype=None):
        tk, tn = gate_up if out_dtype is None else down
        return gmm(xs, w, sizes, preferred_element_type=out_dtype or xs.dtype,
                   tiling=(rows, min(tk, w.shape[1]), min(tn, w.shape[2])))
    return mm3


def ragged(xs, w, sizes, out_dtype=None):
    return jax.lax.ragged_dot(
        xs, w, sizes, preferred_element_type=out_dtype or xs.dtype)


def grouped(mm3, x, w, top_w, top_i):
    """One expert layer over rows sorted by expert, every expert held."""
    (n, d), k, e = x.shape, top_i.shape[1], w[0].shape[0]
    expert_of = top_i.reshape(n * k)
    pair = jnp.arange(n * k, dtype=jnp.int32)
    _, order = jax.lax.sort((expert_of, pair), num_keys=1, is_stable=True)
    sizes = jnp.zeros((e,), jnp.int32).at[expert_of].add(1)
    back = jnp.zeros((n * k,), jnp.int32).at[order].set(pair)
    xs = x[order // k]
    gate, up, down = w
    h = jax.nn.silu(mm3(xs, gate, sizes)) * mm3(xs, up, sizes)
    ys = mm3(h, down, sizes, jnp.float32)
    ys = ys * top_w.reshape(n * k)[order][:, None]
    return ys[back].reshape(n, k, d).sum(1).astype(x.dtype)


def dense(x, w, top_w, top_i):
    gate, up, down = w
    h = jax.nn.silu(jnp.einsum("nd,edf->enf", x, gate)) * jnp.einsum(
        "nd,edf->enf", x, up)
    y = jnp.einsum("enf,efd->end", h, down)
    weight = jnp.sum(jnp.where(
        top_i[..., None] == jnp.arange(gate.shape[0]), top_w[..., None], 0.0),
        axis=1)
    return jnp.einsum("end,ne->nd", y, weight.astype(y.dtype))


def timed(row: dict, step, args, floor_ms: float, ref):
    """Median wall of `step(*args)` over 10 calls into `row`; the output
    (float32) for the next variant to be compared with `ref`."""
    try:
        f = jax.jit(step)
        y = jax.block_until_ready(f(*args))
        if isinstance(y, tuple):    # (output, the layers' mean `stats`)
            y, row["stats_mean"] = y[0], np.asarray(y[1]).tolist()
        times = []
        for _ in range(10):
            t0 = time.perf_counter()
            jax.block_until_ready(f(*args))
            times.append(time.perf_counter() - t0)
        row["ms"] = float(np.median(times)) * 1e3
        row["pct_of_hbm_peak"] = floor_ms / row["ms"] * 100
        y = np.asarray(y, np.float32)
        ref = y if ref is None else ref
        row["max_abs_diff_vs_first"] = float(np.abs(y - ref).max())
    except Exception as e:  # noqa: BLE001 — a tiling Mosaic refuses
        row["error"] = f"{type(e).__name__}: {e}"[:300]
    print(json.dumps(row), flush=True)
    return ref


def tile_variants(candidates) -> dict:
    """name -> megablox at (gate / up tile, down tile)."""
    return {"gmm_%dx%d_%dx%d" % (*gate_up, *down): mega(gate_up, down)
            for gate_up, down in candidates}


def routed(dev, shape: str) -> dict:
    layers, e, k, d, f, token_counts = ROUTED[shape]
    if shape == "deepseek":
        # PR 34's sweep: one (rows, tk, tn) for all three calls, clamped,
        # so its down call ran 1,408 x 1,408 tiles over 2,048 columns
        variants = {"ragged_dot": functools.partial(grouped, ragged),
                    "dense_all_experts": dense}
        for rows, tk, tn in ((128, 1024, 1408), (128, 2048, 1408),
                             (256, 1024, 1408), (128, 512, 1408),
                             (512, 1024, 1408), (128, 1408, 1024)):
            variants["gmm_%d_%d_%d" % (rows, tk, tn)] = functools.partial(
                grouped, mega((tk, tn), (tk, tn), rows))
        tiles = tile_variants([((2048, 1408), (1408, 1024))])
    else:
        # PR 43's tiles first ((2048, 1024) leaves a ragged second k tile,
        # (1024, 3072) a second n tile of 512 real columns), then each call
        # repaired alone, then tiles that divide both widths
        tiles = tile_variants([
            ((2048, 1024), (1024, 3072)), ((1792, 1024), (1024, 3072)),
            ((2048, 1024), (1024, 1792)), ((1792, 1024), (1024, 1792)),
            ((3584, 512), (1024, 1792)), ((3584, 512), (1024, 3584)),
            ((1792, 1024), (1024, 896)), ((896, 1024), (1024, 1792)),
            ((1792, 512), (512, 3584)), ((3584, 256), (1024, 512))])
        variants = {}
    # first the layer's own call, at whatever `gmm_tiles` gives today
    rule = "rule_%dx%d_%dx%d" % (*moe.gmm_tiles(d, f, 2),
                                 *moe.gmm_tiles(f, d, 2))
    variants[rule] = functools.partial(grouped, moe.grouped_matmul)
    variants.update({name: functools.partial(grouped, mm3)
                     for name, mm3 in tiles.items()})

    key = jax.random.PRNGKey(0)
    ws = []
    for i in range(layers):
        k1, k2, k3 = jax.random.split(jax.random.fold_in(key, i), 3)
        ws.append((jax.random.normal(k1, (e, d, f), jnp.bfloat16) * 0.02,
                   jax.random.normal(k2, (e, d, f), jnp.bfloat16) * 0.02,
                   jax.random.normal(k3, (e, f, d), jnp.bfloat16) * 0.02))
    weight_bytes = layers * 3 * e * d * f * 2
    out = {"device": dev.device_kind, "weight_bytes": weight_bytes,
           "floor_ms": weight_bytes / PEAK * 1e3, "rows": []}
    for n in token_counts:
        x = jax.random.normal(key, (n, d), jnp.bfloat16)
        logits = jax.random.normal(jax.random.fold_in(key, 99), (n, e))
        top_w, top_i = jax.lax.top_k(jax.nn.softmax(logits), k)
        top_i = top_i.astype(jnp.int32)
        ref = None
        for name, fn in variants.items():
            # the routing is an ARGUMENT: closed over, XLA folds the sort
            # into constants (PR 39, call 2)
            def step(ws, x, top_w, top_i, fn=fn):
                for w in ws:
                    x = x + fn(x, w, top_w, top_i)
                return x
            row = {"tokens": n, "variant": name,
                   "experts_hit": int(jnp.unique(top_i).size)}
            ref = timed(row, step, (ws, x, top_w, top_i), out["floor_ms"],
                        ref)
            out["rows"].append(row)
    return out


# ------------------------------------------------- a share of the experts

S_LAYERS, S_HELD, S_SCORED, S_K, S_D, S_F = 6, 16, 256, 8, 4096, 2048


def share_layer(form: str, x, w, top_w, top_i, mm3=moe.grouped_matmul):
    """One expert layer over the pairs routed to experts [0, S_HELD):
    `whole` as `moe_block` runs a layer of one block, `scatter` /
    `onehot` a block of `block_rows` rows at a time; `mm3` the grouped
    matmul (the layer's own, or megablox at given tiles)."""
    n, d = x.shape
    m = n * S_K
    expert_of = top_i.reshape(m)
    expert_of = jnp.where(expert_of < S_HELD, expert_of, S_HELD)
    pair = jnp.arange(m, dtype=jnp.int32)
    sorted_expert, order = jax.lax.sort(
        (expert_of, pair), num_keys=1, is_stable=True)
    sizes = jnp.zeros((S_HELD + 1,), jnp.int32).at[expert_of].add(1)[:S_HELD]
    pair_w = top_w.reshape(m)

    def experts(xs, sizes):
        gate, up = mm3(xs, w[0], sizes), mm3(xs, w[1], sizes)
        return mm3(jax.nn.silu(gate) * up, w[2], sizes, jnp.float32)

    if form == "whole":
        ys = experts(x[order // S_K], sizes)
        ys = jnp.where((sorted_expert < S_HELD)[:, None],
                       ys * pair_w[order][:, None], 0.0)
        back = jnp.zeros((m,), jnp.int32).at[order].set(pair)
        return ys[back].reshape(n, S_K, d).sum(1).astype(x.dtype)

    c = moe.block_rows(m, S_HELD, S_SCORED)
    ends = jnp.cumsum(sizes)
    held = ends[-1]

    def block(i, acc):
        start = i * c
        rows = jax.lax.dynamic_slice(order, (start,), (c,))
        token = rows // S_K
        ys = experts(
            x[token], jnp.diff(jnp.clip(ends - start, 0, c), prepend=0))
        real = start + jnp.arange(c) < held
        if form == "scatter":
            ys = jnp.where(real[:, None], ys * pair_w[rows][:, None], 0.0)
            return acc.at[token].add(ys)
        # [N, C] weights, a pair's in its token's row, times the block's
        # rows on the MXU (garbage rows selected out first: NaN x 0)
        ys = jnp.where(real[:, None], ys, 0.0)
        hot = jnp.where(
            (token[None, :] == jnp.arange(n)[:, None]) & real[None, :],
            pair_w[rows][None, :], 0.0)
        return acc + jnp.dot(hot, ys, precision=jax.lax.Precision.HIGHEST)

    return jax.lax.fori_loop(
        0, -(-held // c), block, jnp.zeros((n, d), jnp.float32)
    ).astype(x.dtype)


def share(dev) -> dict:
    cfg = get_config("mimo-v2-flash").with_(
        experts_held=S_HELD, num_experts=S_SCORED, num_experts_per_tok=S_K,
        hidden_size=S_D, moe_intermediate_size=S_F)
    key = jax.random.PRNGKey(0)
    lps = [moe.init_moe_params(cfg, jax.random.fold_in(key, i))
           for i in range(S_LAYERS)]
    ws = [(lp["we_gate"], lp["we_up"], lp["we_down"]) for lp in lps]
    weight_bytes = S_LAYERS * 3 * S_HELD * S_D * S_F * 2
    out = {"device": dev.device_kind, "weight_bytes": weight_bytes,
           "floor_ms": weight_bytes / PEAK * 1e3, "rows": []}
    for n in (256, 512):
        x = jax.random.normal(key, (n, S_D), jnp.bfloat16)
        logits = jax.random.normal(jax.random.fold_in(key, 99), (n, S_SCORED))
        top_w, top_i = jax.lax.top_k(jax.nn.sigmoid(logits), S_K)
        top_w = top_w / top_w.sum(-1, keepdims=True)
        top_i = top_i.astype(jnp.int32)
        held = int((top_i < S_HELD).sum())
        ref = None
        # the three forms at the layer's own tiles, then the form the
        # program runs (`scatter`) at PR 36's tiles ((2048, 1536) leaves a
        # second n tile of 512 real columns, and a third of 1,024 in the
        # down call) and at tiles that divide both widths
        forms = {form: (form, moe.grouped_matmul)
                 for form in ("whole", "scatter", "onehot")}
        forms.update({"scatter_" + name: ("scatter", mm3)
                      for name, mm3 in tile_variants([
                          ((2048, 1536), (2048, 1536)),
                          ((2048, 1024), (2048, 1536)),
                          ((2048, 1536), (2048, 1024)),
                          ((2048, 1024), (2048, 1024)),
                          ((4096, 512), (2048, 1024)),
                          ((1024, 2048), (1024, 2048)),
                          ((2048, 1024), (1024, 2048)),
                          ((2048, 512), (2048, 512)),
                          ((2048, 2048), (2048, 2048))]).items()})
        for name, (form, mm3) in forms.items():
            # the routing is an ARGUMENT: closed over, XLA folds the sort
            # and the blocks' count into constants and unrolls the loop
            def step(ws, x, top_w, top_i, form=form, mm3=mm3):
                for w in ws:
                    x = x + share_layer(form, x, w, top_w, top_i, mm3)
                return x
            row = {"tokens": n, "variant": name, "pairs_held": held,
                   "block_rows": moe.block_rows(n * S_K, S_HELD, S_SCORED)}
            ref = timed(row, step, (ws, x, top_w, top_i), out["floor_ms"],
                        ref)
            out["rows"].append(row)
        # the layer as the program runs it, its own router's pairs (so no
        # output to compare), at the whole width and by blocks
        rows_of = moe.block_rows
        for name, fn in (("moe_block_whole", lambda m, *_: m),
                         ("moe_block", rows_of)):
            def step(lps, x):
                stats = []
                for lp in lps:
                    x = x + moe.moe_block(lp, cfg, x[None], stats=stats)[0]
                return x, jnp.mean(jnp.asarray(stats, jnp.float32), axis=0)
            moe.block_rows = fn
            row = {"tokens": n, "variant": name}
            try:
                timed(row, step, (lps, x), out["floor_ms"], None)
            finally:
                moe.block_rows = rows_of
            out["rows"].append(row)
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--shape", choices=("deepseek", "xing", "share", "all"),
                    default="all")
    args = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"needs a TPU, found {dev.platform}", file=sys.stderr)
        return 3
    path = "chiprun_out/moe_layer_shapes.json"
    out = {}
    if os.path.exists(path):
        with open(path) as f:
            old = json.load(f)
        # PR 34's file was the DeepSeek shape's table itself
        out = {"deepseek": old} if "rows" in old else old
    os.makedirs("chiprun_out", exist_ok=True)
    for shape in ("xing", "share", "deepseek"):
        if args.shape in (shape, "all"):
            out[shape] = share(dev) if shape == "share" else routed(dev, shape)
            with open(path, "w") as f:
                json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
