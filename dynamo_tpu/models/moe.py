"""Sparse mixture-of-experts FFN, drop-free: every routed (token, expert)
pair is computed, at any imbalance, with static shapes.

The reference serves MoE checkpoints (DeepSeek, Mixtral) through its
engines' fused MoE kernels (SURVEY §2.4). Here the layer is four steps,
each under the `jax.named_scope` a profile finds it by:

- `mlp.moe_router`: scores over ALL `num_experts` in float32 (bf16 logits
  flip near-tie top-k membership), softmax or sigmoid (`scoring_func`),
  the k largest, chosen by score + a learned correction bias where the
  configuration has one (`router_bias`, noaux_tc: the bias selects, the
  weights are the scores without it). What happens to the k weights is
  the CONFIGURATION's: renormalised over the selected experts
  (`norm_topk_prob`, Mixtral, MiMo-V2) or used as they are (DeepSeek-V2),
  times `routed_scaling_factor`.
- a layer may hold a SHARE of the experts (`experts_held` from
  `expert_offset`: one chip of an expert-parallel group): pairs routed to
  an expert it does not hold get the sentinel below, and the layer's
  output is its own experts' part of the sum (the group's exchange adds
  the parts up; on one chip nothing stands in for the absent chips).
- `mlp.moe_dispatch`: the N x k pairs sorted by expert (stable, so a
  token's rows keep their order inside an expert) and the tokens gathered
  into that order: rows [0, g0) belong to expert 0, the next g1 to expert
  1, ... Padding rows (bucket pad, inactive decode slots) are given the
  sentinel expert E: they sort last, belong to no group and are computed
  by nobody.
- `mlp.moe_experts`: three grouped matmuls (`grouped_matmul`: rows x
  [E, in, out] under the group sizes) around the SwiGLU: ONE kernel on
  every platform, megablox (`jax.experimental.pallas.ops.tpu.megablox.gmm`),
  which visits an expert's weights once per 128-row tile that holds one
  of its rows; compiled where the program is lowered for a TPU, in the
  pallas interpreter elsewhere (CPU tests run the tiling, the sentinel
  rows and the unwritten tail the chip runs). Its weight tiles are
  `gmm_tiles`'s, a function of the matrix's shape alone: a `tk` that
  DIVIDES k and a `tn` that DIVIDES n, because the kernel (jax 0.9.0's
  `megablox/gmm.py`) runs a ragged last n tile at the whole tile's cost
  (`out_block_spec`, `_store_accum`: the dot, the accumulator and the
  masked store are a full tile's whatever part of it exists) and masks a
  ragged last k tile with a float32 select over both operands
  (`mask_k_rem`, lines 424-431). Timed alone on the v5e
  (`scripts/moe_layer_tpu.py`, PERF.md section 6, PRs 34 and 44), the
  expert layers of one step, decode rows / a 512-token chunk, tiles of
  the gate and up calls | of the down call:
  DeepSeek-V2-Lite (8 layers, 10.8 ms of weights at the HBM peak): one
  tile is the whole matrix, 2,048 x 1,408 | 1,408 x 2,048: 13.5 / 16.3 ms
  (PR 34's sweep gave all three calls one tile, so its down call ran
  1,408 x 1,408: 13.8 / 16.9; 1,024- or 512-deep k tiles and 256 rows
  slower); XLA:TPU's `jax.lax.ragged_dot` 34.9 / 47.3 ms (why it is not
  the layer's form); every expert over every token 12.7 / 28.3.
  Xing4.0 (5 layers, 64 of 3,584 x 1,024, 8.6 ms): PR 43's 2,048 x 1,024 |
  1,024 x 3,072: 12.2 / 14.3 ms; the down call's second n tile, 3,072
  wide for 512 real columns, was 1.0-1.1 ms of that and the gate / up
  calls' ragged k tile (2,048 + 1,536, the mask) NOTHING measurable;
  1,792 x 1,024 | 1,024 x 1,792 (the rule's): 11.1 / 13.1; the whole k,
  3,584 x 512: 11.0 / 12.6; 896-wide tiles 11.1-11.2 / 13.1-13.3.
  MiMo-V2-Flash's share (6 layers, 16 held of 4,096 x 2,048, 5.9 ms, a
  256-row block): PR 36's 2,048 x 1,536 in all three calls: 8.7-8.8 /
  9.8 ms; its cost was the gate / up calls' second n tile (512 real
  columns of 1,536, under two k tiles), the down call's third (1,024 of
  1,536) nothing; 2,048 x 1,024 in all three (the rule's): 8.0-8.2 / 9.0-9.2;
  the whole k, 4,096 x 512: 8.1 / 9.0 in one call and 8.5-8.6 / 9.3-9.5 in
  the next (why `tk` stays within `GMM_K_MOST`).
- `mlp.moe_combine`: each pair's output times its weight, un-sorted back
  to token order, the k rows of a token added up.

The last three steps work the sorted pairs a BLOCK of `C` rows at a time
(`block_rows`, static, from shapes the layer sees: the rows and the share
of the scored experts it holds) and stop after the last block that holds
a real pair. A layer that holds every expert is the case of one block,
`C` = all rows: no loop, the steps as written above. A layer that holds a
share gets `C` = twice the rows an even router would send it, in whole
tiles (one chip of sixteen at top-8: 256 of the decode program's 2,048
pairs, of which ~92 are real), and a device-side loop of `ceil(R / C)`
blocks, R the pairs it holds: a block's tokens gathered, its part of each
group (the groups' running ends clipped to the block), the three grouped
matmuls, weight and select, and its rows ADDED to their tokens' rows of an
[N, D] float32 sum (a scatter-add; the un-sort gather of N x k rows is the
form that cannot shrink). Still drop-free: pairs past `C` are a second,
third ... block's, which re-reads only the weights of the experts that
have rows in it; nothing held, no block. Timed alone on the v5e at
MiMo-V2-Flash's widths, 16 of 256 held, 6 layers
(`scripts/moe_layer_tpu.py --shape share`, PERF.md section 6, PR 39).

Shared experts (`num_shared_experts`, one SwiGLU of that many expert
widths on EVERY token) are `mlp.moe_shared`. Expert weights live as
[E, ...] arrays, sharded P('ep', ...) on a mesh that has the axis.

`stats` (a list the caller passes) receives this layer's load over the
experts it HOLDS as four int32 scalars, (distinct experts with a token,
most tokens on one expert, blocks the pass ran, pairs held = R):
the decode program returns their means with the tokens (engine.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from dynamo_tpu.ops.quant import mm

# rows a grouped-matmul tile holds, the deepest k tile, and the most bytes
# of an expert's weights a tile may take (two such tiles are in flight,
# inside the scoped VMEM a kernel has without asking: 2,048 x 2,048 bf16
# ran out of it). `gmm_tiles` takes the widest tiles within them that
# DIVIDE the matrix: at DeepSeek-V2-Lite's widths the whole matrix (2,048
# x 1,408), at Xing4.0's 1,792 x 1,024 and 1,024 x 1,792, at MiMo-V2-
# Flash's 2,048 x 1,024; the timings are in the module's docstring
GMM_ROWS = 128
GMM_K_MOST = 2048
GMM_WEIGHT_TILE_BYTES = 6 << 20


def init_moe_params(cfg, key, dtype=jnp.bfloat16) -> dict:
    """Per-layer MoE params: router [D, E], expert FFNs [held, D, F] /
    [held, F, D], and the shared experts as one FFN of width S x F.

    A configuration that states the experts it holds (`experts_held`)
    draws each expert from its own key (the layer key folded with the
    expert's id), so a share holds exactly the rows the whole layer
    would: the shares of a layer add up (tests/test_mimo_v2_flash.py)."""
    d, f, e = cfg.hidden_size, cfg.expert_width, cfg.num_experts
    k_router, k_gate, k_up, k_down, k_shared = jax.random.split(key, 5)

    def dense(k, shape, scale):
        return (jax.random.normal(k, shape, jnp.float32) * scale).astype(dtype)

    if cfg.experts_held:
        ids = cfg.expert_offset + jnp.arange(cfg.experts_held)

        def experts(k, shape, scale):
            return jax.vmap(
                lambda i: dense(jax.random.fold_in(k, i), shape, scale)
            )(ids)
    else:
        def experts(k, shape, scale):
            return dense(k, (e, *shape), scale)

    # a checkpoint's experts resemble each other; seeded ones are
    # strangers, so a near-tie at the top-k threshold that bf16 activations
    # decide the other way than float32 (a few in a hundred pairs, any
    # router) exchanges two unrelated outputs. Where a chosen expert weighs
    # much (renormalised and scaled up: ~0.5, not a softmax's ~0.03) those
    # exchanges, not the arithmetic, would be what a comparison with a
    # float32 reference reads, so such a preset STATES a scale for its
    # seeded down-projection (`seed_expert_down_scale`, 1 = fan-in scale,
    # every preset but the Xing4.0 family's)
    down = cfg.seed_expert_down_scale * f ** -0.5
    lp = {
        "router": dense(k_router, (d, e), d ** -0.5),
        "we_gate": experts(k_gate, (d, f), d ** -0.5),
        "we_up": experts(k_up, (d, f), d ** -0.5),
        "we_down": experts(k_down, (f, d), down),
    }
    if cfg.router_bias:
        # seeded so that the selection bias is judged (HF initialises it to
        # zero), at the deviation the preset states
        # (`seed_router_bias_std`): N(0, 0.02) keeps the load near even, as
        # the trained bias of a checkpoint does (at 0.05 the draw starves
        # 2-3 experts of a held share of 16)
        lp["router_bias"] = cfg.seed_router_bias_std * jax.random.normal(
            jax.random.fold_in(k_router, 1), (e,), jnp.float32
        )
    if cfg.num_shared_experts:
        fs = cfg.num_shared_experts * f
        ks = jax.random.split(k_shared, 3)
        lp.update({
            "ws_gate": dense(ks[0], (d, fs), d ** -0.5),
            "ws_up": dense(ks[1], (d, fs), d ** -0.5),
            "ws_down": dense(ks[2], (fs, d), fs ** -0.5),
        })
    return lp


@jax.named_scope("mlp.moe_router")
def route(lp: dict, cfg, xf: jnp.ndarray):
    """xf [N, D] -> (weights [N, k] float32, experts [N, k] int32)."""
    logits = xf.astype(jnp.float32) @ lp["router"].astype(jnp.float32)
    if cfg.scoring_func == "sigmoid":
        probs = jax.nn.sigmoid(logits)
    else:
        probs = jax.nn.softmax(logits, axis=-1)
    if cfg.router_bias:
        # the bias chooses; the weights are the scores without it
        _, top_i = jax.lax.top_k(
            probs + lp["router_bias"], cfg.num_experts_per_tok
        )
        top_w = jnp.take_along_axis(probs, top_i, axis=-1)
    else:
        top_w, top_i = jax.lax.top_k(probs, cfg.num_experts_per_tok)
    if cfg.norm_topk_prob:
        top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
    if cfg.routed_scaling_factor != 1.0:
        top_w = top_w * cfg.routed_scaling_factor
    return top_w, top_i.astype(jnp.int32)


def _dividing_tile(width: int, most: int) -> int:
    """The widest tile of whole 128-lane columns that divides `width` and
    is at most `most`: `width` itself where it fits; where no multiple of
    128 from half of `most` up divides it, `most` in whole 128s (a ragged
    last tile: many thin tiles would cost more than the one)."""
    most = max(most // 128 * 128, 128)
    if width <= most:
        return width
    return next((t for t in range(most, most // 2 - 1, -128)
                 if width % t == 0), most)


def gmm_tiles(k: int, n: int, itemsize: int) -> tuple[int, int]:
    """(tk, tn) of a grouped matmul over [E, k, n] weights, from the
    shape alone: `tk` divides `k` and `tn` divides `n` wherever the widths
    allow it, a tile of weights within `GMM_WEIGHT_TILE_BYTES`."""
    tk = _dividing_tile(k, GMM_K_MOST)
    return tk, _dividing_tile(n, GMM_WEIGHT_TILE_BYTES // (tk * itemsize))


def grouped_matmul(xs, w, group_sizes, out_dtype=None):
    """xs [M, in] (rows sorted by group, M a multiple of GMM_ROWS) x
    w [E, in, out] -> [M, out]: rows [0, g0) times w[0], the next g1 times
    w[1], ... Rows past the groups' end come out as whatever was there
    (the caller selects them out). The megablox kernel: compiled where
    this is lowered for a TPU (a described one too), interpreted on any
    other platform."""
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    kernel = functools.partial(
        gmm, preferred_element_type=out_dtype or xs.dtype,
        tiling=(GMM_ROWS, *gmm_tiles(*w.shape[1:], w.dtype.itemsize)),
    )
    return jax.lax.platform_dependent(
        xs, w, group_sizes, tpu=kernel,
        default=functools.partial(kernel, interpret=True),
    )


def block_rows(m: int, held: int, scored: int) -> int:
    """STATIC rows of the sorted pairs a pass works at a time: all `m`
    where the layer holds every expert the router scores (one block, no
    loop), else twice the rows an even router would send to the held
    share, in whole grouped-matmul tiles."""
    if held == scored:
        return m
    return min(m, -(-2 * m * held // (scored * GMM_ROWS)) * GMM_ROWS)


def _pair_weights(top_w, m: int):
    """The router's weights [N, k] by pair, padded to the `m` rows sorted."""
    return jnp.pad(top_w.reshape(-1), (0, m - top_w.size))


def _experts(lp: dict, xs, group_sizes):
    """The SwiGLU of each row's expert: xs [M, D] sorted by expert ->
    [M, D] float32 (rows past the groups' end: whatever was there)."""
    with jax.named_scope("mlp.moe_experts"):
        gate = grouped_matmul(xs, lp["we_gate"], group_sizes)
        up = grouped_matmul(xs, lp["we_up"], group_sizes)
        return grouped_matmul(
            jax.nn.silu(gate) * up, lp["we_down"], group_sizes, jnp.float32
        )


def moe_block(lp: dict, cfg, x: jnp.ndarray, real_mask=None,
              stats: list | None = None) -> jnp.ndarray:
    """x [B, T, D] -> [B, T, D]; `real_mask` [B, T] bool marks genuine
    tokens (others route nowhere and come out as the shared experts'
    output alone, which no real row reads)."""
    b, t, d = x.shape
    n = b * t
    # `e` experts are HELD here, ids [offset, offset + e) of the
    # `num_experts` the router scores; every other preset holds them all
    e, k = cfg.held_experts, cfg.num_experts_per_tok
    xf = x.reshape(n, d)
    top_w, top_i = route(lp, cfg, xf)

    # the grouped matmul works on whole tiles of rows, the pass on whole
    # blocks of `c` rows: the pairs are padded with sentinel rows
    m = -(-n * k // GMM_ROWS) * GMM_ROWS
    c = block_rows(m, e, cfg.num_experts)
    m = -(-m // c) * c

    with jax.named_scope("mlp.moe_dispatch"):
        expert_of = top_i.reshape(n * k)
        if e != cfg.num_experts:
            # a pair routed to an expert another chip holds: the sentinel
            expert_of = expert_of - cfg.expert_offset
            expert_of = jnp.where(
                (expert_of >= 0) & (expert_of < e), expert_of, e
            )
        if real_mask is not None:
            expert_of = jnp.where(
                jnp.repeat(real_mask.reshape(n), k), expert_of, e
            )
        expert_of = jnp.pad(expert_of, (0, m - n * k), constant_values=e)
        pair = jnp.arange(m, dtype=jnp.int32)
        sorted_expert, order = jax.lax.sort(
            (expert_of, pair), num_keys=1, is_stable=True
        )
        # rows per expert; the sentinel's bin is cut off
        group_sizes = jnp.zeros((e + 1,), jnp.int32).at[expert_of].add(1)[:e]
    if stats is not None:
        held = jnp.sum(group_sizes)
        stats.append((jnp.sum(group_sizes > 0), jnp.max(group_sizes),
                      -(-held // c) if c < m else 1, held))

    if c < m:
        out = _blocked(lp, xf, top_w, c, order, group_sizes)
    else:
        with jax.named_scope("mlp.moe_dispatch"):
            xs = xf[jnp.minimum(order // k, n - 1)]          # [M, D]
        ys = _experts(lp, xs, group_sizes)                   # [M, D]
        with jax.named_scope("mlp.moe_combine"):
            # a row past the groups' end holds whatever the grouped matmul
            # left there: selected out, never multiplied by a zero
            w_sorted = _pair_weights(top_w, m)[order]
            ys = jnp.where(
                (sorted_expert < e)[:, None], ys * w_sorted[:, None], 0.0
            )
            back = jnp.zeros((m,), jnp.int32).at[order].set(pair)
            out = ys[back[: n * k]].reshape(n, k, d).sum(axis=1)
    out = out.astype(x.dtype)

    if cfg.num_shared_experts:
        with jax.named_scope("mlp.moe_shared"):
            hidden = jax.nn.silu(mm(xf, lp["ws_gate"])) * mm(xf, lp["ws_up"])
            out = out + mm(hidden, lp["ws_down"])
    return out.reshape(b, t, d)


def _blocked(lp: dict, xf, top_w, c: int, order, group_sizes):
    """The held pairs, rows [0, R) of the sorted order, `c` rows a block:
    ceil(R / c) blocks on the device's own count, each gathered, run
    through its experts, weighted and added to its tokens' rows of an
    [N, D] float32 sum. A block past the first re-reads only the weights
    of the experts that have rows in it."""
    (n, d), k, m = xf.shape, top_w.shape[1], order.shape[0]
    with jax.named_scope("mlp.moe_dispatch"):
        pair_w = _pair_weights(top_w, m)
        ends = jnp.cumsum(group_sizes)      # running ends of the groups
        held = ends[-1]

    def block(i, acc):
        start = i * c
        with jax.named_scope("mlp.moe_dispatch"):
            rows = jax.lax.dynamic_slice(order, (start,), (c,))
            token = jnp.minimum(rows // k, n - 1)
            xs = xf[token]                                   # [C, D]
            # the block's part of each group: its ends clipped to [0, C]
            sizes = jnp.diff(jnp.clip(ends - start, 0, c), prepend=0)
        ys = _experts(lp, xs, sizes)
        with jax.named_scope("mlp.moe_combine"):
            # as above: a row past the last held pair is selected out
            ys = jnp.where(
                (start + jnp.arange(c) < held)[:, None],
                ys * pair_w[rows][:, None], 0.0,
            )
            return acc.at[token].add(ys)

    with jax.named_scope("mlp.moe_experts"):
        return jax.lax.fori_loop(
            0, -(-held // c), block, jnp.zeros((n, d), jnp.float32)
        )
