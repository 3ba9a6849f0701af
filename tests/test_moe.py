"""Sparse MoE, drop-free (models/moe.py): block oracle match for each
published router (renormalised top-k, softmax scores as they are, scaled,
beside shared experts), no token dropped at any imbalance, padding rows,
sharded forward equivalence on the ep axis, engine e2e serving."""

from __future__ import annotations

import jax
import jax.numpy as jnp

import numpy as np
import pytest

from dynamo_tpu.models import llama
from dynamo_tpu.models.config import get_config
from dynamo_tpu.models.moe import init_moe_params, moe_block
from dynamo_tpu.parallel import mesh as meshmod

CFG = get_config("tiny-moe").with_(dtype="float32")


def moe_oracle(lp, cfg, x):
    """Per-token loop: route to the top-k experts, weighted SwiGLU sum
    (weights renormalised or as they are, by the configuration), plus the
    shared experts on every token."""
    b, t, d = x.shape
    out = np.zeros((b, t, d), np.float32)
    w32 = {k: np.asarray(v, np.float32) for k, v in lp.items()}

    def swiglu(h, gate, up, down):
        g = h @ gate
        return ((g / (1 + np.exp(-g))) * (h @ up)) @ down

    for bi in range(b):
        for ti in range(t):
            h = np.asarray(x[bi, ti], np.float32)
            logits = h @ w32["router"]
            probs = np.exp(logits - logits.max())
            probs /= probs.sum()
            top = np.argsort(-probs, kind="stable")[: cfg.num_experts_per_tok]
            w = probs[top]
            if cfg.norm_topk_prob:
                w = w / w.sum()
            w = w * cfg.routed_scaling_factor
            for wi, e in zip(w, top):
                out[bi, ti] += wi * swiglu(
                    h, w32["we_gate"][e], w32["we_up"][e], w32["we_down"][e])
            if cfg.num_shared_experts:
                out[bi, ti] += swiglu(
                    h, w32["ws_gate"], w32["ws_up"], w32["ws_down"])
    return out


# the router as each family publishes it: Mixtral renormalises its top-2;
# DeepSeek-V2 uses the softmax scores as they are, beside shared experts
ROUTERS = {
    "renormalised": CFG,
    "as-is": CFG.with_(norm_topk_prob=False),
    "as-is-scaled-shared": CFG.with_(
        norm_topk_prob=False, routed_scaling_factor=2.5, num_shared_experts=2,
        moe_intermediate_size=32),
}


def _layer(cfg, seed=0):
    return init_moe_params(cfg, jax.random.PRNGKey(seed), dtype=jnp.float32)


@pytest.mark.parametrize("router", sorted(ROUTERS))
def test_moe_block_matches_oracle(router):
    cfg = ROUTERS[router]
    lp = _layer(cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, cfg.hidden_size))
    got = np.asarray(moe_block(lp, cfg, x))
    ref = moe_oracle(lp, cfg, np.asarray(x))
    np.testing.assert_allclose(got, ref, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("routing", ["uniform", "one-expert"])
def test_no_token_is_dropped_at_any_imbalance(routing):
    """Drop-free: every routed (token, expert) pair is computed, whether
    the tokens spread evenly or ALL of them pick the same expert first
    (a capacity-bounded layer drops most of them there)."""
    cfg = ROUTERS["as-is"]
    lp = _layer(cfg)
    n = 64
    x = jax.random.normal(jax.random.PRNGKey(2), (1, n, cfg.hidden_size))
    if routing == "one-expert":
        # positive inputs on a huge router column: expert 0 wins every row
        lp["router"] = lp["router"].at[:, 0].set(100.0)
        x = jnp.abs(x)
    if routing == "uniform":
        # a router of zeros scores every expert alike: top-k takes the
        # first k for every token, each with weight 1/E
        lp["router"] = jnp.zeros_like(lp["router"])
    stats = []
    got = np.asarray(moe_block(lp, cfg, x, stats=stats))
    ref = moe_oracle(lp, cfg, np.asarray(x))
    np.testing.assert_allclose(got, ref, rtol=2e-4, atol=2e-4)
    (hit, load_max, blocks, held), = stats
    assert int(load_max) == n  # every token reached its first expert
    # a layer that holds all its experts: one block, every pair held
    assert (int(blocks), int(held)) == (1, n * cfg.num_experts_per_tok)
    assert int(hit) == cfg.num_experts_per_tok if routing == "uniform" \
        else 2 <= int(hit) <= cfg.num_experts


@pytest.mark.parametrize("k,n,tiles", [
    (64, 256, (64, 256)),
    # a k of two whole tiles and an n of two, within a budget made small:
    # the accumulation across k and the second n tile the chip runs
    (256, 512, (128, 256)),
], ids=["one-tile", "two-k-tiles-two-n-tiles"])
def test_grouped_matmul_kernel_matches_ragged_dot(monkeypatch, k, n, tiles):
    """The layer's grouped matmul (megablox; off a TPU it runs in the
    pallas interpreter) against `jax.lax.ragged_dot` on rows sorted by
    group: uneven groups, an empty one, and rows past the groups' end
    (which nobody reads)."""
    from dynamo_tpu.models import moe

    monkeypatch.setattr(moe, "GMM_K_MOST", 128)
    monkeypatch.setattr(moe, "GMM_WEIGHT_TILE_BYTES", 128 * 256 * 4)
    assert moe.gmm_tiles(k, n, 4) == tiles
    rng = np.random.RandomState(0)
    xs = jnp.asarray(rng.randn(2 * moe.GMM_ROWS, k).astype(np.float32))
    w = jnp.asarray(rng.randn(4, k, n).astype(np.float32))
    sizes = jnp.asarray([70, 0, 129, 31], jnp.int32)
    used = int(sizes.sum())
    got = moe.grouped_matmul(xs, w, sizes)
    want = jax.lax.ragged_dot(xs, w, sizes)
    np.testing.assert_allclose(
        np.asarray(got)[:used], np.asarray(want)[:used], rtol=1e-5, atol=1e-4)


def _tiles_before_pr44(k: int, n: int, itemsize: int):
    """The choice `grouped_matmul` made until PR 44: 2,048 of k, and of n
    what 6 MiB leave in whole 128s, whether or not they divide."""
    tk = min(k, 2048)
    return tk, min(n, max((6 << 20) // (tk * itemsize) // 128 * 128, 128))


@pytest.mark.parametrize("call", ["gate", "up", "down"])
@pytest.mark.parametrize("name", [
    "deepseek-v2-lite", "mimo-v2-flash", "xing4.0-29b-a4b", "tiny-moe"])
def test_grouped_matmul_tiles_divide_the_published_widths(name, call):
    """The tile rule at each configuration's three grouped matmuls (bf16
    as served): DeepSeek-V2-Lite's tiles are literally the ones it had,
    the whole matrices (its programs are the parent's); MiMo's and Xing's
    divide both widths in whole 128-lane columns inside the byte budget,
    so no call masks a ragged k tile or multiplies an n tile for a
    fraction of its columns; a tiny preset keeps its choice."""
    from dynamo_tpu.models.moe import (
        GMM_K_MOST, GMM_WEIGHT_TILE_BYTES, gmm_tiles)

    cfg = get_config(name)
    d, f = cfg.hidden_size, cfg.expert_width
    k, n = (f, d) if call == "down" else (d, f)
    tk, tn = gmm_tiles(k, n, 2)
    before = _tiles_before_pr44(k, n, 2)
    if name == "deepseek-v2-lite":
        assert (tk, tn) == before == (k, n)
    elif name == "tiny-moe":
        assert (tk, tn) == before
    else:
        assert (tk, tn) != before
        assert k % tk == 0 and n % tn == 0 and tk % 128 == 0 and tn % 128 == 0
        assert tk <= GMM_K_MOST and tk * tn * 2 <= GMM_WEIGHT_TILE_BYTES


def test_padding_rows_route_nowhere_and_change_no_real_row():
    """Rows outside `real_mask` (bucket pad, inactive decode slots) belong
    to no expert: the real rows come out as if the pads were not there,
    whatever the pads hold, and the load counts real rows only."""
    cfg = ROUTERS["as-is-scaled-shared"]
    lp = _layer(cfg)
    n, real = 48, 20
    x = jax.random.normal(jax.random.PRNGKey(2), (1, n, cfg.hidden_size))
    mask = (jnp.arange(n) % 3 == 0)[None, :] & (jnp.arange(n) < 3 * real)
    stats = []
    out = np.asarray(moe_block(lp, cfg, x, real_mask=mask, stats=stats))
    ref = moe_oracle(lp, cfg, np.asarray(x))
    keep = np.asarray(mask[0])
    np.testing.assert_allclose(out[0, keep], ref[0, keep], rtol=2e-4, atol=2e-4)
    # other values in the pad rows, the same real rows
    x2 = jnp.where(mask[..., None], x, 1e3 * x + 7.0)
    out2 = np.asarray(moe_block(lp, cfg, x2, real_mask=mask))
    np.testing.assert_array_equal(out2[0, keep], out[0, keep])
    # a pad row carries the shared experts' output alone
    pad_only = moe_oracle(
        {k: v for k, v in lp.items()},
        cfg.with_(num_experts_per_tok=0), np.asarray(x))
    np.testing.assert_allclose(
        out[0, ~keep], pad_only[0, ~keep], rtol=2e-4, atol=2e-4)
    assert int(stats[0][1]) <= int(keep.sum())


def test_sharded_forward_matches_single_device():
    """Full tiny-moe forward on an ep=2 x tp=2 x dp=2 mesh must match the
    unsharded forward (GSPMD all-to-alls change nothing numerically)."""
    rng = np.random.RandomState(0)
    b, t, page = 2, 16, 8
    params = llama.init_params(CFG, jax.random.PRNGKey(0), dtype=jnp.float32)
    tokens = rng.randint(1, CFG.vocab_size, (b, t)).astype(np.int32)
    positions = np.tile(np.arange(t, dtype=np.int32), (b, 1))
    wslots = np.concatenate(
        [np.arange(page * (1 + 4 * i), page * (1 + 4 * i) + t) for i in range(b)]
    ).astype(np.int32)
    smat = np.stack(
        [np.arange(page * (1 + 4 * i), page * (1 + 4 * i) + t) for i in range(b)]
    ).astype(np.int32)

    kv = llama.init_kv_cache(CFG, 256, dtype=jnp.float32)
    ref, _ = llama.forward(
        params, CFG, jnp.asarray(tokens), jnp.asarray(positions), kv,
        jnp.asarray(wslots), jnp.asarray(smat),
    )

    mc = meshmod.MeshConfig(ep=2, tp=2, dp=2)
    meshmod.validate_model_mesh(CFG, mc)
    mesh = meshmod.build_mesh(mc, jax.devices()[:8])
    sharded = meshmod.shard_params(params, CFG, mesh)
    kv2 = llama.init_kv_cache(CFG, 256, dtype=jnp.float32)
    with jax.set_mesh(mesh):
        got, _ = jax.jit(llama.forward, static_argnums=(1,))(
            sharded, CFG, jnp.asarray(tokens), jnp.asarray(positions), kv2,
            jnp.asarray(wslots), jnp.asarray(smat),
        )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(ref), rtol=2e-4, atol=2e-4
    )


def test_mesh_rejects_bad_ep():
    try:
        meshmod.validate_model_mesh(CFG, meshmod.MeshConfig(ep=3))
        raise AssertionError("expected ValueError")
    except ValueError as e:
        assert "num_experts" in str(e)


async def test_engine_serves_moe_model():
    from .test_engine import collect, greedy_request, make_engine

    engine = make_engine(model=CFG)
    prompt = [5, 17, 42, 9]
    tokens, finish, _ = await collect(engine, greedy_request(prompt, max_tokens=6))
    assert len(tokens) == 6 and finish == "length"
    # determinism across a fresh engine (routing is stable)
    engine2 = make_engine(model=CFG)
    tokens2, _, _ = await collect(engine2, greedy_request(prompt, max_tokens=6))
    assert tokens2 == tokens
    await engine.close()
    await engine2.close()


def test_mixtral_weight_loading(tmp_path):
    """HF mixtral-style safetensors (block_sparse_moe.*) load into the
    stacked [E, ...] expert params and produce the same forward as
    directly-constructed params."""
    import torch
    from safetensors.torch import save_file

    cfg = CFG.with_(num_layers=1)
    params = llama.init_params(cfg, jax.random.PRNGKey(3), dtype=jnp.float32)
    lp = params["layers"][0]
    sd = {
        "model.embed_tokens.weight": torch.from_numpy(
            np.asarray(params["embed"])
        ),
        "model.norm.weight": torch.from_numpy(np.asarray(params["final_norm"])),
        "model.layers.0.input_layernorm.weight": torch.from_numpy(
            np.asarray(lp["attn_norm"])
        ),
        "model.layers.0.post_attention_layernorm.weight": torch.from_numpy(
            np.asarray(lp["mlp_norm"])
        ),
        "model.layers.0.block_sparse_moe.gate.weight": torch.from_numpy(
            np.ascontiguousarray(np.asarray(lp["router"]).T)
        ),
    }
    for our, hf in (("wq", "q_proj"), ("wk", "k_proj"), ("wv", "v_proj"),
                    ("wo", "o_proj")):
        sd[f"model.layers.0.self_attn.{hf}.weight"] = torch.from_numpy(
            np.ascontiguousarray(np.asarray(lp[our]).T)
        )
    for our, hf in (("we_gate", "w1"), ("we_up", "w3"), ("we_down", "w2")):
        for e in range(cfg.num_experts):
            sd[f"model.layers.0.block_sparse_moe.experts.{e}.{hf}.weight"] = (
                torch.from_numpy(np.ascontiguousarray(np.asarray(lp[our][e]).T))
            )
    save_file(sd, str(tmp_path / "model.safetensors"))

    from dynamo_tpu.models.weights import load_params

    loaded = load_params(str(tmp_path), cfg, dtype=jnp.float32)
    for key in ("router", "we_gate", "we_up", "we_down"):
        np.testing.assert_allclose(
            np.asarray(loaded["layers"][0][key]), np.asarray(lp[key]),
            rtol=1e-6, atol=1e-6,
        )


# ------------------- a layer that holds a share; every other preset unmoved

# sha256 (first 16 hex) over the layer's output, the router's weights and
# indices and every parameter, and the layer's load, computed with the
# tree BEFORE `experts_held` / the sigmoid router existed (PR 35): the
# softmax / all-held presets must not move by a bit
ALL_HELD_GOLDEN = {
    "tiny-moe": ("b6aaaf5b13a6f696", [4, 8]),
    "tiny-mla": ("09c3922492ceda88", [8, 4]),
    "mixtral-8x7b": ("2a07021484d6a4aa", [8, 4]),
}


def _preset(name):
    from dynamo_tpu.models.config import PRESETS

    cfg = PRESETS[name]
    if name == "mixtral-8x7b":   # its router and counts, at a CPU's width
        cfg = cfg.with_(hidden_size=64, intermediate_size=128)
    return cfg


@pytest.mark.parametrize("name", sorted(ALL_HELD_GOLDEN))
def test_all_held_softmax_presets_are_bit_equal_to_before(name):
    import hashlib

    from dynamo_tpu.models.moe import init_moe_params, moe_block, route

    cfg = _preset(name)
    assert cfg.held_experts == cfg.num_experts and cfg.expert_offset == 0
    lp = init_moe_params(cfg, jax.random.PRNGKey(11), dtype=jnp.float32)
    x = jax.random.normal(
        jax.random.PRNGKey(12), (2, 7, cfg.hidden_size), jnp.float32)
    mask = jnp.ones((2, 7), bool).at[1, 5:].set(False)
    stats = []
    y = moe_block(lp, cfg, x, real_mask=mask, stats=stats)
    w, i = route(lp, cfg, x.reshape(-1, cfg.hidden_size))
    h = hashlib.sha256()
    for a in (y, w, i, *[v for _, v in sorted(lp.items())]):
        h.update(np.asarray(a).tobytes())
    assert (h.hexdigest()[:16], [int(s) for s in stats[0][:2]]) == (
        ALL_HELD_GOLDEN[name])
    # beside the load: one block, and the real rows' pairs (14 - 2 rows)
    assert [int(s) for s in stats[0][2:]] == [
        1, 12 * cfg.num_experts_per_tok]


def test_a_share_computes_only_its_own_experts():
    """8 experts scored, 3 held from 2: pairs routed elsewhere are the
    sentinel's (computed by nobody), the load counts held experts only,
    and the output is exactly the held experts' part of the whole sum."""
    from dynamo_tpu.models.config import PRESETS
    from dynamo_tpu.models.moe import init_moe_params, moe_block, route

    whole_cfg = PRESETS["tiny-mimo"].with_(experts_held=8)
    cfg = whole_cfg.with_(experts_held=3, expert_offset=2)
    key = jax.random.PRNGKey(4)
    whole = init_moe_params(whole_cfg, key, dtype=jnp.float32)
    lp = init_moe_params(cfg, key, dtype=jnp.float32)
    assert lp["router"].shape[1] == 8 and lp["we_up"].shape[0] == 3
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 40, cfg.hidden_size))
    stats = []
    got = moe_block(lp, cfg, x, stats=stats)
    w, idx = route(lp, cfg, x.reshape(40, -1))
    held = (idx >= 2) & (idx < 5)
    assert int(stats[0][0]) == len(np.unique(np.asarray(idx)[held]))
    assert int(stats[0][1]) == np.bincount(
        np.asarray(idx)[held], minlength=8).max()
    xf = x.reshape(40, -1)
    want = jnp.zeros_like(xf)
    for e in range(2, 5):
        y = (jax.nn.silu(xf @ whole["we_gate"][e]) * (xf @ whole["we_up"][e])
             ) @ whole["we_down"][e]
        want = want + y * jnp.sum(jnp.where(idx == e, w, 0.0), 1)[:, None]
    np.testing.assert_allclose(got[0], want, atol=1e-5)


# ------------------------- a small share: the held pairs, a block at a time

# 2 of 16 experts held, top-2, 256 tokens: 512 sorted pairs worked 128 at
# a time (`block_rows`), about 64 of them real under an even router
def _share():
    from dynamo_tpu.models.config import PRESETS

    return PRESETS["tiny-mimo"].with_(
        num_experts=16, experts_held=2, expert_offset=4)


def _held_part(lp, cfg, x, mask=None):
    """Per held expert, over every token: its SwiGLU times the weight the
    router gave the pair (0 where the token did not choose it)."""
    from dynamo_tpu.models.moe import route

    xf = x.reshape(-1, cfg.hidden_size)
    w, idx = route(lp, cfg, xf)
    if mask is not None:
        w = jnp.where(mask.reshape(-1, 1), w, 0.0)
    want = jnp.zeros_like(xf)
    for j in range(cfg.held_experts):
        y = (jax.nn.silu(xf @ lp["we_gate"][j]) * (xf @ lp["we_up"][j])
             ) @ lp["we_down"][j]
        want = want + y * jnp.sum(
            jnp.where(idx == cfg.expert_offset + j, w, 0.0), 1)[:, None]
    return want.reshape(x.shape), (idx >= cfg.expert_offset) & (
        idx < cfg.expert_offset + cfg.held_experts)


def test_block_rows_follow_from_the_share_held_and_the_row_count():
    from dynamo_tpu.models.moe import GMM_ROWS, block_rows

    # every expert held: the whole width, whatever it is
    assert block_rows(768, 64, 64) == 768
    assert block_rows(GMM_ROWS, 8, 8) == GMM_ROWS
    # 16 of 256 at top-8: 256 decode slots, a 512-token chunk
    assert block_rows(2048, 16, 256) == 256
    assert block_rows(4096, 16, 256) == 512
    # half held: twice the even share is everything
    assert block_rows(256, 4, 8) == 256
    # never less than a tile, never more than the rows there are
    assert block_rows(GMM_ROWS, 1, 256) == GMM_ROWS
    assert block_rows(512, 2, 16) == 128
    assert block_rows(384, 2, 16) == 128


@pytest.mark.parametrize("load,blocks", [
    ("even", 1), ("all-held", 4), ("half-held", 2), ("none-held", 0)])
def test_a_small_share_is_drop_free_a_block_at_a_time(load, blocks):
    """A router that sends EVERY token's pairs to the held experts runs
    m / C blocks and computes them all; an even one runs one block, one
    that sends nothing here runs none; padding rows are nobody's."""
    cfg = _share()
    lp = _layer(cfg, seed=3)
    n = 256
    x = jax.random.normal(jax.random.PRNGKey(7), (2, n // 2, cfg.hidden_size))
    held = slice(cfg.expert_offset, cfg.expert_offset + cfg.held_experts)
    if load != "even":
        # sigmoid(0) = 0.5 everywhere, the held columns far above (or
        # below) it on positive inputs: both held experts win (or lose)
        x = jnp.abs(x)
        lp["router"] = jnp.zeros_like(lp["router"]).at[:, held].set(
            -100.0 if load == "none-held" else 100.0)
    if load == "half-held":
        # every second token's input negated: its held scores fall to 0
        x = x * jnp.where(jnp.arange(n // 2) % 2 == 0, 1.0, -1.0)[None, :, None]
    mask = jnp.ones((2, n // 2), bool).at[1, 100:].set(False)
    if load == "all-held":
        mask = jnp.ones_like(mask)      # all 512 pairs real: every block
    stats = []
    got = moe_block(lp, cfg, x, real_mask=mask, stats=stats)
    want, chosen = _held_part(lp, cfg, x, mask)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=1e-5)
    pairs = int((chosen & mask.reshape(-1, 1)).sum())
    hit, load_max, ran, pairs_held = (int(s) for s in stats[0])
    assert (ran, pairs_held) == (blocks, pairs)
    assert ran == -(-pairs // 128)
    if load == "all-held":
        assert (pairs, load_max, hit) == (2 * n, n, 2)
    if load == "none-held":
        assert pairs == hit == 0 and not np.asarray(got).any()
    # a padding row comes out empty and, whatever it holds, moves no
    # real row
    keep = np.asarray(mask.reshape(-1))
    flat = np.asarray(got).reshape(n, -1)
    assert not flat[~keep].any()
    x2 = jnp.where(mask[..., None], x, 1e3 * x + 7.0)
    again = np.asarray(moe_block(lp, cfg, x2, real_mask=mask)).reshape(n, -1)
    np.testing.assert_array_equal(again[keep], flat[keep])


def test_pairs_that_are_not_whole_blocks_are_padded_to_them():
    """192 tokens x 2 = 384 pairs, 128 a block: three blocks of rows."""
    cfg = _share()
    lp = _layer(cfg, seed=4)
    x = jnp.abs(jax.random.normal(
        jax.random.PRNGKey(8), (1, 192, cfg.hidden_size)))
    held = slice(cfg.expert_offset, cfg.expert_offset + cfg.held_experts)
    lp["router"] = jnp.zeros_like(lp["router"]).at[:, held].set(100.0)
    stats = []
    got = moe_block(lp, cfg, x, stats=stats)
    want, _ = _held_part(lp, cfg, x)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=1e-5)
    assert [int(s) for s in stats[0][2:]] == [3, 384]


# sha256 (first 16 hex) of str(jax.make_jaxpr(moe_block)) with the tree of
# PR 38, before a pass knew of blocks: a layer that holds all its experts
# (and tiny-mimo's half) traces to the program it traced to then
WHOLE_WIDTH_JAXPR = {
    "tiny-moe": "91fa5adfd1cd7942",
    "tiny-mla": "701a98e77cf35805",
    "mixtral-8x7b": "886d0140e9dff74c",
    "tiny-mimo": "bc3f8955766d97e2",
}


def _jaxpr(cfg, tokens):
    lp = init_moe_params(cfg, jax.random.PRNGKey(11), dtype=jnp.float32)
    x = jnp.zeros((2, tokens, cfg.hidden_size), jnp.float32)
    mask = jnp.ones((2, tokens), bool)
    return str(jax.make_jaxpr(
        lambda lp, x, mask: moe_block(lp, cfg, x, real_mask=mask)
    )(lp, x, mask))


@pytest.mark.parametrize("name", sorted(WHOLE_WIDTH_JAXPR))
def test_a_layer_of_one_block_traces_to_the_parent_s_program(name):
    import hashlib

    text = _jaxpr(_preset(name), 7)
    assert "while" not in text
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == (
        WHOLE_WIDTH_JAXPR[name])


def test_a_small_share_loops_around_one_body_s_three_kernels():
    whole, share = _jaxpr(_preset("tiny-mimo"), 7), _jaxpr(_share(), 128)
    assert share.count("while[") == 1
    assert share.count("name=gmm") == whole.count("name=gmm") > 0
