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
    (hit, load_max), = stats
    assert int(load_max) == n  # every token reached its first expert
    assert int(hit) == cfg.num_experts_per_tok if routing == "uniform" \
        else 2 <= int(hit) <= cfg.num_experts


def test_grouped_matmul_kernel_matches_ragged_dot():
    """The layer's grouped matmul (megablox; off a TPU it runs in the
    pallas interpreter) against `jax.lax.ragged_dot` on rows sorted by
    group: uneven groups, an empty one, and rows past the groups' end
    (which nobody reads)."""
    from dynamo_tpu.models.moe import GMM_ROWS, grouped_matmul

    rng = np.random.RandomState(0)
    xs = jnp.asarray(rng.randn(2 * GMM_ROWS, 64).astype(np.float32))
    w = jnp.asarray(rng.randn(4, 64, 256).astype(np.float32))
    sizes = jnp.asarray([70, 0, 129, 31], jnp.int32)
    used = int(sizes.sum())
    got = grouped_matmul(xs, w, sizes)
    want = jax.lax.ragged_dot(xs, w, sizes)
    np.testing.assert_allclose(
        np.asarray(got)[:used], np.asarray(want)[:used], rtol=1e-5, atol=1e-4)


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


@pytest.mark.parametrize("name", sorted(ALL_HELD_GOLDEN))
def test_all_held_softmax_presets_are_bit_equal_to_before(name):
    import hashlib

    from dynamo_tpu.models.config import PRESETS
    from dynamo_tpu.models.moe import init_moe_params, moe_block, route

    cfg = PRESETS[name]
    if name == "mixtral-8x7b":   # its router and counts, at a CPU's width
        cfg = cfg.with_(hidden_size=64, intermediate_size=128)
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
    assert (h.hexdigest()[:16], [int(s) for s in stats[0]]) == (
        ALL_HELD_GOLDEN[name])


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
