"""Sparse MoE (mixtral-style) + expert parallelism: block oracle match,
sharded forward equivalence on the ep axis, engine e2e serving."""

from __future__ import annotations

import jax
import jax.numpy as jnp

import numpy as np

from dynamo_tpu.models import llama
from dynamo_tpu.models.config import get_config
from dynamo_tpu.models.moe import expert_capacity, init_moe_params, moe_block
from dynamo_tpu.parallel import mesh as meshmod

CFG = get_config("tiny-moe").with_(dtype="float32")


def moe_oracle(lp, cfg, x):
    """Per-token loop: route to top-k experts, weighted SwiGLU sum —
    assumes capacity is never exceeded."""
    b, t, d = x.shape
    out = np.zeros((b, t, d), np.float32)
    router = np.asarray(lp["router"], np.float32)
    for bi in range(b):
        for ti in range(t):
            h = np.asarray(x[bi, ti], np.float32)
            logits = h @ router
            probs = np.exp(logits - logits.max())
            probs /= probs.sum()
            top = np.argsort(-probs)[: cfg.num_experts_per_tok]
            w = probs[top] / probs[top].sum()
            for wi, e in zip(w, top):
                gate = np.asarray(lp["we_gate"], np.float32)[e]
                up = np.asarray(lp["we_up"], np.float32)[e]
                down = np.asarray(lp["we_down"], np.float32)[e]
                g = h @ gate
                silu = g / (1 + np.exp(-g))
                out[bi, ti] += wi * ((silu * (h @ up)) @ down)
    return out


def test_moe_block_matches_oracle():
    key = jax.random.PRNGKey(0)
    lp = init_moe_params(CFG, key, dtype=jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, CFG.hidden_size))
    got = np.asarray(moe_block(lp, CFG, x))
    ref = moe_oracle(lp, CFG, np.asarray(x))
    np.testing.assert_allclose(got, ref, rtol=2e-4, atol=2e-4)


def test_capacity_drops_overflow_deterministically():
    # force every token's top-1 to expert 0 via a huge router column; with
    # N tokens > cap, tokens at batch positions >= cap lose their expert-0
    # slot (GShard priority: earlier rows win) and keep ONLY their
    # second-choice expert's weighted contribution
    cfg = CFG.with_(expert_capacity_factor=0.1)
    lp = init_moe_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    lp["router"] = lp["router"].at[:, 0].set(100.0)
    n = 64
    cap = expert_capacity(cfg, n)
    assert cap < n
    x = jax.random.normal(jax.random.PRNGKey(2), (1, n, cfg.hidden_size))
    out = np.asarray(moe_block(lp, cfg, x))
    assert np.isfinite(out).all()

    # replicate the GShard priority exactly: slot-major (all first
    # choices, row order, then all second choices); an assignment past
    # `cap` in its expert contributes nothing
    router = np.asarray(lp["router"], np.float32)
    counters = {e: 0 for e in range(cfg.num_experts)}
    per_tok = []
    for ti in range(n):
        h = np.asarray(x[0, ti], np.float32)
        logits = h @ router
        probs = np.exp(logits - logits.max())
        probs /= probs.sum()
        top = np.argsort(-probs)[:2]
        w = probs[top] / probs[top].sum()
        per_tok.append((h, top, w))
    assignments = [[None, None] for _ in range(n)]
    for slot in range(2):
        for ti in range(n):
            e = int(per_tok[ti][1][slot])
            kept = counters[e] < cap
            counters[e] += 1
            assignments[ti][slot] = kept
    dropped = [ti for ti in range(n) if not all(assignments[ti])]
    assert dropped, "test setup must overflow some expert"
    for ti in range(n):
        h, top, w = per_tok[ti]
        expected = np.zeros(cfg.hidden_size, np.float32)
        for slot in range(2):
            if not assignments[ti][slot]:
                continue
            e = int(top[slot])
            g = h @ np.asarray(lp["we_gate"], np.float32)[e]
            silu = g / (1 + np.exp(-g))
            expected += w[slot] * (
                (silu * (h @ np.asarray(lp["we_up"], np.float32)[e]))
                @ np.asarray(lp["we_down"], np.float32)[e]
            )
        np.testing.assert_allclose(out[0, ti], expected, rtol=2e-4, atol=2e-4)


def test_padding_rows_do_not_consume_capacity():
    """With a real_mask, pad rows ahead of real tokens must not evict
    them from their routed expert."""
    cfg = CFG.with_(expert_capacity_factor=0.1)
    lp = init_moe_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    lp["router"] = lp["router"].at[:, 0].set(100.0)
    n = 64
    cap = expert_capacity(cfg, n)
    x = jax.random.normal(jax.random.PRNGKey(2), (1, n, cfg.hidden_size))
    # first half pads: without the mask they'd eat expert-0 capacity
    mask = jnp.arange(n)[None, :] >= (n - cap)
    out = np.asarray(moe_block(lp, cfg, x, real_mask=mask))
    # all real tokens (the last cap rows) got their full two-expert sum
    ref = moe_oracle(lp, cfg, np.asarray(x))
    np.testing.assert_allclose(
        out[0, n - cap:], ref[0, n - cap:], rtol=2e-4, atol=2e-4
    )
    # pad rows contribute nothing
    np.testing.assert_allclose(out[0, : n - cap], 0.0, atol=1e-6)


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
