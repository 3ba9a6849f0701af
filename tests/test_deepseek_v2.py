"""The DeepSeek-V2 family on the CPU, at a tiny size on seeded weights:
latent attention over the paged latent pool (absorbed form, the pallas
kernels in interpret mode) against the benchmark's plain reference
(expanded form), YaRN against its closed form, the checkpoint names, and
every plane that refuses a latent cache."""

from __future__ import annotations

import importlib.util
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine.config import EngineConfig
from dynamo_tpu.models import llama
from dynamo_tpu.models.config import PRESETS, ModelConfig
from dynamo_tpu.ops import rope as ropemod
from dynamo_tpu.ops.attention import latent_attention
from dynamo_tpu.ops.pallas_mla import (
    latent_page_write,
    mla_paged_decode_attention,
)

from .test_engine import collect, greedy_request, make_engine

CFG = PRESETS["tiny-mla"].with_(dtype="float32")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _reference():
    """benchmark/references/deepseek_v2.py, by its path: the file `correct`
    is judged by on the chip is the oracle here."""
    path = os.path.join(ROOT, "benchmark", "references", "deepseek_v2.py")
    spec = importlib.util.spec_from_file_location("ref_deepseek_v2", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _hf(cfg: ModelConfig) -> dict:
    """The config.json keys the reference reads, from a ModelConfig."""
    return {
        "num_attention_heads": cfg.num_heads,
        "qk_nope_head_dim": cfg.qk_nope_head_dim,
        "qk_rope_head_dim": cfg.qk_rope_head_dim,
        "v_head_dim": cfg.v_head_dim, "kv_lora_rank": cfg.kv_lora_rank,
        "rms_norm_eps": cfg.rms_norm_eps, "rope_theta": cfg.rope_theta,
        "rope_scaling": cfg.rope_scaling,
        "num_experts_per_tok": cfg.num_experts_per_tok,
        "norm_topk_prob": cfg.norm_topk_prob,
        "routed_scaling_factor": cfg.routed_scaling_factor,
    }


PUBLISHED = {
    "attention_bias": False, "first_k_dense_replace": 1, "hidden_act": "silu",
    "hidden_size": 2048, "intermediate_size": 10944, "kv_lora_rank": 512,
    "max_position_embeddings": 163840, "model_type": "deepseek_v2",
    "moe_intermediate_size": 1408, "moe_layer_freq": 1, "n_group": 1,
    "n_routed_experts": 64, "n_shared_experts": 2, "norm_topk_prob": False,
    "num_attention_heads": 16, "num_experts_per_tok": 6,
    "num_hidden_layers": 27, "num_key_value_heads": 16, "q_lora_rank": None,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
    "rope_scaling": {
        "beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 0.707,
        "mscale_all_dim": 0.707, "original_max_position_embeddings": 4096,
        "type": "yarn"},
    "rope_theta": 10000, "routed_scaling_factor": 1,
    "scoring_func": "softmax", "seq_aux": True, "tie_word_embeddings": False,
    "topk_group": 1, "topk_method": "greedy", "v_head_dim": 128,
    "vocab_size": 102400,
}


def test_published_config_is_the_preset():
    cfg = ModelConfig.from_hf_config(PUBLISHED, name="deepseek-v2-lite")
    assert cfg == PRESETS["deepseek-v2-lite"]
    assert (cfg.latent_width, cfg.latent_pool_width) == (576, 640)
    assert [cfg.is_moe_layer(i) for i in (0, 1, 26)] == [False, True, True]


@pytest.mark.parametrize("key,value", [
    ("q_lora_rank", 1536), ("scoring_func", "sigmoid"),
    ("topk_method", "group_limited_greedy"), ("n_group", 8),
    ("moe_layer_freq", 2), ("attention_bias", True),
])
def test_unserved_deepseek_keys_are_refused_by_name(key, value):
    with pytest.raises(ValueError, match=key):
        ModelConfig.from_hf_config({**PUBLISHED, key: value})


def test_yarn_table_and_softmax_scale_match_the_closed_form():
    cfg = PRESETS["deepseek-v2-lite"]
    sc, dim, theta = cfg.rope_scaling, 64, 10000.0
    m = 0.1 * 0.707 * math.log(40) + 1
    assert round(m, 4) == 1.2608
    assert ropemod.yarn_mscale(40, 0.707) == pytest.approx(m)
    assert ropemod.softmax_scale(cfg) == pytest.approx(192 ** -0.5 * m * m)
    # the ramp's ends: the pair indices that turn 32 times and once within
    # 4,096 positions
    def turns_at(rot):
        return dim * math.log(4096 / (rot * 2 * math.pi)) / (
            2 * math.log(theta))
    low, high = math.floor(turns_at(32)), math.ceil(turns_at(1))
    assert (low, high) == (10, 23)
    inv = ropemod.rope_inv_freq(cfg)
    plain = theta ** -(np.arange(0, dim, 2) / dim)
    np.testing.assert_allclose(inv[:low + 1], plain[:low + 1], rtol=1e-6)
    np.testing.assert_allclose(inv[high:], plain[high:] / 40, rtol=1e-6)
    mid = (low + high) // 2
    ramp = (mid - low) / (high - low)
    assert inv[mid] == pytest.approx(
        plain[mid] / 40 * ramp + plain[mid] * (1 - ramp), rel=1e-6)
    # the reference builds the same table on its own
    cos, sin, scale = _reference().rope_tables(
        {**PUBLISHED, "rope_scaling": sc}, 8)
    assert scale == pytest.approx(ropemod.softmax_scale(cfg))
    np.testing.assert_allclose(np.asarray(cos[1]), np.cos(inv), rtol=1e-5)
    with pytest.raises(ValueError, match="mscale"):
        ropemod.softmax_scale(cfg.with_(
            rope_scaling={**sc, "mscale": 1.0}))


def _decode_case(ps=8, wrow=None, seed=0, h=4, rank=32, width=128):
    """Inputs of one decode step. `wrow` None: four short rows, the new
    row each one's last (PR 34's case). Else five rows in pages of `ps`,
    three of them longer than a work item (4 pages), with the new row on
    row `wrow` of the FIRST, a MIDDLE and the LAST live page; a row of
    length 0 that names a position and real pages; a row that attends
    and writes nothing."""
    rng = np.random.RandomState(seed)
    if wrow is None:
        lengths = np.array([13, 0, 64, 33], np.int32)  # attended, new row incl.
        wpos = np.array([12, -1, 63, 32], np.int32)
    else:
        lengths = np.array(
            [5 * ps + 5, 0, 5 * ps + 3, 5 * ps + wrow + 1, ps + 3], np.int32)
        wpos = np.array([wrow, 3, 2 * ps + wrow, 5 * ps + wrow, -1], np.int32)
    b = len(lengths)
    held = np.maximum(-(-lengths // ps), 1)  # the inactive row holds a page
    tables = np.zeros((b, 9), np.int32)
    nxt = 1
    for i in range(b):
        tables[i, :held[i]] = np.arange(nxt, nxt + held[i])
        nxt += held[i]
    pool = rng.randn((nxt + 2) * ps, width).astype(np.float32)
    qa = rng.randn(b, h, width).astype(np.float32)
    new = rng.randn(b, width).astype(np.float32)
    return pool, lengths, wpos, tables, qa, new, rank, ps


@pytest.mark.parametrize("ps,wrow", [
    (8, None),
    # a slab is a whole page: its first and last row
    (8, 0), (8, 7),
    # a slab is half a page: a page's first and last row, and both sides
    # of the slab boundary
    (32, 0), (32, 15), (32, 16), (32, 31),
])
def test_latent_decode_kernel_reads_and_writes_like_the_oracle(ps, wrow):
    """The paged latent decode kernel (interpret mode): the new row lands
    in its page, whichever work item of its sequence owns that page; every
    head attends the ONE row a token keeps; an inactive row emits zeros
    and writes nothing; and the pool that comes back differs from the one
    that went in ONLY in the written rows and the trash page: the rest of
    a written row's slab, the rest of its page and every other page are
    bit-equal."""
    pool, lengths, wpos, tables, qa, new, rank, ps = _decode_case(ps, wrow)
    out, pool2 = mla_paged_decode_attention(
        jnp.asarray(qa), jnp.asarray(new), jnp.asarray(pool),
        jnp.asarray(tables), jnp.asarray(lengths), jnp.asarray(wpos),
        rank=rank, page_size=ps, interpret=True)
    want_pool = pool.copy()
    writes = np.flatnonzero((wpos >= 0) & (wpos < lengths))
    assert len(writes) == 3
    for i in writes:
        want_pool[tables[i, wpos[i] // ps] * ps + wpos[i] % ps] = new[i]
    np.testing.assert_array_equal(np.asarray(pool2)[ps:], want_pool[ps:])
    smat = (tables[:, :, None] * ps + np.arange(ps)).reshape(len(tables), -1)
    ref = latent_attention(
        jnp.asarray(qa)[:, None], jnp.asarray(want_pool)[smat],
        jnp.asarray(lengths - 1)[:, None], rank)[:, 0]
    act = lengths > 0
    np.testing.assert_allclose(
        np.asarray(out)[act], np.asarray(ref)[act], rtol=1e-5, atol=1e-5)
    assert not np.asarray(out)[~act].any()


def test_latent_page_write_lands_whole_pages():
    rng = np.random.RandomState(1)
    ps, width = 8, 128
    pool = rng.randn(20 * ps, width).astype(np.float32)
    pages = rng.randn(3, ps, width).astype(np.float32)
    got = np.asarray(latent_page_write(
        jnp.asarray(pool), jnp.asarray([5, 0, 7], jnp.int32),
        jnp.asarray(pages), page_size=ps, interpret=True,
    )).reshape(20, ps, width)
    np.testing.assert_array_equal(got[5], pages[0])
    np.testing.assert_array_equal(got[7], pages[2])
    keep = [i for i in range(1, 20) if i not in (5, 7)]
    np.testing.assert_array_equal(
        got[keep], pool.reshape(20, ps, width)[keep])


def test_absorbed_attention_equals_expanded():
    """The served layer (absorbed: W_uk folded into the query, W_uv after
    the softmax, multi-query over the cached rows) against the reference's
    expanded layer (per-head keys and values from W_kvb), one whole
    forward, float32."""
    params = llama.init_params(CFG, jax.random.PRNGKey(0), dtype=jnp.float32)
    rng = np.random.RandomState(0)
    t, page = 40, 8
    ids = rng.randint(1, CFG.vocab_size, (t,)).astype(np.int32)
    kv = llama.init_kv_cache(CFG, 256, dtype=jnp.float32)
    assert kv.latent and kv.v is None
    assert kv.k[0].shape == (256, CFG.latent_pool_width)
    hidden, kv = llama.forward(
        params, CFG, jnp.asarray(ids[None]),
        jnp.arange(t, dtype=jnp.int32)[None], kv,
        jnp.asarray(page + np.arange(t), jnp.int32),
        jnp.asarray((page + np.arange(64))[None], jnp.int32))
    lps = jax.nn.log_softmax(llama.logits(params, CFG, hidden[0]), -1)
    got = np.asarray(lps[np.arange(t - 9, t - 1), ids[t - 8:]])
    want = _reference().token_logprobs(params, _hf(CFG), list(ids), 8, 64)
    np.testing.assert_allclose(got, want, atol=2e-5)
    # the pad lanes of a written row stay zero
    row = np.asarray(kv.k[0][page])
    assert row[:CFG.latent_width].any() and not row[CFG.latent_width:].any()


@pytest.mark.parametrize("backend", ["gather", "pallas"])
async def test_served_logprobs_match_the_reference(backend):
    """Through the engine: a prompt prefilled in two chunks, then 8 tokens
    decoded through the latent cache (`pallas`: the latent kernels in
    interpret mode, the path the chip takes; `gather`: plain XLA), each
    served log-probability against the reference's, teacher-forced."""
    engine = make_engine(model=CFG, attn_backend=backend, prefill_chunk=32)
    assert engine.attention_backend["kind"] == backend
    assert engine.kv.latent
    rng = np.random.RandomState(3)
    prompt = [int(x) for x in rng.randint(1, CFG.vocab_size, (44,))]
    pre = greedy_request(prompt, max_tokens=8)
    pre.sampling_options.logprobs = True
    tokens, finish, frames = await collect(engine, pre)
    assert len(tokens) == 8 and finish == "length"
    served = [lp for f in frames for lp in f.get("log_probs") or []]
    assert len(served) == 8
    want = _reference().token_logprobs(
        engine.params, _hf(CFG), prompt + tokens, 8, 64)
    np.testing.assert_allclose(np.asarray(served), want, atol=5e-5)
    if backend == "pallas":
        rows = engine.flight.snapshot()
        decodes = [r for r in rows if r["kind"] == "decode"]
        assert decodes and all(
            r["kv_pages_streamed"] == r["kv_pages_held"] > 0 for r in decodes)
        loads = [r for r in rows if r["moe_experts_hit"]]
        # one expert layer, one row, top-2: two experts hit, one token each
        assert loads and all(r["kind"] in ("sync", "overlap") for r in loads)
        assert all(r["moe_experts_hit"] == 2.0 and r["moe_load_max"] == 1.0
                   for r in loads)
        # all experts held: the pass is one block, over the row's 2 pairs
        assert all(r["moe_row_blocks"] == 1.0 and r["moe_pairs_held"] == 2.0
                   for r in loads)
    await engine.close()


def test_deepseek_v2_weight_loading(tmp_path):
    """A `deepseek_v2`-named checkpoint (kv_a_proj_with_mqa, kv_a_layernorm,
    kv_b_proj, mlp.gate, mlp.experts.N, mlp.shared_experts; layer 0 a plain
    mlp) loads into the tree `init_params` builds."""
    import torch
    from safetensors.torch import save_file

    from dynamo_tpu.models.weights import load_params

    params = llama.init_params(CFG, jax.random.PRNGKey(3), dtype=jnp.float32)

    def t(a, transpose=True):
        a = np.asarray(a)
        return torch.from_numpy(np.ascontiguousarray(a.T if transpose else a))

    sd = {
        "model.embed_tokens.weight": t(params["embed"], False),
        "model.norm.weight": t(params["final_norm"], False),
        "lm_head.weight": t(params["lm_head"]),
    }
    attn_names = {
        "wq": "self_attn.q_proj", "w_kva": "self_attn.kv_a_proj_with_mqa",
        "w_kvb": "self_attn.kv_b_proj", "wo": "self_attn.o_proj",
    }
    for i, lp in enumerate(params["layers"]):
        pre = f"model.layers.{i}."
        sd[pre + "input_layernorm.weight"] = t(lp["attn_norm"], False)
        sd[pre + "post_attention_layernorm.weight"] = t(lp["mlp_norm"], False)
        sd[pre + "self_attn.kv_a_layernorm.weight"] = t(lp["kv_norm"], False)
        for ours, theirs in attn_names.items():
            sd[pre + theirs + ".weight"] = t(lp[ours])
        if "router" not in lp:
            for ours, theirs in (("w_gate", "gate_proj"), ("w_up", "up_proj"),
                                 ("w_down", "down_proj")):
                sd[pre + f"mlp.{theirs}.weight"] = t(lp[ours])
            continue
        sd[pre + "mlp.gate.weight"] = t(lp["router"])
        for ours, theirs in (("gate", "gate_proj"), ("up", "up_proj"),
                             ("down", "down_proj")):
            sd[pre + f"mlp.shared_experts.{theirs}.weight"] = t(
                lp["ws_" + ours])
            for e in range(CFG.num_experts):
                sd[pre + f"mlp.experts.{e}.{theirs}.weight"] = t(
                    lp["we_" + ours][e])
    save_file(sd, str(tmp_path / "model.safetensors"))
    loaded = load_params(str(tmp_path), CFG, dtype=jnp.float32)
    assert jax.tree.structure(loaded) == jax.tree.structure(params)
    for got, want in zip(jax.tree.leaves(loaded), jax.tree.leaves(params)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("backend", ["gather", "pallas"])
async def test_a_prompt_sent_twice_reuses_its_latent_pages(backend):
    """The in-engine prefix cache over a latent pool: the second serve of
    a prompt of several pages reserves the pages the first registered,
    prefills only the tail, and serves the same tokens and
    log-probabilities."""
    engine = make_engine(model=CFG, attn_backend=backend, prefill_chunk=32)
    summaries = []
    engine.subscribe_requests(summaries.append)
    rng = np.random.RandomState(5)
    prompt = [int(x) for x in rng.randint(1, CFG.vocab_size, (44,))]

    async def serve():
        pre = greedy_request(prompt, max_tokens=8)
        pre.sampling_options.logprobs = True
        tokens, _, frames = await collect(engine, pre)
        return tokens, np.asarray(
            [lp for f in frames for lp in f.get("log_probs") or []])

    first_t, first_lp = await serve()
    assert engine.allocator.pages_cached > 0
    assert engine.peek_prefix_tokens(prompt) == 40  # 5 whole pages of 8
    prefilled = engine.phase_stats["prefill_tokens"]
    again_t, again_lp = await serve()
    assert engine.phase_stats["prefill_tokens"] - prefilled == 4
    assert [s["prefix"]["reused_blocks"] for s in summaries] == [0, 5]
    assert again_t == first_t and len(again_lp) == 8
    np.testing.assert_allclose(again_lp, first_lp, atol=5e-5)
    assert engine.kv_ledger.audit() == []
    await engine.close()


# ------------------------------------------------- what a latent cache refuses

async def test_latent_engine_refuses_the_page_moving_planes():
    """The host-staged and device-path disaggregation planes
    (engine/kv_transfer.py, engine/xproc_kv.py's send side is
    `prefill_only(device_arrays=True)`), prefix ingest / export: each
    refuses with the reason, none moves half a cache."""
    from dynamo_tpu.engine.kv_transfer import device_transfer_kv
    from dynamo_tpu.llm.protocols.common import PreprocessedRequest
    from dynamo_tpu.runtime.pipeline.context import Context

    engine = make_engine(model=CFG)
    pre = greedy_request([5, 6, 7, 8], max_tokens=2)
    with pytest.raises(ValueError, match="latent"):
        await engine.prefill_only(pre)
    with pytest.raises(ValueError, match="latent"):
        await engine.prefill_only(pre, device_arrays=True)
    with pytest.raises(ValueError, match="latent"):
        await engine.generate_remote(
            Context(pre.to_dict()), 1, np.zeros((2, 4, 8)), np.zeros((2, 4, 8)))
    with pytest.raises(ValueError, match="latent"):
        engine.ingest_prefix(list(range(16)), None, None)
    with pytest.raises(ValueError, match="latent"):
        engine.export_prefix(list(range(16)))
    with pytest.raises(ValueError, match="latent"):
        device_transfer_kv(engine, engine, [1], [2], 8)
    with pytest.raises(ValueError, match="latent"):
        engine._extract_fn(engine.kv, jnp.zeros((1,), jnp.int32))
    assert isinstance(pre, PreprocessedRequest)
    await engine.close()


def test_latent_cache_refuses_quantization_with_a_sentence():
    with pytest.raises(ValueError, match="no quantized latent rows"):
        llama.init_kv_cache(CFG, 64, kv_quant="int8")
    with pytest.raises(ValueError, match="int8 weights"):
        llama.init_params(CFG, jax.random.PRNGKey(0), quantize=True)


async def test_latent_runtime_mixed_toggle_builds_nothing():
    """mixed_batching switched on at runtime on a latent engine never
    builds a mixed step (`_mixed_unsupported_reason`, the one predicate
    construction raises and the tick consults): the engine keeps serving."""
    engine = make_engine(model=CFG)
    engine.config.mixed_batching = True
    assert "latent" in engine._mixed_unsupported_reason()
    tokens, finish, _ = await collect(
        engine, greedy_request([5, 6, 7, 8, 9], max_tokens=6))
    assert len(tokens) == 6 and finish == "length"
    await engine.close()


def test_engine_config_sizes_latent_pages_by_their_lanes():
    """Sizing counts a latent row at the lanes it occupies (640 for 576
    values), one pool a layer."""
    cfg = PRESETS["deepseek-v2-lite"].with_(num_layers=9)
    assert cfg.num_layers * cfg.latent_pool_width * 2 == 11520
    assert EngineConfig(model=CFG).model_config().latent


# ------------------------------- the control behind the cell's tolerance

@pytest.fixture(scope="module")
def control_readings():
    """benchmark/controls/deepseek_v2.py at the configuration's rehearsal
    size: the reference with one thing lowered, in the program's place,
    judged by the harness's `compare` under the configuration's limits."""
    path = os.path.join(ROOT, "benchmark", "controls", "deepseek_v2.py")
    spec = importlib.util.spec_from_file_location("control_deepseek_v2", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.readings("deepseek-v2-lite-l9", [1], rehearse=True,
                        controls=("float32",) + mod.CONTROLS)


@pytest.mark.parametrize("control", [
    "float32", "int8_weights", "fp8_weights", "int8_latent", "bf16_softmax"])
def test_lower_precision_control_reads_a_gap(control_readings, control):
    """Nothing lowered reads exactly 0 over the 64 judged positions; each
    control reads a finite gap above it (fp8 weights above int8's), so a
    limit can be placed against it."""
    got = control_readings[control]["1"]
    assert got["positions"] == 64 and np.isfinite(got["gap_max"])
    if control == "float32":
        assert got["gap_max"] == 0.0 and got["ok"]
        return
    assert 0.0 < got["gap_mean"] <= got["gap_max"]
    if control == "fp8_weights":
        assert got["gap_mean"] > control_readings["int8_weights"]["1"]["gap_mean"]
