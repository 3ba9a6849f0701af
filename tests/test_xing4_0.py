"""The Xing4.0 family on the CPU, at a tiny size on seeded weights: the
configuration and its refusals, the residual of four streams
(models/mhc.py) against the benchmark's plain reference, low-rank queries,
prefill in chunks then decode through the paged latent pool (the gather
oracle and the pallas kernels in interpret mode), and each control of the
cell's tolerance. The engine is `tests/test_xing4_0_engine.py`'s."""

from __future__ import annotations

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.models import llama, mhc
from dynamo_tpu.models.config import PRESETS, ModelConfig
from dynamo_tpu.ops import rope as ropemod

CFG = PRESETS["tiny-xing"].with_(dtype="float32")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(folder: str, name: str):
    path = os.path.join(ROOT, "benchmark", folder, name + ".py")
    spec = importlib.util.spec_from_file_location(f"{folder}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _reference():
    """benchmark/references/xing4_0.py, by its path: the file `correct` is
    judged by on the chip is the oracle here."""
    return _load("references", "xing4_0")


def _hf(cfg: ModelConfig) -> dict:
    """The config.json keys the reference reads, from a ModelConfig."""
    return {
        "num_attention_heads": cfg.num_heads,
        "qk_nope_head_dim": cfg.qk_nope_head_dim,
        "qk_rope_head_dim": cfg.qk_rope_head_dim,
        "v_head_dim": cfg.v_head_dim, "kv_lora_rank": cfg.kv_lora_rank,
        "q_lora_rank": cfg.q_lora_rank,
        "rms_norm_eps": cfg.rms_norm_eps, "rope_theta": cfg.rope_theta,
        "rope_scaling": cfg.rope_scaling,
        "num_experts_per_tok": cfg.num_experts_per_tok,
        "norm_topk_prob": cfg.norm_topk_prob,
        "routed_scaling_factor": cfg.routed_scaling_factor,
        "scoring_func": cfg.scoring_func, "hc_mult": cfg.hc_mult,
        "hc_sinkhorn_iters": cfg.hc_sinkhorn_iters, "hc_eps": cfg.hc_eps,
        "mhc_h_res_clamp_min": -cfg.hc_res_clamp,
        "mhc_h_res_clamp_max": cfg.hc_res_clamp,
    }


PUBLISHED = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 2,
    "hidden_act": "silu", "hidden_size": 3584, "intermediate_size": 9216,
    "kv_lora_rank": 512, "max_position_embeddings": 262144,
    "model_type": "xing4_0", "moe_intermediate_size": 1024,
    "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 64,
    "n_shared_experts": 1, "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts_per_tok": 4, "num_hidden_layers": 40,
    "num_key_value_heads": 32, "num_nextn_predict_layers": 1, "hc_mult": 4,
    "hc_sinkhorn_iters": 20, "hc_eps": 1e-06, "mhc_h_res_clamp_min": -30,
    "mhc_h_res_clamp_max": 30, "q_lora_rank": 768, "qk_nope_head_dim": 128,
    "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06, "rope_theta": 10000,
    "rope_scaling": {
        "beta_fast": 32, "beta_slow": 1, "factor": 64, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
        "type": "yarn"},
    "routed_scaling_factor": 2, "scoring_func": "sigmoid",
    "tie_word_embeddings": False, "topk_group": 1, "topk_method": "noaux_tc",
    "v_head_dim": 128, "vocab_size": 131072,
}


def test_published_config_is_the_preset():
    cfg = ModelConfig.from_hf_config(PUBLISHED, name="xing4.0-29b-a4b")
    assert cfg == PRESETS["xing4.0-29b-a4b"]
    assert (cfg.q_lora_rank, cfg.hc_mult, cfg.hc_sinkhorn_iters) == (768, 4, 20)
    assert (cfg.latent_width, cfg.latent_pool_width) == (576, 640)
    assert [cfg.is_moe_layer(i) for i in (0, 1, 2, 39)] == [
        False, False, True, True]
    # YaRN x 64 with mscale = mscale_all_dim = 1: 192^-0.5 x (0.1 ln 64 + 1)^2
    assert ropemod.softmax_scale(cfg) == pytest.approx(
        192 ** -0.5 * (0.1 * np.log(64) + 1) ** 2)
    # every other preset carries one stream and full-rank queries
    assert all(c.hc_mult == 1 and c.q_lora_rank == 0
               for n, c in PRESETS.items() if "xing" not in n)


@pytest.mark.parametrize("key,value", [
    ("n_group", 8), ("topk_group", 4), ("ep_size", 8),
    ("attention_bias", True), ("moe_layer_freq", 2),
    ("scoring_func", "softmax"), ("topk_method", "greedy"),
    ("q_lora_rank", None), ("hc_mult", 1), ("mhc_h_res_clamp_min", -10),
    ("rope_scaling", {**PUBLISHED["rope_scaling"], "mscale": 0.707}),
])
def test_unserved_xing_keys_are_refused_by_name(key, value):
    with pytest.raises(ValueError, match=key):
        ModelConfig.from_hf_config({**PUBLISHED, key: value})


@pytest.mark.parametrize("model_type,key,value", [
    ("deepseek_v2", "q_lora_rank", 768),
    ("deepseek_v2", "scoring_func", "sigmoid"),
    ("mimo_v2_flash", "routed_scaling_factor", 2),
    ("mimo_v2_flash", "n_shared_experts", 1),
])
def test_the_sibling_families_keep_their_refusals(model_type, key, value):
    """What `xing4_0` runs (low-rank queries, a scaling factor and a shared
    expert beside a sigmoid router) is still refused for the model types
    whose published code this build has not been held to with them."""
    if model_type == "deepseek_v2":
        from .test_deepseek_v2 import PUBLISHED as base
    else:
        from .test_mimo_v2_flash import CFG as mimo, _hf as mimo_hf

        base = mimo_hf(mimo)
    with pytest.raises(ValueError, match=key):
        ModelConfig.from_hf_config({**base, key: value})


def test_softmax_scale_names_the_rule_not_the_family():
    sc = {**PUBLISHED["rope_scaling"], "mscale": 0.5}
    with pytest.raises(ValueError, match="mscale == mscale_all_dim"):
        ropemod.softmax_scale(CFG.with_(rope_scaling=sc))


# ------------------------------------------------------------ the residual


def _maps_of(seed=0, rows=6):
    hp = mhc.init_mhc_params(CFG, jax.random.PRNGKey(seed), jnp.float32)
    x = jax.random.normal(
        jax.random.PRNGKey(seed + 1), (2, rows // 2, 4 * CFG.hidden_size))
    return hp, x, mhc.maps(hp, CFG, x)


def test_sinkhorn_gives_a_doubly_stochastic_matrix():
    """20 iterations: every row and every column of H_res sums to 1 within
    1e-3, every entry positive; H_pre in (0, 1), H_post in (0, 2)."""
    _, _, (h_pre, h_post, h_res) = _maps_of()
    h_res = np.asarray(h_res)                               # [n, n, R]
    assert h_res.shape == (4, 4, 6) and (h_res > 0).all()
    np.testing.assert_allclose(h_res.sum(0), 1.0, atol=1e-3)
    np.testing.assert_allclose(h_res.sum(1), 1.0, atol=1e-3)
    assert ((0 < np.asarray(h_pre)) & (np.asarray(h_pre) < 1)).all()
    assert ((0 < np.asarray(h_post)) & (np.asarray(h_post) < 2)).all()
    # seeded so that the mixing is seen: a stream keeps most of itself,
    # and the maps differ from row to row (they depend on the input)
    assert 0.3 < np.median(h_res[np.arange(4), np.arange(4)]) < 0.95
    assert np.asarray(h_res).std(axis=-1).mean() > 0.01


def test_boundary_equals_the_reference_s():
    """maps / pre / post against the reference's einsum forms."""
    ref = _reference()
    hp, x, got = _maps_of(seed=3)
    X = x.reshape(6, 4, CFG.hidden_size)
    want = ref._hc_maps(X, hp, eps=CFG.rms_norm_eps, iters=20, hc_eps=1e-6,
                        clamp=(-30.0, 30.0))
    np.testing.assert_allclose(np.asarray(got[0]).T, want[0], atol=1e-5)
    np.testing.assert_allclose(np.asarray(got[1]).T, want[1], atol=1e-5)
    np.testing.assert_allclose(
        np.moveaxis(np.asarray(got[2]), -1, 0), want[2], atol=1e-5)
    y = jax.random.normal(jax.random.PRNGKey(9), (2, 3, CFG.hidden_size))
    np.testing.assert_allclose(
        np.asarray(mhc.pre(got, x)).reshape(6, -1), ref._hc_pre(X, want),
        atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(mhc.post(got, x, y)).reshape(6, 4, -1),
        ref._hc_post(X, want, y.reshape(6, -1)), atol=1e-5)
    # copy-in and sum-out
    h = x[..., :CFG.hidden_size]
    np.testing.assert_array_equal(
        np.asarray(mhc.collapse(mhc.expand(h, 4), 4)), np.asarray(4 * h))


def test_the_clamp_bounds_exp_s_argument():
    hp, x, _ = _maps_of()
    big = {**hp, "b_res": hp["b_res"].at[0, 1].set(1e4)}
    h_res = np.asarray(mhc.maps(big, CFG, x)[2])
    assert np.isfinite(h_res).all()
    np.testing.assert_allclose(h_res.sum(0), 1.0, atol=1e-3)


# ------------------------------------------- the program against the reference


def _params(seed=0):
    return llama.init_params(CFG, jax.random.PRNGKey(seed), dtype=jnp.float32)


def test_the_new_leaves_and_the_parameter_count():
    params = _params()
    lp = params["layers"][1]
    assert "wq" not in lp
    assert lp["w_qa"].shape == (64, 24) and lp["w_qb"].shape == (24, 4 * 48)
    assert lp["q_norm"].shape == (24,)
    assert lp["hc_attn"]["phi"].shape == (256, 24)
    assert lp["hc_mlp"]["b_res"].shape == (4, 4)
    assert lp["hc_attn"]["alpha"].dtype == jnp.float32
    # the two boundaries of a layer are seeded apart, and so are layers
    assert not np.allclose(lp["hc_attn"]["phi"], lp["hc_mlp"]["phi"])
    assert not np.allclose(lp["hc_attn"]["phi"],
                           params["layers"][2]["hc_attn"]["phi"])
    with pytest.raises(ValueError, match="int8 weights"):
        llama.init_params(CFG, jax.random.PRNGKey(0), quantize=True)


def test_full_forward_equals_the_reference():
    """One whole forward, float32: four streams, low-rank queries, the
    absorbed form over the latent pool against the reference's expanded
    form, the sigmoid router with its bias, scaling and shared expert."""
    params = _params()
    rng = np.random.RandomState(0)
    t, page = 40, 8
    ids = rng.randint(1, CFG.vocab_size, (t,)).astype(np.int32)
    kv = llama.init_kv_cache(CFG, 256, dtype=jnp.float32)
    assert kv.latent and kv.k[0].shape == (256, CFG.latent_pool_width)
    hidden, kv = llama.forward(
        params, CFG, jnp.asarray(ids[None]),
        jnp.arange(t, dtype=jnp.int32)[None], kv,
        jnp.asarray(page + np.arange(t), jnp.int32),
        jnp.asarray((page + np.arange(64))[None], jnp.int32))
    # nothing outside models/ learns of the streams
    assert hidden.shape == (1, t, CFG.hidden_size)
    lps = jax.nn.log_softmax(llama.logits(params, CFG, hidden[0]), -1)
    got = np.asarray(lps[np.arange(t - 9, t - 1), ids[t - 8:]])
    want = _reference().token_logprobs(params, _hf(CFG), list(ids), 8, 64)
    np.testing.assert_allclose(got, want, atol=2e-5)


@pytest.mark.parametrize("backend", ["gather", "pallas"])
def test_chunked_prefill_then_decode_through_the_pool(backend):
    """A prompt prefilled in two chunks of whole pages, then 6 tokens
    decoded one at a time through the paged latent pool (`pallas`: the page
    writer and the decode kernel in interpret mode, as the chip runs them;
    `gather`: the slot-matrix oracle): every position's logits against the
    reference's, teacher-forced."""
    params = _params(1)
    rng = np.random.RandomState(1)
    ps, chunk, n_prompt, n_dec = 8, 16, 28, 6
    ids = rng.randint(1, CFG.vocab_size, (n_prompt + n_dec,)).astype(np.int32)
    kv = llama.init_kv_cache(CFG, 16 * ps, dtype=jnp.float32)
    table = np.arange(1, 9, dtype=np.int32)                 # pages 1..8
    slots = (table[:, None] * ps + np.arange(ps)).reshape(-1)
    pallas = backend == "pallas"
    rows = []
    for start in range(0, n_prompt, chunk):
        n = min(chunk, n_prompt - start)
        tok = np.zeros((1, chunk), np.int32)
        tok[0, :n] = ids[start:start + n]
        pos = (start + np.arange(chunk, dtype=np.int32))[None]
        w_slots = np.where(np.arange(chunk) < n, slots[start:start + chunk], 0)
        if pallas:
            attn = llama.AttnSpec.gather(
                None, write_tables=jnp.asarray(
                    table[start // ps:(start + chunk) // ps]),
                page_size=ps, interpret=True,
                block_tables=jnp.asarray(table[None]),
                q_pos0=jnp.asarray([start], jnp.int32),
                lengths=jnp.asarray([n], jnp.int32))
        else:
            attn = llama.AttnSpec.gather(jnp.asarray(slots[None]))
        hidden, kv = llama.forward(
            params, CFG, jnp.asarray(tok), jnp.asarray(pos), kv,
            jnp.asarray(w_slots, jnp.int32), attn)
        rows.append(np.asarray(hidden[0, :n]))
    for p in range(n_prompt, n_prompt + n_dec):
        tok = jnp.asarray(ids[p:p + 1][None])
        pos = jnp.asarray([[p]], jnp.int32)
        if pallas:
            attn = llama.AttnSpec.pallas_decode(
                jnp.asarray(table[None]), jnp.asarray([p + 1], jnp.int32),
                ps, write_pos=jnp.asarray([p], jnp.int32), interpret=True)
            w_slots = jnp.zeros((1,), jnp.int32)
        else:
            attn = llama.AttnSpec.gather(jnp.asarray(slots[None]))
            w_slots = jnp.asarray(slots[p:p + 1], jnp.int32)
        hidden, kv = llama.forward(params, CFG, tok, pos, kv, w_slots, attn)
        rows.append(np.asarray(hidden[0]))
    hidden = jnp.asarray(np.concatenate(rows))
    lps = jax.nn.log_softmax(llama.logits(params, CFG, hidden), -1)
    n = len(ids)
    got = np.asarray(lps[np.arange(n - 13, n - 1), ids[n - 12:]])
    want = _reference().token_logprobs(params, _hf(CFG), list(ids), 12, 64)
    np.testing.assert_allclose(got, want, atol=3e-5)


def test_stage_executors_refuse_the_streams_with_a_sentence():
    """Manual tp / tp_overlap (`layer_step`) carry one stream [B, T, D]."""
    params = _params()
    x = jnp.zeros((1, 2, 4 * CFG.hidden_size))
    for kw in (dict(tp_axis="tp"), dict(tp_axis="tp", tp_overlap=True,
                                        bt_shape=(1, 2))):
        with pytest.raises(ValueError, match="4 streams"):
            llama.layer_step(
                params["layers"][0], CFG.with_(num_experts=0), x, None, None,
                None, None, None, llama.AttnSpec(), None, layer=0, **kw)


# ------------------------------------ the controls behind the cell's tolerance


@pytest.fixture(scope="module")
def control_readings():
    """benchmark/controls/xing4_0.py at the configuration's rehearsal size:
    the reference with ONE thing changed, in the program's place, judged
    by the harness's `compare` under the configuration's limits."""
    mod = _load("controls", "xing4_0")
    return mod, mod.readings("xing4.0-29b-a4b-l6", [1], rehearse=True,
                             controls=("float32",) + mod.CONTROLS,
                             n_prompts=2)


@pytest.mark.parametrize("control", [
    "float32", "int8_weights", "h_res_identity", "row_softmax", "no_phi",
    "h_post_1", "no_q_norm", "no_router_bias", "no_shared", "routed_scale_1"])
def test_each_control_moves_the_logits(control_readings, control):
    """Nothing changed reads exactly 0 over the 32 judged positions (the
    first two check prompts, one of them past a prefill chunk); each
    control reads a finite gap above it, so a limit can be placed against
    it: every piece of the mechanism is SEEN by the comparison."""
    mod, got = control_readings
    assert set(got) == {"float32", *mod.CONTROLS}
    got = got[control]["1"]
    assert got["positions"] == 32 and np.isfinite(got["gap_max"])
    if control == "float32":
        assert got["gap_max"] == 0.0 and got["ok"]
        return
    assert 1e-4 < got["gap_mean"] <= got["gap_max"]
