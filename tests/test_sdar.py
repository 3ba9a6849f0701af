"""The SDAR family (`sdar_moe`: grouped-query attention with a norm a head,
softmax-routed experts in every layer, generated by diffusion over blocks)
below the engine, tiny preset on the CPU: the published config is the
preset and what is not served refuses by name; the forward under the
block-causal mask against `benchmark/references/sdar_moe.py` on both
backends; the mask ACTS at the tested size; the flash kernel under a block
against the gather oracle, and at block 1 the program it always was; the
transfer of `ops/sampling.sample_block`. The engine is
`tests/test_sdar_engine.py`'s."""

from __future__ import annotations

import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.models import llama
from dynamo_tpu.models.config import PRESETS, ModelConfig
from dynamo_tpu.ops.attention import paged_attention
from dynamo_tpu.ops.pallas_prefill import flash_prefill_attention
from dynamo_tpu.ops.sampling import sample_block

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CFG = PRESETS["tiny-sdar"].with_(dtype="float32")
# the catalog row's config (model-configs guide, architectures.jsonl) and
# the block keys the served config.json states beside them
PUBLISHED = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "max_position_embeddings": 32768, "max_window_layers": 48,
    "mlp_only_layers": [], "model_type": "sdar_moe",
    "moe_intermediate_size": 768, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 48, "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "rope_scaling": None, "rope_theta": 1000000, "sliding_window": None,
    "tie_word_embeddings": False, "use_sliding_window": False,
    "vocab_size": 151936,
    "block_length": 4, "denoising_steps": 2,
    "remasking_strategy": "sequential", "mask_token_id": 151669,
}


def _reference():
    spec = importlib.util.spec_from_file_location(
        "sdar_reference",
        os.path.join(ROOT, "benchmark", "references", "sdar_moe.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _hf(cfg):
    return dict(
        model_type="sdar_moe", vocab_size=cfg.vocab_size,
        hidden_size=cfg.hidden_size,
        num_attention_heads=cfg.num_heads,
        num_key_value_heads=cfg.num_kv_heads, head_dim=cfg.head_dim,
        rope_theta=cfg.rope_theta, rms_norm_eps=cfg.rms_norm_eps,
        num_experts=cfg.num_experts,
        num_experts_per_tok=cfg.num_experts_per_tok,
        norm_topk_prob=cfg.norm_topk_prob, block_length=cfg.block_length,
        denoising_steps=cfg.denoising_steps,
        remasking_strategy=cfg.remasking_strategy,
        mask_token_id=cfg.mask_token_id)


# one denoising step: a block's FIRST pass (four masks) fills all of it,
# so the reference reads every position of the block from that pass
_ONE_PASS = _hf(CFG.with_(denoising_steps=1))


# ------------------------------------------------------------ the config

def test_the_published_config_is_the_preset():
    got = ModelConfig.from_hf_config(PUBLISHED, name="sdar-30b-a3b")
    assert got == PRESETS["sdar-30b-a3b"]
    assert got.dlm and got.qk_norm and not PRESETS["tiny"].dlm
    assert (got.num_experts, got.num_experts_per_tok, got.expert_width,
            got.first_dense_layers, got.num_shared_experts) == (
                128, 8, 768, 0, 0)
    with open(os.path.join(
            ROOT, "benchmark", "configs", "sdar-30b-a3b-l6.json")) as f:
        served = json.load(f)
    # the file states every key of the catalog row but the depth it cut
    assert {k: v for k, v in served.items() if k in PUBLISHED} == {
        **PUBLISHED, "num_hidden_layers": 6}


@pytest.mark.parametrize("key,value", [
    ("use_sliding_window", True), ("rope_scaling", {"type": "yarn"}),
    ("mlp_only_layers", [0]), ("decoder_sparse_step", 2),
    ("attention_bias", True), ("hidden_act", "gelu"), ("block_length", 3),
    ("block_length", 1), ("denoising_steps", 3), ("denoising_steps", 0),
    ("remasking_strategy", "low_confidence_dynamic"),
    ("remasking_strategy", "low_confidence_static"),
    ("mask_token_id", 151936),
])
def test_what_is_not_served_refuses_by_name(key, value):
    with pytest.raises(ValueError, match=f"sdar_moe config: {key}="):
        ModelConfig.from_hf_config({**PUBLISHED, key: value})


def test_the_per_head_norms_are_leaves_of_every_layer():
    params = jax.eval_shape(
        lambda: llama.init_params(CFG, jax.random.PRNGKey(0)))
    for lp in params["layers"]:
        assert lp["q_norm"].shape == lp["k_norm"].shape == (CFG.head_dim,)
        assert lp["we_gate"].shape == (8, 64, 32) and "w_gate" not in lp
    plain = jax.eval_shape(
        lambda: llama.init_params(PRESETS["tiny"], jax.random.PRNGKey(0)))
    assert "q_norm" not in plain["layers"][0]


# ----------------------------------------------------------- the forward

def _forward_logprobs(params, ids, backend, ps=8):
    """The program's forward over the whole of `ids` under the block-causal
    mask, written through a paged cache; log-probabilities of the last
    block's positions with the mask token's logit excluded."""
    n, b = len(ids), CFG.block_length
    pages = -(-n // ps)
    kv = llama.init_kv_cache(CFG, (pages + 1) * ps, dtype=jnp.float32,
                             page_size=ps)
    tables = jnp.arange(1, pages + 1, dtype=jnp.int32)[None]
    smat = (tables[:, :, None] * ps + jnp.arange(ps)).reshape(1, -1)
    pos = jnp.arange(n, dtype=jnp.int32)[None]
    if backend == "gather":
        attn = llama.AttnSpec.gather(smat, page_size=ps, mask_block=b)
    else:
        attn = llama.AttnSpec.gather(
            smat, write_tables=tables[0], page_size=ps, interpret=True,
            block_tables=tables, q_pos0=pos[:, 0],
            lengths=jnp.asarray([n], jnp.int32), mask_block=b)
    tokens = jnp.asarray(ids, jnp.int32)[None]
    if backend == "pallas" and n % ps:   # whole pages for the page scatter
        pad = ps - n % ps
        tokens, pos = (jnp.pad(a, ((0, 0), (0, pad))) for a in (tokens, pos))
    hidden, _ = llama.forward(
        params, CFG, tokens, pos, kv, smat[0, :tokens.shape[1]], attn)
    lg = llama.logits(params, CFG, hidden[0, n - b:n])
    lg = jnp.where(jnp.arange(CFG.vocab_size) == CFG.mask_token_id,
                   -jnp.inf, lg)
    return np.asarray(jax.nn.log_softmax(lg, axis=-1))


@pytest.mark.parametrize("backend", ["gather", "pallas"])
def test_forward_under_the_block_causal_mask_equals_the_reference(backend):
    """24 tokens whose last block is four mask tokens: the program's one
    forward is the reference's first denoising pass of that block, every
    earlier block seeing all of itself and nothing later."""
    params = llama.init_params(CFG, jax.random.PRNGKey(0), dtype=jnp.float32)
    rng = np.random.RandomState(1)
    ids = [int(t) for t in rng.randint(1, 250, (20,))] + [
        CFG.mask_token_id] * 4
    got = _forward_logprobs(params, ids, backend)
    want = _reference().logprob_rows(params, _ONE_PASS, ids, 4, 32)
    np.testing.assert_allclose(got, want, atol=3e-5)


def test_the_in_block_mask_acts_at_the_tested_size():
    """With a causal line inside the block the reference gives OTHER
    log-probabilities at a block's first positions (which then miss the
    later ones; its last sees all of it either way and differs only
    through the layer below): the tests above would not pass on a causal
    program, nor on one that encodes the prompt causally."""
    params = llama.init_params(CFG, jax.random.PRNGKey(0), dtype=jnp.float32)
    rng = np.random.RandomState(2)
    ids = [int(t) for t in rng.randint(1, 250, (24,))]
    ref = _reference()
    block = ref.logprob_rows(params, _ONE_PASS, ids, 4, 32)
    causal = ref.logprob_rows(params, _ONE_PASS, ids, 4, 32, in_block=False)
    keep = np.arange(CFG.vocab_size) != CFG.mask_token_id   # -inf there
    gap = np.abs(block - causal)[:, keep].max(axis=1)
    assert (gap > 1e-2).all() and gap[0] > gap[3]
    prompt = ref.logprob_rows(params, _ONE_PASS, ids, 4, 32,
                              prompt_block=False)
    assert np.abs(block - prompt)[:, keep].max() > 1e-2


# ------------------------------------------------------------ the kernel

def _pool(key, slots, width):
    return jax.random.normal(key, (slots, width), jnp.float32)


@pytest.mark.parametrize("block", [1, 4])
def test_flash_kernel_under_a_block_against_the_gather_oracle(block):
    """Rows of `q_len` 4 from mid-page block starts beside a chunk row and
    a padding row, keys and values already in the pool: the flash kernel
    (interpret) and the gather oracle agree under `mask_block` 4, and
    under 1 both are causal by position."""
    ps, kh, hd, h = 8, 2, 16, 4
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    k_pool, v_pool = _pool(ks[0], 64, kh * hd), _pool(ks[1], 64, kh * hd)
    q = jax.random.normal(ks[2], (4, 8, h, hd), jnp.float32)
    tables = jnp.asarray([[1, 2, 3, 0], [4, 5, 0, 0], [6, 7, 0, 0],
                          [0, 0, 0, 0]], jnp.int32)
    pos0 = jnp.asarray([12, 4, 8, 0], jnp.int32)
    lens = jnp.asarray([4, 4, 8, 0], jnp.int32)
    kw = {"mask_block": block} if block > 1 else {}
    got = flash_prefill_attention(
        q, k_pool, v_pool, tables, pos0, lens, page_size=ps, interpret=True,
        **kw)
    smat = (tables[:, :, None] * ps + jnp.arange(ps)).reshape(4, -1)
    want = paged_attention(
        q, k_pool, v_pool, smat, pos0[:, None] + jnp.arange(8)[None],
        q_lens=lens, **kw)
    np.testing.assert_allclose(got, want, atol=2e-5)
    if block > 1:
        causal = flash_prefill_attention(
            q, k_pool, v_pool, tables, pos0, lens, page_size=ps,
            interpret=True)
        # a block's first query differs, its last is the same either way
        assert np.abs(np.asarray(got - causal))[0, 0].max() > 1e-3
        np.testing.assert_allclose(got[0, 3], causal[0, 3], atol=2e-6)


def test_block_1_is_the_program_it_was():
    """`mask_block` 1, said or unsaid, traces to one and the same program
    and gives the same bits (the parent's own programs are pinned in
    tests/test_granite_hybrid.py and tests/test_sdar_engine.py)."""
    ps, kh, hd, h = 8, 2, 16, 4
    args = (jnp.ones((2, 8, h, hd)), jnp.ones((32, kh * hd)),
            jnp.ones((32, kh * hd)), jnp.ones((2, 2), jnp.int32),
            jnp.zeros((2,), jnp.int32), jnp.full((2,), 8, jnp.int32))
    said = jax.make_jaxpr(lambda *a: flash_prefill_attention(
        *a, page_size=ps, interpret=True, mask_block=1))(*args)
    unsaid = jax.make_jaxpr(lambda *a: flash_prefill_attention(
        *a, page_size=ps, interpret=True))(*args)
    assert str(said) == str(unsaid)
    blocked = jax.make_jaxpr(lambda *a: flash_prefill_attention(
        *a, page_size=ps, interpret=True, mask_block=4))(*args)
    assert str(blocked) != str(unsaid)


# rows of a block pass, as (first position of the block, live): page 8 and
# 2 pages a work item, so an item is 16 positions
_BLOCK_ROWS = {
    "mid-page": [(12, True), (4, True)],
    "ends-on-a-page": [(4, True), (20, True)],       # positions 4-7, 20-23
    "ends-on-an-item": [(12, True), (28, True)],     # positions 12-15, 28-31
    "one-page": [(0, True), (4, True)],
    "partly-live-last-item": [(40, True), (16, True), (68, True)],
    "padding-rows": [(0, False), (24, True), (0, False), (36, True),
                     (0, False)],
    "every-row-padding": [(0, False), (0, False)],
}


@pytest.mark.parametrize("g,vd", [(1, 16), (8, 16), (2, 8)],
                         ids=["G1", "G8", "narrow-values"])
@pytest.mark.parametrize("case", sorted(_BLOCK_ROWS))
def test_block_kernel_against_the_gather_oracle(case, g, vd):
    """The block pass's own kernel (ops/pallas_block.py, interpret) against
    the gather oracle under `mask_block` 4, keys and values already in the
    pool: a block that starts mid-page, one whose 4 positions end exactly
    on a page's and on a work item's last position, a row of one page, a
    row of several items whose last is partly live, padding rows between
    live ones (zeros out, no item) and nothing but padding; one query head
    a KV head and eight; values narrower than keys."""
    from dynamo_tpu.ops.pallas_block import block_paged_attention

    ps, ppb, kh, hd, width = 8, 2, 2, 16, 10
    rows = _BLOCK_ROWS[case]
    ks = jax.random.split(jax.random.PRNGKey(len(case)), 3)
    slots = (1 + sum(-(-(p + 4) // ps) for p, live in rows if live)) * ps
    k_pool, v_pool = _pool(ks[0], slots, kh * hd), _pool(ks[1], slots, kh * vd)
    q = jax.random.normal(ks[2], (len(rows), 4, kh * g, hd), jnp.float32)
    tables, nxt = np.zeros((len(rows), width), np.int32), 1
    for i, (p, live) in enumerate(rows):
        n = -(-(p + 4) // ps) if live else 0
        tables[i, :n] = np.arange(nxt, nxt + n)
        nxt += n
    pos0 = jnp.asarray([p for p, _ in rows], jnp.int32)
    lens = jnp.asarray([4 if live else 0 for _, live in rows], jnp.int32)
    got = block_paged_attention(
        q, k_pool, v_pool, jnp.asarray(tables), pos0, lens, page_size=ps,
        mask_block=4, pages_per_block=ppb, interpret=True)
    smat = (tables[:, :, None] * ps + np.arange(ps)).reshape(len(rows), -1)
    want = paged_attention(
        q, k_pool, v_pool, jnp.asarray(smat),
        pos0[:, None] + jnp.arange(4)[None], q_lens=lens, mask_block=4)
    assert got.shape == want.shape == (len(rows), 4, kh * g, vd)
    np.testing.assert_allclose(got, want, atol=2e-5)
    dead = [i for i, (_, live) in enumerate(rows) if not live]
    assert not np.asarray(got)[dead].any()


def test_block_kernel_reads_a_block_that_starts_off_a_multiple_as_the_oracle():
    """No caller makes one (a block starts at a multiple of its length),
    but the mask is the formula, not that fact: from position 6 the first
    two queries see keys through 7 and the last two through 11."""
    from dynamo_tpu.ops.pallas_block import block_paged_attention

    ps, kh, hd = 8, 2, 16
    ks = jax.random.split(jax.random.PRNGKey(6), 3)
    k_pool, v_pool = _pool(ks[0], 32, kh * hd), _pool(ks[1], 32, kh * hd)
    q = jax.random.normal(ks[2], (1, 4, 4, hd), jnp.float32)
    tables = jnp.asarray([[1, 2, 0]], jnp.int32)
    pos0, lens = jnp.asarray([6], jnp.int32), jnp.asarray([4], jnp.int32)
    got = block_paged_attention(
        q, k_pool, v_pool, tables, pos0, lens, page_size=ps, mask_block=4,
        interpret=True)
    smat = (tables[:, :, None] * ps + jnp.arange(ps)).reshape(1, -1)
    want = paged_attention(
        q, k_pool, v_pool, smat, pos0[:, None] + jnp.arange(4)[None],
        q_lens=lens, mask_block=4)
    np.testing.assert_allclose(got, want, atol=2e-5)
    assert np.abs(np.asarray(got[0, 0] - got[0, 3])).max() > 1e-3


def _custom_calls(jaxpr) -> list[str]:
    """Names of the jitted functions that hold a pallas_call, outermost
    first: which kernel a call traced to."""
    found = []

    def walk(jp, path):
        for eqn in jp.eqns:
            if eqn.primitive.name == "pallas_call":
                found.append(path[-1] if path else "")
            for sub in jax.core.jaxprs_in_params(eqn.params):
                name = eqn.params.get("name", "")
                walk(sub, path + [name] if name else path)

    walk(jaxpr.jaxpr, [])
    return found


def test_which_ragged_call_takes_which_kernel():
    """`ragged_paged_attention` routes by what it can see in the call: a
    block pass (`q_len == mask_block > 1`) traces to the block kernel; a
    chunk of whole blocks under the same mask and every call at
    `mask_block` 1 (said or unsaid, at any query length, 4 among them)
    trace to the flash prefill kernel, the program they always were."""
    from dynamo_tpu.ops.pallas_attention import ragged_paged_attention

    ps, kh, hd, h = 8, 2, 16, 4

    def args(t):
        return (jnp.ones((2, t, h, hd)), jnp.ones((32, kh * hd)),
                jnp.ones((32, kh * hd)), jnp.ones((2, 2), jnp.int32),
                jnp.zeros((2,), jnp.int32), jnp.full((2,), t, jnp.int32))

    def ragged(t, **kw):
        return jax.make_jaxpr(lambda *a: ragged_paged_attention(
            *a, page_size=ps, interpret=True, **kw))(*args(t))

    def flash(t, **kw):
        return jax.make_jaxpr(lambda *a: flash_prefill_attention(
            *a, page_size=ps, interpret=True, **kw))(*args(t))

    assert _custom_calls(ragged(4, mask_block=4)) == ["block_paged_attention"]
    for t, kw in ((8, {"mask_block": 4}), (4, {}), (4, {"mask_block": 1}),
                  (8, {}), (1, {})):
        got = ragged(t, **kw)
        assert _custom_calls(got) == ["flash_prefill_attention"], (t, kw)
        flash_kw = kw if kw.get("mask_block", 1) > 1 else {}
        assert str(got) == str(flash(t, **flash_kw)), (t, kw)


# ---------------------------------------------------------- the transfer

@pytest.mark.parametrize("n_fill", [1, 2, 4])
def test_sample_block_fills_a_fixed_count_of_the_masked(n_fill):
    rows, b, v, mask_id = 5, 4, 64, 7
    lg = jax.random.normal(jax.random.PRNGKey(3), (rows * b, v))
    lg = lg.at[:, mask_id].set(50.0)     # the likeliest: never sampled
    masked = jnp.asarray([[1, 1, 1, 1], [0, 0, 1, 1], [0, 1, 0, 1],
                          [0, 0, 0, 1], [0, 0, 0, 0]], bool)
    zero = jnp.zeros((rows,), jnp.float32)
    ids, fill, lps = sample_block(
        lg, masked, jax.random.PRNGKey(4), zero, zero.astype(jnp.int32),
        zero + 1, n_fill=n_fill, mask_token_id=mask_id,
        all_greedy=True, return_logprobs=True)
    ids, fill, lps = (np.asarray(a) for a in (ids, fill, lps))
    assert (ids != mask_id).all() and (lps <= 0).all()
    # the leftmost `n_fill` of a row's masked positions, or what is left
    left = np.cumsum(np.asarray(masked), axis=1) - 1
    np.testing.assert_array_equal(
        fill, np.asarray(masked) & (left < n_fill))
    assert fill.sum(axis=1).tolist() == [
        min(n_fill, m) for m in (4, 2, 2, 1, 0)]
    # the log-probability is the sampled token's with the mask excluded
    flat = np.asarray(lg, np.float64)
    flat[:, mask_id] = -np.inf
    logz = np.log(np.exp(flat - flat.max(1, keepdims=True)).sum(1)) + flat.max(1)
    np.testing.assert_allclose(
        lps.reshape(-1), flat[np.arange(rows * b), ids.reshape(-1)] - logz,
        atol=1e-5)


def test_sample_block_samples_a_position_with_temperature_and_top_k():
    rows, b, v = 64, 4, 32
    lg = jnp.tile(jnp.linspace(0.0, 3.0, v)[None], (rows * b, 1))
    masked = jnp.ones((rows, b), bool)
    ids, fill, _ = sample_block(
        lg, masked, jax.random.PRNGKey(5), jnp.full((rows,), 1.0),
        jnp.full((rows,), 4, jnp.int32), jnp.ones((rows,)), n_fill=2,
        mask_token_id=v - 1)
    ids = np.asarray(ids)
    # top-4 of what is left once the mask token (the largest logit) is out
    assert set(np.unique(ids)) <= {v - 5, v - 4, v - 3, v - 2}
    assert len(np.unique(ids)) > 1 and np.asarray(fill).sum() == rows * 2
