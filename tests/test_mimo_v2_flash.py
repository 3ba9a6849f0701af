"""The MiMo-V2 family (`mimo_v2_flash`): window layers with a learned sink
beside full-attention layers, keys wider than values, sigmoid-routed
experts of which the layer holds a share, two kinds of KV pool whose
window kind releases its pages behind the window. The served path against
`benchmark/references/mimo_v2_flash.py` (plain float32 `jax.numpy`, nothing
imported from `dynamo_tpu`), the kernels against the gather oracle."""

from __future__ import annotations

import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.models.config import FULL, PRESETS, WINDOW, ModelConfig
from dynamo_tpu.ops.attention import paged_attention
from dynamo_tpu.ops.pallas_attention import fused_paged_decode_attention
from dynamo_tpu.ops.pallas_prefill import flash_prefill_attention

from .test_engine import collect, greedy_request, make_engine

CFG = PRESETS["tiny-mimo"]
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _reference():
    return _load(os.path.join(
        ROOT, "benchmark", "references", "mimo_v2_flash.py"), "ref_mimo")


def _hf(cfg: ModelConfig) -> dict:
    """The `config.json` a checkpoint of `cfg` would carry, as a chip's
    share states it (`n_routed_experts` = held, `router_width` = all)."""
    return {
        "model_type": "mimo_v2_flash", "vocab_size": cfg.vocab_size,
        "hidden_size": cfg.hidden_size,
        "intermediate_size": cfg.intermediate_size,
        "num_hidden_layers": cfg.num_layers,
        "num_attention_heads": cfg.num_heads,
        "num_key_value_heads": cfg.num_kv_heads, "head_dim": cfg.head_dim,
        "rope_theta": cfg.rope_theta, "layernorm_epsilon": cfg.rms_norm_eps,
        "max_position_embeddings": cfg.max_position_embeddings,
        "tie_word_embeddings": False,
        "partial_rotary_factor": cfg.rotary_dim / cfg.head_dim + 1e-3,
        "sliding_window": cfg.sliding_window,
        "sliding_window_size": cfg.sliding_window,
        "swa_rope_theta": cfg.swa_rope_theta, "attention_bias": False,
        "v_head_dim": cfg.v_head_dim,
        "hybrid_layer_pattern": list(cfg.layer_kinds),
        "add_swa_attention_sink_bias": True,
        "add_full_attention_sink_bias": False,
        "moe_layer_freq": [
            int(cfg.is_moe_layer(l)) for l in range(cfg.num_layers)],
        "moe_intermediate_size": cfg.moe_intermediate_size,
        "n_routed_experts": cfg.experts_held,
        "router_width": cfg.num_experts, "expert_offset": cfg.expert_offset,
        "n_shared_experts": None,
        "num_experts_per_tok": cfg.num_experts_per_tok,
        "norm_topk_prob": True, "scoring_func": "sigmoid", "n_group": 1,
        "topk_group": 1, "topk_method": "noaux_tc",
        "routed_scaling_factor": None,
        "swa_num_attention_heads": cfg.num_heads,
        "swa_num_key_value_heads": cfg.swa_num_kv_heads,
        "swa_head_dim": cfg.swa_head_dim, "swa_v_head_dim": cfg.swa_v_head_dim,
        "attention_value_scale": cfg.attn_value_scale,
    }


# ------------------------------------------------------------ configuration


def _catalog_row() -> dict:
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(path):
        pytest.skip("the model-configs catalog is not on this machine")
    with open(path) as f:
        for line in f:
            row = json.loads(line)
            if row["name"] == "MiMo-V2-Flash":
                return row["config"]
    pytest.skip("the catalog has no MiMo-V2-Flash row")


def test_published_config_is_the_preset():
    got = ModelConfig.from_hf_config(_catalog_row(), name="mimo-v2-flash")
    assert got == PRESETS["mimo-v2-flash"]
    assert got.layers_of(WINDOW) == 39 and got.layers_of(FULL) == 9
    assert got.held_experts == got.num_experts == 256
    assert ModelConfig.from_hf_config(_hf(CFG), name="tiny-mimo") == CFG


def test_benchmark_file_with_reduced_put_back_is_the_preset():
    """What `benchmark/lib/harness.check_preset` requires of the cell's
    configuration, and what the file as RUN is: a share."""
    with open(os.path.join(
            ROOT, "benchmark", "configs", "mimo-v2-flash-l7.json")) as f:
        config = json.load(f)
    cb = config.pop("benchmark")
    assert sorted(cb["reduced"]) == [
        "hybrid_layer_pattern", "moe_layer_freq", "n_routed_experts",
        "num_hidden_layers", "vocab_size"]
    whole = ModelConfig.from_hf_config(
        {**config, **cb["reduced"]}, name="mimo-v2-flash")
    assert whole == PRESETS["mimo-v2-flash"]
    run = ModelConfig.from_hf_config(config, name="l7")
    assert (run.num_layers, run.num_experts, run.held_experts,
            run.expert_offset, run.vocab_size) == (7, 256, 16, 0, 19072)
    assert run.layer_kinds == (0, 1, 1, 1, 1, 0, 1)
    published = _catalog_row()
    for key, value in published.items():   # every width as published
        if key not in cb["reduced"]:
            assert config[key] == value, key


@pytest.mark.parametrize("key,value", [
    ("n_group", 8), ("topk_group", 4), ("attention_bias", True),
    ("add_full_attention_sink_bias", True), ("routed_scaling_factor", 2.5),
    ("n_shared_experts", 1), ("scoring_func", "softmax"),
    ("topk_method", "greedy"), ("swa_num_attention_heads", 2),
    ("moe_layer_freq", [0, 1, 0, 1, 1, 1, 1]),
    ("hybrid_layer_pattern", [0, 1, 1]),
])
def test_unserved_mimo_keys_are_refused_by_name(key, value):
    with pytest.raises(ValueError, match=key):
        ModelConfig.from_hf_config({**_hf(CFG), key: value})


def test_deepseek_refusal_names_its_model_type_not_sigmoid_in_general():
    from .test_deepseek_v2 import PUBLISHED

    with pytest.raises(ValueError, match="for this model_type"):
        ModelConfig.from_hf_config({**PUBLISHED, "scoring_func": "sigmoid"})


# ------------------------------------------------------------------ kernels


def _case(kh, h, kd, vd, ps, lens, seed=0, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    b, w = len(lens), 8
    pages = 1 + b * w

    def arr(*shape):
        return jnp.asarray(rng.normal(size=shape), dtype)

    tables = 1 + np.arange(b * w, dtype=np.int32).reshape(b, w)
    slots = (tables[:, :, None] * ps + np.arange(ps)).reshape(b, -1)
    return dict(
        kc=arr(pages * ps, kh * kd), vc=arr(pages * ps, kh * vd),
        tables=tables, slots=slots, lens=np.asarray(lens, np.int32),
        sink=jnp.asarray(rng.normal(size=(h,)), jnp.float32), arr=arr)


# contexts under the window (1, 5), of exactly a page (8), a window that
# starts on a page's first row (24 at window 16, 16 and 64 at window 8: one
# page slot dead) beside rows that fill every slot (17, 33, 40), a fused
# write that opens a page (1, 17, 33), empty rows between live ones, and
# nine live rows: not a multiple of any group
GROUP_LENS = [1, 5, 0, 16, 17, 0, 0, 40, 64, 8, 24, 33]


@pytest.mark.parametrize("kh,window,sink,group,ps,lens", [
    (2, 16, True, 0, 8, None), (1, 0, False, 0, 8, None),
    (2, 16, False, 0, 8, None), (2, 0, True, 0, 8, None),
    # the grouped item (a window that fits one work item): three page
    # slots a sequence at window 16 over pages of 8, two at window 8 (the
    # benchmark's geometry: a window of one page); pages of 32: the fused
    # write sends back the 16 rows around the new one, the first or the
    # second half of a page
    (2, 16, True, 4, 8, GROUP_LENS), (2, 8, True, 4, 8, GROUP_LENS),
    (1, 32, False, 2, 32, [0, 9, 0, 32, 49, 130, 80]),
])
def test_decode_kernel_window_sink_and_widths_match_the_oracle(
        kh, window, sink, group, ps, lens):
    """Keys 24 wide over values 16 wide, a window start a sequence (the
    pages behind it NAMED TRASH in the table AND poisoned in the pool, as
    after a release), a sink logit a head: the fused kernel (interpret
    mode) against the gather oracle, and the written rows land where the
    oracle's do. `group`: the static window is handed over and the work
    item holds that many sequences; its outputs are the per-sequence
    list's, its pools to the bit, and no page a row does not hold is
    read (NaN in, finite out) or written (NaN still)."""
    h, kd, vd = 4, 24, 16
    c = _case(kh, h, kd, vd, ps, lens or [1, 5, 16, 17, 40, 64])
    b, lens = len(c["lens"]), c["lens"]
    q, nk, nv = c["arr"](b, h, kd), c["arr"](b, kh * kd), c["arr"](b, kh * vd)
    live = lens > 0
    wpos = lens - 1
    tb = c["tables"].copy()
    held = np.zeros(c["kc"].shape[0], bool)
    for i in range(b):
        first = max(lens[i] - window, 0) // ps if window else 0
        tb[i, :first] = 0
        pages = tb[i, first:-(-lens[i] // ps)]
        held[(pages[:, None] * ps + np.arange(ps)).ravel()] = True
    kc = jnp.where(held[:, None], c["kc"], jnp.nan)
    vc = jnp.where(held[:, None], c["vc"], jnp.nan)
    kwargs = dict(sink=c["sink"] if sink else None, page_size=ps,
                  interpret=True)
    starts = jnp.maximum(lens - window, 0) if window else None
    args = (q, nk, nv, kc, vc, jnp.asarray(tb), jnp.asarray(lens),
            jnp.asarray(wpos))
    out, k2, v2 = fused_paged_decode_attention(*args, starts=starts, **kwargs)
    if group:
        per_sequence = out, k2, v2
        out, k2, v2 = fused_paged_decode_attention(
            *args, window=window, window_group=group, **kwargs)
        # (a one-page row's dots run over its dead page slot too: the
        # CPU's dot may add in another order there; the pools may not)
        np.testing.assert_allclose(out, per_sequence[0], atol=1e-6)
        np.testing.assert_array_equal(k2, per_sequence[1])
        np.testing.assert_array_equal(v2, per_sequence[2])
    ws = c["slots"][np.arange(b), wpos][live]
    ko, vo = c["kc"].at[ws].set(nk[live]), c["vc"].at[ws].set(nv[live])
    want = paged_attention(
        q[:, None], ko, vo, jnp.asarray(c["slots"]),
        jnp.asarray(np.maximum(wpos, 0))[:, None],
        window=window, sink=c["sink"] if sink else None)[:, 0]
    np.testing.assert_allclose(out[live], want[live], atol=2e-6)
    assert out.shape == (b, h, vd) and not np.asarray(out[~live]).any()
    np.testing.assert_array_equal(k2[ws], nk[live])
    np.testing.assert_array_equal(v2[ws], nv[live])
    np.testing.assert_array_equal(k2[held], ko[held])
    assert np.isnan(np.asarray(k2)[~held]).all()
    assert np.isnan(np.asarray(v2)[~held]).all()


@pytest.mark.parametrize("kh,starts,sink,digest", [
    (2, False, False, "b957f8c2d9ef4a2d"), (2, True, True, "d80f51a0a9653c29"),
    (1, True, False, "7d51362a90a4a274")])
def test_a_call_without_a_window_is_the_parent_s_to_the_bit(
        kh, starts, sink, digest):
    """Every model but the hybrid one calls the unquantized kernel with no
    static window: outputs and pools hash to what the tree before the
    grouped item (PR 36, f257dfe) gave on the same inputs."""
    import hashlib

    h, kd, vd, ps = 4, 24, 16, 8
    c = _case(kh, h, kd, vd, ps, [1, 5, 0, 16, 17, 40, 64], seed=3)
    b, lens = len(c["lens"]), c["lens"]
    q, nk, nv = c["arr"](b, h, kd), c["arr"](b, kh * kd), c["arr"](b, kh * vd)
    res = fused_paged_decode_attention(
        q, nk, nv, c["kc"], c["vc"], jnp.asarray(c["tables"]),
        jnp.asarray(lens), jnp.asarray(lens - 1),
        starts=jnp.maximum(lens - 16, 0) if starts else None,
        sink=c["sink"] if sink else None, page_size=ps, interpret=True)
    m = hashlib.sha256()
    for a in res:
        m.update(np.asarray(a).tobytes())
    assert m.hexdigest()[:16] == digest


@pytest.mark.parametrize("window", [16, 128])
def test_streamed_pages_with_a_window_counts_from_the_start_s_page(window):
    """The digest's count of the pages a window layer's decode kernel
    copies in is the kernel's own rule. On the per-sequence list (a
    window too long for one work item, or `starts` alone) the list begins
    at the page that holds the window's first position; the grouped item
    copies `window_pages` slots for each of its `group` entries, a dead
    slot (the sequence's first page again) and a partial item's repeats
    included, a call (a row of the leading axes) at a time."""
    from dynamo_tpu.ops.pallas_attention import (
        window_grouped,
        window_items,
        window_pages,
        streamed_pages,
    )

    ps = 8
    lens = np.array([[1, 7, 8, 9, 0], [16, 17, 130, 257, 44]])
    starts = np.maximum(lens - window, 0)
    want = int(np.sum(-(-lens // ps) - starts // ps))
    assert streamed_pages(lens, ps, starts=starts) == want
    assert streamed_pages(lens, ps, pages_per_block=1, starts=starts) == want
    assert streamed_pages(lens, ps, pages_per_block=1, window=window) == want
    assert streamed_pages(lens, ps) == int(np.sum(-(-lens // ps)))
    # 4 and 5 live rows, 2 an item: 2 + 3 items of 2 x 3 page slots
    assert window_pages(16, ps) == 3 and window_pages(128, 128) == 2
    assert list(window_items(lens, 2, xp=np)) == [2, 3]
    assert int(window_items(jnp.asarray(lens[1]), 4)) == 2
    if window_grouped(window, ps, 4):
        assert streamed_pages(lens, ps, window=window, group=2) == 5 * 2 * 3
        assert streamed_pages(lens, ps, window=window, group=4) == 3 * 4 * 3
    else:   # 17 page slots: the per-sequence list
        assert window == 128
        assert streamed_pages(lens, ps, window=window) == want


@pytest.mark.parametrize("kh,window,sink", [(2, 16, True), (1, 0, False)])
def test_prefill_kernel_window_sink_and_widths_match_the_oracle(
        kh, window, sink):
    h, kd, vd, ps, t = 4, 24, 16, 8, 16
    c = _case(kh, h, kd, vd, ps, [5, 16, 17, 40, 64], seed=1)
    b, lens = len(c["lens"]), c["lens"]
    pos0 = np.maximum((lens // ps) * ps - t, 0).astype(np.int32)
    tv = np.minimum(lens - pos0, t).astype(np.int32)
    q = c["arr"](b, t, h, kd)
    tb = c["tables"].copy()
    for i in range(b):   # the pages a release would have taken
        tb[i, :max(pos0[i] - window + 1, 0) // ps if window else 0] = 0
    out = flash_prefill_attention(
        q, c["kc"], c["vc"], jnp.asarray(tb), jnp.asarray(pos0),
        jnp.asarray(tv), sink=c["sink"] if sink else None, page_size=ps,
        interpret=True, window=window)
    want = paged_attention(
        q, c["kc"], c["vc"], jnp.asarray(c["slots"]),
        jnp.asarray(pos0[:, None] + np.arange(t)[None]),
        q_lens=jnp.asarray(tv), window=window,
        sink=c["sink"] if sink else None)
    np.testing.assert_allclose(out, want, atol=2e-6)
    assert out.shape == (b, t, h, vd)


# ------------------------------------------------------------- expert layer


def test_sigmoid_bias_selection_matches_a_numpy_oracle():
    from dynamo_tpu.models.moe import init_moe_params, route

    lp = init_moe_params(CFG, jax.random.PRNGKey(1), dtype=jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(2), (33, CFG.hidden_size))
    w, idx = route(lp, CFG, x)
    logits = np.asarray(x, np.float64) @ np.asarray(lp["router"], np.float64)
    s = 1.0 / (1.0 + np.exp(-logits))
    chosen = s + np.asarray(lp["router_bias"], np.float64)
    want_i = np.argsort(-chosen, axis=1)[:, :CFG.num_experts_per_tok]
    want_w = np.take_along_axis(s, want_i, axis=1)   # WITHOUT the bias
    want_w = want_w / want_w.sum(axis=1, keepdims=True)
    np.testing.assert_array_equal(np.sort(idx, 1), np.sort(want_i, 1))
    np.testing.assert_allclose(
        np.sort(w, 1), np.sort(want_w, 1), rtol=1e-5)
    # the bias moves a selection somewhere: it is judged
    assert (np.argsort(-s, axis=1)[:, :2] != want_i).any()


@pytest.mark.parametrize("scored,tokens", [(8, 9), (16, 128)])
def test_the_shares_add_up(scored, tokens):
    """The expert layer's output summed over all shares (2 experts held
    each) equals the uncut layer's, and the reference's uncut layer: at 4
    shares of 8 (a share's pass is one block, the whole width) and at 8
    shares of 16 over 512 pairs (a pass of 128-row blocks, as many as
    hold a pair of the share's: `models/moe.py: block_rows`)."""
    from dynamo_tpu.models.moe import init_moe_params, moe_block

    key = jax.random.PRNGKey(5)
    x = jax.random.normal(jax.random.PRNGKey(6), (2, tokens, CFG.hidden_size))
    whole_cfg = CFG.with_(num_experts=scored, experts_held=scored)
    whole = init_moe_params(whole_cfg, key, dtype=jnp.float32)
    want = moe_block(whole, whole_cfg, x)
    total = jnp.zeros_like(want)
    ran = []
    for share in range(scored // 2):
        cfg = whole_cfg.with_(experts_held=2, expert_offset=2 * share)
        lp = init_moe_params(cfg, key, dtype=jnp.float32)
        np.testing.assert_array_equal(
            lp["we_gate"], whole["we_gate"][2 * share:2 * share + 2])
        stats = []
        total = total + moe_block(lp, cfg, x, stats=stats)
        assert int(stats[0][0]) <= 2   # load over the experts HELD
        ran.append(int(stats[0][2]))
        assert ran[-1] == (1 if scored == 8 else -(-int(stats[0][3]) // 128))
    assert scored == 8 or max(ran) <= 2 < 4    # never the whole width
    np.testing.assert_allclose(total, want, atol=1e-5)
    ref = _reference()
    lp = {**whole, "mlp_norm": jnp.ones((CFG.hidden_size,))}
    xf = x.reshape(-1, CFG.hidden_size)
    with jax.default_matmul_precision("highest"):
        # the reference norms its input: hand both the same normed rows
        normed = ref._rms_norm(xf, lp["mlp_norm"], 1e-5)
        got = ref._expert_ffn(
            xf, lp, eps=1e-5, k=2, renorm=True, offset=0, use_bias=True) - xf
        ours = moe_block(whole, whole_cfg, normed[None])[0]
    np.testing.assert_allclose(ours, got, atol=2e-5)


# ------------------------------------------------------------ served tokens


@pytest.mark.parametrize("backend", ["gather", "pallas"])
async def test_served_logprobs_match_the_reference(backend):
    """Through the engine: a prompt of 75 tokens (past the 16-token
    window, across released pages) prefilled in three chunks, then 12
    tokens decoded through both kinds of pool, each served
    log-probability against the reference's, teacher-forced."""
    engine = make_engine(model=CFG, attn_backend=backend, prefill_chunk=32,
                         decode_steps=4)
    assert engine.attention_backend["kind"] == backend
    assert engine.kv.k[0].shape[0] > engine.kv.k[1].shape[0]  # two kinds
    rng = np.random.RandomState(3)
    prompt = [int(x) for x in rng.randint(1, CFG.vocab_size, (75,))]
    pre = greedy_request(prompt, max_tokens=12)
    pre.sampling_options.logprobs = True
    tokens, finish, frames = await collect(engine, pre)
    assert len(tokens) == 12 and finish == "length"
    served = [lp for f in frames for lp in f.get("log_probs") or []]
    want = _reference().token_logprobs(
        engine.params, _hf(CFG), prompt + tokens, 12, 96)
    np.testing.assert_allclose(np.asarray(served), want, atol=5e-5)
    m = engine.metrics()
    # 87 tokens over pages of 8, window 16: all but the last pages went
    assert m["kv_window_pages_released_total"] >= 7
    assert m["kv_window_pages_used"] == 0 == engine.allocator.pages_used
    rows = engine.flight.snapshot()
    decodes = [r for r in rows if r["kind"] == "decode"]
    assert decodes and all(
        0 < r["kv_win_pages_held"] < r["kv_pages_held_full"] for r in decodes)
    assert all(r["kv_frac"] == max(r["kv_frac_full"], r["kv_frac_win"])
               for r in rows if r["kind"] in ("prefill", "decode"))
    if backend == "pallas":
        # the full kind streams what it holds; a window layer's kernel
        # walks ceil(rows / group) work items a step (one row here), each
        # a copy of every page slot (3 at window 16 over pages of 8) of
        # every entry, a partial item's repeats included
        from dynamo_tpu.ops.pallas_attention import WINDOW_GROUP, window_pages

        for r in decodes:
            assert r["kv_pages_held"] == (
                r["kv_pages_held_full"] + r["kv_win_pages_held"])
            steps = r["tokens"] // r["rows"]
            assert r["kv_win_items"] == -(-r["rows"] // WINDOW_GROUP) * steps
            assert r["kv_pages_streamed"] - r["kv_pages_held_full"] == (
                r["kv_win_items"] * WINDOW_GROUP * window_pages(16, 8))
    assert all(r["kv_win_items"] == 0 for r in rows
               if backend == "gather" or r["kind"] != "decode")
    loads = [r for r in rows if r["moe_experts_hit"]]
    assert loads and all(r["moe_experts_hit"] <= 4.0 for r in loads)
    # 4 of 8 held: twice the even share is every row, one block a pass,
    # over at most the one row's two pairs
    assert all(r["moe_row_blocks"] == 1.0 and 0 < r["moe_pairs_held"] <= 2.0
               for r in loads)
    await engine.close()


async def test_window_pages_release_and_the_audit_closes_after_preemption():
    """Five long answers over a full-kind pool too small for them: a
    preemption, every stream finishes, window pages were released behind
    the window, and both ledgers' audits close with zero orphans."""
    import asyncio

    engine = make_engine(model=CFG, attn_backend="gather", num_pages=30,
                         max_batch_size=4, decode_steps=4)
    rng = np.random.RandomState(7)
    reqs = [greedy_request(
        [int(x) for x in rng.randint(1, CFG.vocab_size, (20 + 3 * i,))],
        max_tokens=60) for i in range(5)]
    outs = await asyncio.gather(*(collect(engine, r) for r in reqs))
    assert all(len(t) == 60 and f == "length" for t, f, _ in outs)
    m = engine.metrics()
    assert m["preemptions_total"] >= 1
    assert m["kv_window_pages_released_total"] >= 5 * 4
    for ledger, alloc in ((engine.kv_ledger, engine.allocator),
                          (engine.kv_ledger_win, engine.win_allocator)):
        assert ledger.audit() == [] and ledger.audit() == []
        assert ledger.last_orphans == [] and ledger.violations_total == 0
        assert alloc.pages_used == 0
    # a held window page never lies wholly behind the window
    await engine.close()


async def test_a_prompt_longer_than_the_window_pool_is_served_chunk_by_chunk():
    """One row, a window pool of 9 pages and a prompt of 25: window pages
    are taken a chunk at a time (this chunk's and what its window reaches
    back into), so the
    prompt is admitted and served; held whole it would wait for ever."""
    engine = make_engine(model=CFG, attn_backend="gather", max_batch_size=1,
                         prefill_chunk=32, decode_steps=4, max_model_len=256)
    assert engine.win_num_pages == 1 + 5 + 4
    rng = np.random.RandomState(11)
    prompt = [int(x) for x in rng.randint(1, CFG.vocab_size, (200,))]
    pre = greedy_request(prompt, max_tokens=6)
    pre.sampling_options.logprobs = True
    tokens, finish, frames = await collect(engine, pre)
    assert len(tokens) == 6 and finish == "length"
    served = [lp for f in frames for lp in f.get("log_probs") or []]
    want = _reference().token_logprobs(
        engine.params, _hf(CFG), prompt + tokens, 6, 256)
    np.testing.assert_allclose(np.asarray(served), want, atol=5e-5)
    prefills = [r for r in engine.flight.snapshot() if r["kind"] == "prefill"]
    # a chunk's 4 pages + the 2 its first query's window reaches back
    # into, of 9 usable
    assert len(prefills) == 7
    assert max(r["kv_frac_win"] for r in prefills) <= 6 / 9 + 1e-3
    m = engine.metrics()
    assert m["preemptions_total"] == 0
    assert m["kv_window_pages_used"] == 0 == engine.allocator.pages_used
    assert engine.kv_ledger_win.audit() == []
    await engine.close()


async def test_a_window_pool_that_runs_out_in_prefill_preempts(monkeypatch):
    """Two prompts of four chunks over a window pool of 10 pages: the
    second row's next chunk finds the pool out, is preempted (by the rule
    of a decode row's growth), comes back and is served the same tokens;
    both ledgers close."""
    import asyncio

    from dynamo_tpu.engine.engine import JaxEngine

    rng = np.random.RandomState(13)
    prompts = [[int(x) for x in rng.randint(1, CFG.vocab_size, (100,))]
               for _ in range(2)]
    roomy = make_engine(model=CFG, attn_backend="gather", max_batch_size=2,
                        decode_steps=4, max_model_len=256)
    want = [(await collect(roomy, greedy_request(p, max_tokens=8)))[0]
            for p in prompts]
    await roomy.close()
    monkeypatch.setattr(JaxEngine, "_win_pool_pages", lambda self: 11)
    engine = make_engine(model=CFG, attn_backend="gather", max_batch_size=2,
                         decode_steps=4, max_model_len=256)
    outs = await asyncio.gather(*(
        collect(engine, greedy_request(p, max_tokens=8)) for p in prompts))
    assert [t for t, _, _ in outs] == want
    assert engine.metrics()["preemptions_total"] >= 1
    for ledger, alloc in ((engine.kv_ledger, engine.allocator),
                          (engine.kv_ledger_win, engine.win_allocator)):
        assert ledger.audit() == [] and ledger.violations_total == 0
        assert alloc.pages_used == 0
    await engine.close()


async def test_tails_that_meet_under_load_find_their_group_program_loaded():
    """The group budget is one chunk, as in the benchmark's cell: first
    chunks go one a tick, so two-chunk prompts sent ALONE never end in a
    [2, bucket] group. The first one-row tail loads that sibling on rows of
    padding; three two-chunk prompts sent together then meet as a group
    of two tails and compile no prefill program, and the padding moved no
    token."""
    import asyncio
    import logging

    class Compiles(logging.Handler):
        n = 0

        def emit(self, record):
            self.n += "Compiling jit(_model_step)" in record.getMessage()

    seen, lg = Compiles(), logging.getLogger("jax._src.interpreters.pxla")
    engine = make_engine(model=CFG, attn_backend="gather", decode_steps=4,
                         prefill_group_tokens=32)
    rng = np.random.RandomState(17)
    prompts = [[int(x) for x in rng.randint(1, CFG.vocab_size, (40 + i,))]
               for i in range(3)]
    # the flag and not `jax.log_compiles()`: that one is thread-local, and
    # the dispatch that compiles runs on a worker thread
    lg.addHandler(seen)
    jax.config.update("jax_log_compiles", True)
    try:
        alone, _, _ = await collect(
            engine, greedy_request(prompts[0], max_tokens=6))
        # [1, 32] first chunk, [1, 16] tail over 8 pages, and its [2, 16]
        assert seen.n == 3 and len(engine._tail_groups_loaded) == 1
        n_before = len(engine.flight.snapshot())
        outs = await asyncio.gather(*(
            collect(engine, greedy_request(p, max_tokens=6))
            for p in prompts))
        assert seen.n == 3
    finally:
        jax.config.update("jax_log_compiles", False)
        lg.removeHandler(seen)
    met = [r for r in engine.flight.snapshot()[n_before:]
           if r["kind"] == "prefill" and r["rows"] == 2]
    assert met and all(r["tokens"] <= 2 * 16 for r in met)
    assert outs[0][0] == alone
    assert engine.kv_ledger_win.audit() == [] == engine.kv_ledger.audit()
    await engine.close()


# ------------------------------------------- what two kinds of page refuse

async def test_hybrid_engine_refuses_the_page_moving_planes():
    """Disaggregation (both sides, host-staged and device-path), prefix
    ingest / export, the device-path transfer: each refuses with its
    sentence; the in-engine prefix cache registers nothing."""
    from dynamo_tpu.engine.kv_transfer import device_transfer_kv
    from dynamo_tpu.runtime.pipeline.context import Context

    engine = make_engine(model=CFG)
    pre = greedy_request(list(range(1, 20)), max_tokens=2)
    sentence = "window beside full attention"
    with pytest.raises(ValueError, match=sentence):
        await engine.generate_remote(Context(pre.to_dict()), 1, None, None)
    with pytest.raises(ValueError, match=sentence):
        await engine.prefill_only(pre)
    with pytest.raises(ValueError, match=sentence):
        engine.ingest_prefix(list(range(16)), None, None)
    with pytest.raises(ValueError, match=sentence):
        engine.export_prefix(list(range(16)))
    with pytest.raises(ValueError, match=sentence):
        device_transfer_kv(engine, engine, [1], [2], 8)
    await collect(engine, greedy_request(list(range(1, 40)), max_tokens=2))
    assert engine.allocator.pages_cached == 0
    assert engine.peek_prefix_tokens(list(range(1, 40))) == 0
    assert not engine._mixed_unsupported_reason() is None
    await engine.close()


def test_engine_sizes_the_window_pool_from_rows_window_and_chunk():
    engine = make_engine(model=CFG, max_batch_size=4, decode_steps=4,
                         prefill_chunk=32)
    # per row ceil((16 - 1 + 12) / 8) + 1 = 5; a quarter of the rows in
    # prefill with a chunk's 4 pages; the trash page
    assert engine.win_num_pages == 1 + 4 * 5 + 1 * 4
    assert engine.kv.k[1].shape == (engine.win_num_pages * 8, 2 * 24)
    assert engine.kv.v[1].shape == (engine.win_num_pages * 8, 2 * 16)
    assert engine.kv.k[0].shape == (engine.num_pages * 8, 1 * 24)


@pytest.mark.parametrize("rows,items_a_step", [(1, 1), (4, 1), (5, 2)])
def test_the_digest_counts_a_window_layer_s_work_items(rows, items_a_step):
    """`kv_win_items` is the kernel's own count: ceil(live rows / group)
    a window layer and step, whatever slots the rows sit in; the pages
    streamed are every page slot of every entry of those items."""
    from types import SimpleNamespace

    from dynamo_tpu.ops.pallas_attention import WINDOW_GROUP, window_pages

    engine = make_engine(model=CFG, max_batch_size=8, decode_steps=4,
                         prefill_chunk=32)
    seq = SimpleNamespace(win_page_ids=[0, 0, 7, 8, 9], win_first=2)
    slots = [0, 2, 3, 5, 7][:rows]
    bld = SimpleNamespace(
        rows_i=np.arange(20, 28)[:, None], steps=4,
        active=[(i, seq) for i in slots])
    streamed, items, held = engine._kv_window_pages(bld)
    assert items == items_a_step * 4
    assert streamed == items * WINDOW_GROUP * window_pages(16, 8)
    assert held == rows * 3 * 4
