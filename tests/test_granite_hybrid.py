"""The Granite 4.0-H family (`granitemoehybrid`): Mamba-2 layers, which
keep a fixed-size state a sequence in pools indexed by the decode slot,
beside attention layers with no position encoding over paged KV. The
configuration against the published one; the mixer's three forms against
one another and the one-pass kernel against the plain update; and every
other model's `forward` against the program the parent tree traced. The
served path against `benchmark/references/granite_hybrid.py` is
`tests/test_granite_hybrid_engine.py`'s."""

from __future__ import annotations

import hashlib
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.models import llama, mamba2
from dynamo_tpu.models.config import FULL, MAMBA, PRESETS, ModelConfig

from .test_engine import make_engine

CFG = PRESETS["tiny-granite"]
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _reference():
    spec = importlib.util.spec_from_file_location("ref_granite", os.path.join(
        ROOT, "benchmark", "references", "granite_hybrid.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _hf(cfg: ModelConfig) -> dict:
    """The `config.json` a checkpoint of `cfg` would carry."""
    return {
        "model_type": "granitemoehybrid", "vocab_size": cfg.vocab_size,
        "hidden_size": cfg.hidden_size, "intermediate_size": 1,
        "shared_intermediate_size": cfg.intermediate_size,
        "num_hidden_layers": cfg.num_layers,
        "num_attention_heads": cfg.num_heads,
        "num_key_value_heads": cfg.num_kv_heads,
        "rope_theta": cfg.rope_theta, "rms_norm_eps": cfg.rms_norm_eps,
        "max_position_embeddings": cfg.max_position_embeddings,
        "tie_word_embeddings": True, "attention_bias": False,
        "layer_types": ["mamba" if k == MAMBA else "attention"
                        for k in cfg.layer_kinds],
        "position_embedding_type": "nope", "rope_scaling": None,
        "num_local_experts": 0, "num_experts_per_tok": 0,
        "hidden_act": "silu", "normalization_function": "rmsnorm",
        "attention_multiplier": cfg.attn_scale,
        "embedding_multiplier": cfg.embedding_multiplier,
        "residual_multiplier": cfg.residual_multiplier,
        "logits_scaling": cfg.logits_scaling,
        "mamba_n_heads": cfg.mamba_heads, "mamba_d_head": cfg.mamba_head_dim,
        "mamba_d_state": cfg.mamba_d_state,
        "mamba_n_groups": cfg.mamba_groups, "mamba_d_conv": cfg.mamba_d_conv,
        "mamba_chunk_size": cfg.mamba_chunk, "mamba_expand": 2,
        "mamba_conv_bias": True, "mamba_proj_bias": False,
    }


# ------------------------------------------------------------ configuration


def _catalog_row() -> dict:
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(path):
        pytest.skip("the model-configs catalog is not on this machine")
    with open(path) as f:
        for line in f:
            row = json.loads(line)
            if row["name"] == "granite-4.0-h-micro":
                return row["config"]
    pytest.skip("the catalog has no granite-4.0-h-micro row")


def test_published_config_is_the_preset():
    got = ModelConfig.from_hf_config(
        _catalog_row(), name="granite-4.0-h-micro")
    assert got == PRESETS["granite-4.0-h-micro"]
    assert got.layers_of(MAMBA) == 36 and got.layers_of(FULL) == 4
    assert got.paged_layers == (5, 15, 25, 35)
    assert got.recurrent and not got.hybrid and not got.use_rope
    assert got.mamba_inner == 4096 and got.mamba_conv_width == 4352
    assert mamba2.state_bytes_per_slot(got, 2) == 36 * 1_074_688
    assert ModelConfig.from_hf_config(_hf(CFG), name="tiny-granite") == CFG


def test_benchmark_file_is_the_preset_with_nothing_reduced():
    with open(os.path.join(
            ROOT, "benchmark", "configs", "granite-4.0-h-micro.json")) as f:
        config = json.load(f)
    cb = config.pop("benchmark")
    assert cb["reduced"] == {}
    assert ModelConfig.from_hf_config(
        config, name=cb["preset"]) == PRESETS["granite-4.0-h-micro"]
    for key, value in _catalog_row().items():   # every key as published
        assert config[key] == value, key


@pytest.mark.parametrize("key,value", [
    ("num_local_experts", 8), ("mamba_n_groups", 2), ("mamba_d_head", 24),
    ("position_embedding_type", "rope"),
    ("rope_scaling", {"rope_type": "yarn", "factor": 4}),
    ("layer_types", ["mamba", "attention"]),
    ("layer_types", ["mamba", "mamba", "sliding_attention", "mamba"]),
    ("mamba_proj_bias", True), ("attention_bias", True),
    ("mamba_expand", 4), ("hidden_act", "gelu"),
    ("normalization_function", "layernorm"),
])
def test_unserved_granite_keys_are_refused_by_name(key, value):
    with pytest.raises(ValueError, match=key):
        ModelConfig.from_hf_config({**_hf(CFG), key: value})


def test_checkpoint_loading_is_refused_by_name(tmp_path):
    from dynamo_tpu.models.weights import load_params

    with pytest.raises(ValueError, match="Mamba-2 layers beside attention"):
        load_params(str(tmp_path), CFG)


# ------------------------------------------------- the mixer's three forms


def _step(h, x, b, c, dt, a):
    """One token from a state, the recurrence as written: h [B, G, R, P, N]
    f32; x [B, G, R, P]; b, c [B, G, N]; dt [B, G, R] f32; a [G, R]."""
    decay = jnp.exp(dt * a)[..., None, None]
    dtx = (dt[..., None] * x)[..., None]
    h = decay * h + dtx * b[:, :, None, None, :]
    return (h * c[:, :, None, None, :]).sum(-1), h


def _mixer_inputs(t, seed=0, bsz=2):
    g, r = CFG.mamba_groups, CFG.mamba_heads // CFG.mamba_groups
    p, n = CFG.mamba_head_dim, CFG.mamba_d_state
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    return dict(
        h=jax.random.normal(ks[0], (bsz, g, r, p, n)),
        x=jax.random.normal(ks[1], (bsz, t, g, r, p)),
        b=jax.random.normal(ks[2], (bsz, t, g, n)),
        c=jax.random.normal(ks[3], (bsz, t, g, n)),
        dt=jax.nn.softplus(jax.random.normal(ks[4], (bsz, t, g, r)) - 2.0),
        a=-jnp.exp(jax.random.normal(ks[5], (g, r))),
    )


@pytest.mark.parametrize("t,size", [(8, 8), (24, 8), (21, 8), (5, 16)])
def test_the_chunked_form_is_the_recurrence(t, size):
    """`_chunks` (intra-chunk products, a state carried across chunks)
    against the recurrence token by token (`_step`): outputs and the state left behind; a
    length that is no multiple of the chunk pads with dt = 0."""
    v = _mixer_inputs(t)
    y, h = mamba2._chunks(v["h"], v["x"], v["b"], v["c"], v["dt"], v["a"], size)
    hs, ys = v["h"], []
    for i in range(t):
        yi, hs = _step(hs, v["x"][:, i], v["b"][:, i], v["c"][:, i],
                              v["dt"][:, i], v["a"])
        ys.append(yi)
    np.testing.assert_allclose(y, jnp.stack(ys, 1), rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(h, hs, rtol=2e-4, atol=2e-4)


def _mixer(u, ssm, conv, slots, real, fresh, seed=5):
    lp = mamba2.init_mamba_params(CFG, jax.random.PRNGKey(seed), jnp.float32)
    return mamba2.mamba_mixer(lp, CFG, u, ssm, conv, slots, real, fresh)


def _pools(rows=5, fill=0.5):
    ssm, conv = mamba2.init_state_pools(CFG.with_(
        layer_kinds=(MAMBA,), num_layers=1), rows, jnp.float32)
    return ssm[0] + fill, conv[0] - fill


def test_padding_positions_advance_nothing():
    """A row of 11 real tokens in a bucket of 16: outputs, state and the
    convolution's tail equal the 11 tokens alone (the tail is the last
    three REAL tokens, not the bucket's last three); a row of padding
    leaves its slot as it was; a fresh row ignores what its slot held."""
    u = jax.random.normal(jax.random.PRNGKey(1), (2, 16, CFG.hidden_size))
    real = jnp.arange(16)[None] < jnp.asarray([[11], [0]])
    ssm, conv = _pools()
    slots = jnp.asarray([3, 4], jnp.int32)
    fresh = jnp.asarray([True, False])
    out, s2, c2 = _mixer(u, ssm, conv, slots, real, fresh)
    alone, s1, c1 = _mixer(
        u[:1, :11], *_pools(fill=0.0), jnp.asarray([3], jnp.int32),
        jnp.ones((1, 11), bool), jnp.asarray([True]))
    np.testing.assert_allclose(out[0, :11], alone[0], rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(s2[3], s1[3], rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(c2[3], c1[3], rtol=1e-6, atol=1e-6)
    for before, after in ((ssm, s2), (conv, c2)):
        np.testing.assert_array_equal(after[4], before[4])   # padding row
        np.testing.assert_array_equal(after[:3], before[:3])  # other slots


@pytest.mark.parametrize("heads,p,n,rows", [
    (64, 64, 128, 5), (16, 8, 16, 3), (8, 32, 16, 2), (8, 32, 16, 32)])
def test_the_one_pass_kernel_is_the_plain_update(heads, p, n, rows):
    """`ops/pallas_ssm.py`: the kernel (interpreted here) against
    `reference_update`, the plain span from `w_in`'s output to `w_out`'s
    input (the gated, normed row, the state and the tail), and that
    span's state against the recurrence as written (`_step` on the unpacked
    state), at the published widths and at two small ones, a program
    narrower than a group of rows and one of two groups; a row that does
    not advance, a row that starts a sequence; rows past the program's
    width come back untouched."""
    from dynamo_tpu.ops import pallas_ssm as ps

    f, k = ps.pack_factor(p), 4
    q, lanes, inner = heads // f, f * p, heads * p
    cw = inner + 2 * n
    ks = jax.random.split(jax.random.PRNGKey(heads), 9)
    bf = jnp.bfloat16
    pool = jax.random.normal(ks[0], (rows + 2, q, n, lanes)).astype(bf)
    tails = jax.random.normal(ks[1], (rows + 2, k - 1, cw)).astype(bf)
    zxbcdt = jax.random.normal(ks[2], (rows, 1, inner + cw + heads)).astype(bf)
    w = ps.StepWeights(
        conv_w=(jax.random.normal(ks[3], (k, cw)) / 2).astype(bf),
        conv_b=(jax.random.normal(ks[4], (cw,)) / 2).astype(bf),
        dt_bias=jax.random.normal(ks[5], (heads,)) - 2.0,
        a_log=jax.random.normal(ks[6], (heads,)),
        d=jax.random.normal(ks[7], (heads,)),
        norm=jax.random.normal(ks[8], (inner,)).astype(bf))
    real = jnp.arange(rows) != 1
    fresh = jnp.arange(rows) == 0
    y_ref, pool_ref, tails_ref = ps.reference_update(
        zxbcdt, pool, tails, real, fresh, w, eps=1e-5)

    # the recurrence as written, from the same convolved x, B, C and dt
    seq = jnp.concatenate([jnp.where(
        fresh[:, None, None], 0, tails[:rows]), zxbcdt[:, :, inner:-heads]], 1)
    xbc = jax.nn.silu(
        (seq.astype(jnp.float32) * w.conv_w.astype(jnp.float32)).sum(1)
        + w.conv_b.astype(jnp.float32)).astype(bf).astype(jnp.float32)
    dt = jnp.where(real[:, None], jax.nn.softplus(
        zxbcdt[:, 0, -heads:].astype(jnp.float32) + w.dt_bias), 0.0)
    h0 = jnp.where(fresh[:, None, None, None], 0, ps.unpack(pool[:rows], f))
    _, h_rec = _step(
        h0.astype(jnp.float32)[:, None], xbc[:, None, :inner].reshape(
            rows, 1, heads, p), xbc[:, None, inner:inner + n],
        xbc[:, None, inner + n:], dt[:, None], -jnp.exp(w.a_log)[None])

    def same_rounding(got, want):
        # one rounding on the write: equal but for ties that a fused
        # multiply-add in one of the two programs rounds the other way
        got, want = (np.asarray(v, np.float32) for v in (got, want))
        np.testing.assert_allclose(got, want, rtol=2 ** -7, atol=1e-6)
        assert (got != want).mean() < 1e-4

    same_rounding(pool_ref[:rows], ps.pack(h_rec[:, 0].astype(bf), f))
    np.testing.assert_array_equal(pool_ref[1], pool[1])    # not advanced
    np.testing.assert_array_equal(tails_ref[1], tails[1])
    np.testing.assert_array_equal(tails_ref[0, :-1], 0)    # fresh
    np.testing.assert_array_equal(tails_ref[0, -1], zxbcdt[0, 0, inner:-heads])
    if rows > 2:
        np.testing.assert_array_equal(tails_ref[2, :-1], tails[2, 1:])
    y_k, pool_k, tails_k = ps.kernel_update(
        zxbcdt, pool, tails, real, fresh, w, eps=1e-5, interpret=True)
    assert y_k.shape == y_ref.shape == (rows, 1, inner) and y_k.dtype == bf
    np.testing.assert_allclose(np.asarray(y_k, np.float32), np.asarray(
        y_ref, np.float32), rtol=2 ** -7, atol=1e-5)
    same_rounding(pool_k[:rows], pool_ref[:rows])
    np.testing.assert_array_equal(tails_k[:rows], tails_ref[:rows])
    for before, after in ((pool, pool_k), (tails, tails_k),
                          (pool, pool_ref), (tails, tails_ref)):
        np.testing.assert_array_equal(after[rows:], before[rows:])
    np.testing.assert_array_equal(
        ps.unpack(ps.pack(h_rec[:, 0], f), f), h_rec[:, 0])


def test_a_decode_row_that_is_not_advanced_keeps_its_state():
    """`slots` None: row b IS slot b; `real` False (the fused path's
    write_pos == -1) leaves state and tail to the bit; a `fresh` row
    starts from zero whatever its slot holds."""
    u = jax.random.normal(jax.random.PRNGKey(2), (3, 1, CFG.hidden_size))
    ssm, conv = _pools()
    real = jnp.asarray([[True], [False], [True]])
    _, s2, c2 = _mixer(u, ssm, conv, None, real, jnp.zeros(3, bool))
    np.testing.assert_array_equal(s2[1], ssm[1])
    np.testing.assert_array_equal(c2[1], conv[1])
    np.testing.assert_array_equal(s2[3:], ssm[3:])
    assert float(jnp.abs(s2[0] - ssm[0]).max()) > 0
    # a fresh row starts from zero whatever its slot holds
    of, sf, cf = _mixer(u, ssm, conv, None, real, jnp.asarray(
        [False, False, True]))
    oz, sz, cz = _mixer(u, *_pools(fill=0.0), None, real, jnp.zeros(3, bool))
    for got, want in ((of, oz), (sf, sz), (cf, cz)):
        np.testing.assert_array_equal(got[2], want[2])
    np.testing.assert_array_equal(sf[:2], s2[:2])
    np.testing.assert_array_equal(cf[:2], c2[:2])
    # one token at a time = the chunk form over the same tokens
    seq = jax.random.normal(jax.random.PRNGKey(3), (1, 6, CFG.hidden_size))
    one = jnp.ones((1, 1), bool)
    sa, ca = _pools(rows=2, fill=0.0)
    outs = []
    for i in range(6):
        o, sa, ca = _mixer(seq[:, i:i + 1], sa, ca, None, one,
                           jnp.asarray([i == 0]))
        outs.append(o)
    ob, sb, cb = _mixer(seq, *_pools(rows=2), jnp.asarray([0], jnp.int32),
                        jnp.ones((1, 6), bool), jnp.asarray([True]))
    np.testing.assert_allclose(jnp.concatenate(outs, 1), ob, rtol=1e-4,
                               atol=1e-5)
    np.testing.assert_allclose(sa[0], sb[0], rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(ca[0], cb[0], rtol=1e-6, atol=1e-6)


# ---------- a model with no recurrent layer runs the parent's programs

# sha256 (first 16 hex) of str(jax.make_jaxpr(llama.forward)) on abstract
# arguments, TAKEN ON THE PARENT TREE (d5ea1b7) before this family's first
# edit: the decode form (one token a row) and the chunk form (16 tokens a
# row), gather mode and the pallas kernels (interpret), at a tiny dense, a
# tiny latent and a tiny window preset. One entry has been taken again
# since: ("tiny-mla", "decode", "pallas") in PR 45, whose change IS that
# program's kernel body (`ops/pallas_mla.py`: the new row merged into a
# slab under `pl.when`); it was 06410da5a0802baa, and the eleven others
# stood
PARENT_FORWARD_JAXPR = {
    ("tiny", "decode", "gather"): "a183c8e04d6ca209",
    ("tiny", "decode", "pallas"): "fb6efa213a07da62",
    ("tiny", "chunk", "gather"): "55f2503f12daa49f",
    ("tiny", "chunk", "pallas"): "75049c75ebc9813c",
    ("tiny-mla", "decode", "gather"): "4e0392301e10574e",
    ("tiny-mla", "decode", "pallas"): "22be4e011f3ea677",
    ("tiny-mla", "chunk", "gather"): "343a7a82176d5e73",
    ("tiny-mla", "chunk", "pallas"): "16756901cac5ad48",
    ("tiny-mimo", "decode", "gather"): "209c1b5aeb2cb3ed",
    ("tiny-mimo", "decode", "pallas"): "b1bf95c1f9dc5e75",
    ("tiny-mimo", "chunk", "gather"): "7530a70ce67d4c25",
    ("tiny-mimo", "chunk", "pallas"): "a5563be0d6678b15",
}


def _forward_jaxpr(name, form, backend):
    cfg = PRESETS[name]
    ps, w, b = 8, 4, 2
    t = 1 if form == "decode" else 16
    params = jax.eval_shape(lambda: llama.init_params(
        cfg, jax.random.PRNGKey(0), dtype=jnp.float32))
    kv = jax.eval_shape(lambda: llama.init_kv_cache(
        cfg, 64, dtype=jnp.float32, page_size=ps, win_slots=32,
        state_slots=3))

    def i32(*s):
        return jax.ShapeDtypeStruct(s, jnp.int32)

    def spec(tables, smat, lengths, pos0, wpos, wtables):
        if backend == "gather":
            return llama.AttnSpec.gather(smat, page_size=ps)
        if form == "decode":
            return llama.AttnSpec.pallas_decode(
                tables, lengths, ps, write_pos=wpos, interpret=True)
        return llama.AttnSpec.gather(
            smat, write_tables=wtables, page_size=ps, interpret=True,
            block_tables=tables, q_pos0=pos0, lengths=lengths)

    def fn(params, kv, tokens, positions, wslots, tables, smat, lengths,
           wpos, wtables, win):
        attn = spec(tables, smat, lengths, positions[:, 0], wpos, wtables)
        if cfg.hybrid:
            wa = spec(win[0], win[1], lengths, positions[:, 0], wpos, win[2])
            wa.write_slots = win[3]
            attn.win = wa
        return llama.forward(params, cfg, tokens, positions, kv, wslots, attn)

    n_wt = b * (t // ps or 1)
    win = (i32(b, w), i32(b, w * ps), i32(n_wt), i32(b * t))
    return kv, jax.make_jaxpr(fn)(
        params, kv, i32(b, t), i32(b, t), i32(b * t), i32(b, w),
        i32(b, w * ps), i32(b), i32(b), i32(n_wt), win)


@pytest.mark.parametrize("name,form,backend", sorted(PARENT_FORWARD_JAXPR))
def test_forward_traces_to_the_parent_s_program(name, form, backend):
    kv, jaxpr = _forward_jaxpr(name, form, backend)
    text = str(jaxpr)
    # the state is ABSENT for such a model, not a zero-size array
    assert kv.ssm is None and kv.conv is None
    assert "ssm" not in text  # no attn.ssm_* scope either
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == (
        PARENT_FORWARD_JAXPR[(name, form, backend)])


def test_the_recurrent_preset_keeps_a_pool_a_layer_of_each_kind():
    kv, _ = _forward_jaxpr("tiny-granite", "decode", "gather")
    assert len(kv.ssm) == 3 and kv.ssm[0].shape[0] == 3
    assert len(jax.tree.leaves(kv)) == 2 * 1 + 2 * 3


@pytest.mark.parametrize("form,backend", [
    ("decode", "gather"), ("decode", "pallas"), ("chunk", "pallas")])
def test_a_step_program_traces_one_mamba_layer_and_calls_it(form, backend):
    """The MAMBA layers of a step program are calls of ONE traced function
    (`llama._mamba_layer`): a program of 36 such layers traces and lowers
    one, which is what a server's set-up pays for ~35 times."""
    _, jaxpr = _forward_jaxpr("tiny-granite", form, backend)
    calls = [e for e in jaxpr.eqns if e.params.get("name") == "_mamba_layer"]
    assert len(calls) == CFG.layers_of(MAMBA) == 3
    assert len({id(e.params["jaxpr"]) for e in calls}) == 1
    # and nothing of a mixer outside them
    outside = [e for e in jaxpr.eqns if e not in calls]
    assert "ssm" not in "".join(str(e) for e in outside)


def test_a_dense_engine_keeps_no_state_and_takes_no_state_branch():
    """The step programs of a model with no recurrent layer are given no
    state argument, and the engine's flags that gate every state path are
    off: its digests carry zeros in the state columns."""
    engine = make_engine()
    assert not engine._recurrent and not engine._no_prefix_cache
    assert engine.kv.ssm is None and engine.kv.conv is None
    assert len(jax.tree.leaves(engine.kv)) == 2 * engine.model_cfg.num_layers
    m = engine.metrics()
    assert m["state_slots_used"] == 0 == m["state_resets_total"]
