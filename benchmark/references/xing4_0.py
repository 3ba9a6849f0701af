"""The plain reference of the Xing4.0 family (`"reference": "xing4_0"` in a
configuration's file): what `correct` compares the served tokens with.

The source is the model's own `config.json` keys (`model_type: xing4_0`:
the `deepseek_v3` keys plus `hc_mult`, `hc_sinkhorn_iters`, `hc_eps`,
`mhc_h_res_clamp_min/max`) and the public paper those keys name,
manifold-constrained hyper-connections (mHC, arXiv 2512.24880); the
published modelling code is not on this machine. In `jax.numpy`, float32
throughout under `jax.default_matmul_precision("highest")`: no cache, no
pages, no kernels, none of the program's model code. With C the hidden
size and n = `hc_mult`, a token's residual is `X in R^{n x C}`:

- in: `h = E[token]`, `X = (h, ..., h)` (ASSUMED: copy-in; the keys do not
  say how the streams start); out: `h_out = sum_i X[i]` (ASSUMED: sum-out),
  the final RMSNorm, the head.
- a boundary around sublayer F (attention or FFN; parameters `w [nC]`,
  `Phi [nC, 2n + n^2]`, `alpha` (pre, post, res), `b_pre [n]`, `b_post
  [n]`, `B_res [n, n]`): `u = RMSNorm(vec(X); w)` (ASSUMED: a learned
  weight, eps `rms_norm_eps`); `p = u Phi` -> `p_pre`, `p_post`, `p_res`;
  `H_pre = sigmoid(alpha_pre p_pre + b_pre)`; `H_post = 2 sigmoid(alpha_post
  p_post + b_post)`; `A = clamp(alpha_res p_res + B_res, clamp_min,
  clamp_max)` (ASSUMED: the clamp is on exp's argument), `M = exp(A)`, then
  `hc_sinkhorn_iters` times `M <- M / (rowsum(M) + hc_eps)`, `M <- M /
  (colsum(M) + hc_eps)` (ASSUMED: rows before columns, eps inside each
  division); `x_in = sum_i H_pre[i] X[i]`; `y = F(RMSNorm(x_in))`, the
  layer's own pre-norm as `deepseek_v3`; `X'[i] = sum_j H_res[i, j] X[j] +
  H_post[i] y`.
- attention: `c_q = RMSNorm(x W_qa)`; `q = c_q W_qb` -> heads of nope +
  rope; `[c_kv ; k_r] = x W_kva`; `c = RMSNorm(c_kv)`; rotary embedding on
  `q_r` and the ONE `k_r` all heads share, adjacent pairs, YaRN
  frequencies; EXPANDED: `[k_n ; v] = W_kvb c` per head, scores `q . k x
  (nope + rope)^-0.5 x mscale^2`, causal softmax, `W_o`. (The program
  serves the absorbed form over the latent pool.)
- FFN: the first `first_k_dense_replace` layers a SwiGLU of
  `intermediate_size`; the others `s = sigmoid(x W_r)`, the
  `num_experts_per_tok` largest of `s + b`, `w = routed_scaling_factor x
  s[chosen] / sum(s[chosen])` (`norm_topk_prob`), ONE EXPERT AT A TIME,
  plus the shared expert(s) on every token.
- the multi-token-prediction layer (`num_nextn_predict_layers`) is not
  part of the next-token forward and is left out, as a server that does not
  draft from it drops it.

It reads the engine's own parameter tree (models/llama.py names: `w_qa`,
`q_norm`, `w_qb`, `w_kva`, `kv_norm`, `w_kvb`, `wo`, `router`,
`router_bias`, `we_*`, `ws_*`, `w_*`, `hc_attn` / `hc_mlp` with `w`, `phi`,
`alpha`, `b_pre`, `b_post`, `b_res`) and runs prompt + served tokens at
once, teacher-forced, a layer at a time.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np


HEAD_BLOCK = 8        # heads whose scores are held at a time
VOCAB_BLOCK = 16384   # columns of the head upcast at a time


def _f32(w):
    return w.astype(jnp.float32)


def _rms_norm(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * _f32(w)


def yarn_mscale(factor: float, mscale: float) -> float:
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def rope_tables(hf: dict, n: int):
    """cos / sin [n, rope/2] (float64 -> float32) and the softmax scale."""
    dim, theta = hf["qk_rope_head_dim"], float(hf.get("rope_theta", 10000.0))
    inv = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    scale = (hf["qk_nope_head_dim"] + dim) ** -0.5
    on_tables = 1.0
    sc = hf.get("rope_scaling")
    if sc:
        if sc.get("type") != "yarn":
            raise NotImplementedError(f"rope_scaling type {sc.get('type')!r}")
        orig, factor = sc["original_max_position_embeddings"], sc["factor"]

        def correction_dim(turns):
            return dim * math.log(orig / (turns * 2 * math.pi)) / (
                2 * math.log(theta))

        low = max(math.floor(correction_dim(sc["beta_fast"])), 0)
        high = min(math.ceil(correction_dim(sc["beta_slow"])), dim - 1)
        if low == high:
            high += 0.001
        ramp = np.clip(
            (np.arange(dim // 2, dtype=np.float64) - low) / (high - low), 0, 1)
        inv = inv / factor * ramp + inv * (1 - ramp)
        all_dim = sc.get("mscale_all_dim", 0)
        if all_dim:
            scale *= yarn_mscale(factor, all_dim) ** 2
        on_tables = yarn_mscale(factor, sc.get("mscale", 1)) / yarn_mscale(
            factor, all_dim)
    ang = np.arange(n, dtype=np.float64)[:, None] * inv[None, :]
    cos, sin = (jnp.asarray(f(ang) * on_tables, jnp.float32)
                for f in (np.cos, np.sin))
    return cos, sin, scale


def _rope_pairs(x, cos, sin):
    """x [T, H, d] rotated over adjacent pairs; cos / sin [T, d/2]."""
    x1, x2 = x[..., 0::2], x[..., 1::2]
    c, s = cos[:, None, :], sin[:, None, :]
    return jnp.stack([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1).reshape(
        x.shape)


def _sinkhorn(m, iters: int, eps: float):
    """m [T, n, n] positive -> doubly stochastic: rows, then columns."""
    for _ in range(iters):
        m = m / (jnp.sum(m, axis=-1, keepdims=True) + eps)
        m = m / (jnp.sum(m, axis=-2, keepdims=True) + eps)
    return m


def _hc_maps(X, hp, *, eps, iters, hc_eps, clamp):
    """X [T, n, C] -> H_pre [T, n], H_post [T, n], H_res [T, n, n]."""
    t, n, c = X.shape
    u = _rms_norm(X.reshape(t, n * c), hp["w"], eps)
    p = u @ _f32(hp["phi"])
    a_pre, a_post, a_res = (hp["alpha"][i] for i in range(3))
    h_pre = jax.nn.sigmoid(a_pre * p[:, :n] + hp["b_pre"])
    h_post = 2.0 * jax.nn.sigmoid(a_post * p[:, n:2 * n] + hp["b_post"])
    a = jnp.clip(a_res * p[:, 2 * n:].reshape(t, n, n) + hp["b_res"],
                 clamp[0], clamp[1])
    return h_pre, h_post, _sinkhorn(jnp.exp(a), iters, hc_eps)


def _hc_pre(X, maps):
    return jnp.einsum("ti,tic->tc", maps[0], X)


def _hc_post(X, maps, y):
    _, h_post, h_res = maps
    return jnp.einsum("tij,tjc->tic", h_res, X) + h_post[..., None] * y[
        :, None, :]


def _query_latent(h, lp, eps):
    return _rms_norm(h @ _f32(lp["w_qa"]), lp["q_norm"], eps)


def _attention(h, lp, cos, sin, *, heads, nope, rope, vd, rank, eps, scale):
    """h [T, C], the normed input -> the attention's output [T, C]."""
    t = h.shape[0]
    q = (_query_latent(h, lp, eps) @ _f32(lp["w_qb"])).reshape(
        t, heads, nope + rope)
    kva = h @ _f32(lp["w_kva"])
    c = _rms_norm(kva[:, :rank], lp["kv_norm"], eps)
    q_r = _rope_pairs(q[..., nope:], cos, sin)
    k_r = _rope_pairs(kva[:, None, rank:], cos, sin)          # [T, 1, rope]
    kv = (c @ _f32(lp["w_kvb"])).reshape(t, heads, nope + vd)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_r, (t, heads, rope))], axis=-1)
    qf = jnp.concatenate([q[..., :nope], q_r], axis=-1)
    causal = jnp.tril(jnp.ones((t, t), bool))

    def some_heads(qkv):
        # HEAD_BLOCK heads at a time: the scores of all 32 at 2,688
        # positions are 0.9 GB, and the reference runs beside the engine
        qb, kb, vb = qkv
        scores = jnp.einsum("thd,shd->hts", qb, kb) * scale
        probs = jax.nn.softmax(
            jnp.where(causal[None], scores, -jnp.inf), axis=-1)
        return jnp.einsum("hts,shv->thv", probs, vb)

    g = max(heads // HEAD_BLOCK, 1)

    def blocks(a):  # [T, H, d] -> [g, T, H / g, d]
        return a.reshape(t, g, heads // g, a.shape[-1]).swapaxes(0, 1)

    out = jax.lax.map(
        some_heads, (blocks(qf), blocks(k), blocks(kv[..., nope:])))
    out = out.swapaxes(0, 1).reshape(t, heads * vd)
    return out @ _f32(lp["wo"])


def _swiglu(h, gate, up, down):
    return (jax.nn.silu(h @ _f32(gate)) * (h @ _f32(up))) @ _f32(down)


def _route(h, lp, *, k, renorm, factor):
    """-> weight [T, E]: a chosen expert's weight, 0 elsewhere."""
    s = jax.nn.sigmoid(h @ _f32(lp["router"]))
    # the bias chooses; the weights are the scores without it
    _, top_i = jax.lax.top_k(s + lp["router_bias"], k)
    top_w = jnp.take_along_axis(s, top_i, axis=-1)
    if renorm:
        top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
    top_w = top_w * factor
    return jnp.sum(
        jnp.where(top_i[..., None] == jnp.arange(s.shape[1]),
                  top_w[..., None], 0.0), axis=1)


def _shared(h, lp):
    return _swiglu(h, lp["ws_gate"], lp["ws_up"], lp["ws_down"])


def _expert_ffn(h, lp, *, k, renorm, factor):
    weight = _route(h, lp, k=k, renorm=renorm, factor=factor)

    def one(acc, e):
        y = _swiglu(h, lp["we_gate"][e], lp["we_up"][e], lp["we_down"][e])
        return acc + y * weight[:, e][:, None], None

    out = jax.lax.scan(one, jnp.zeros_like(h), jnp.arange(weight.shape[1]))[0]
    if "ws_gate" in lp:
        out = out + _shared(h, lp)
    return out


@functools.partial(jax.jit, static_argnames=(
    "heads", "nope", "rope", "vd", "rank", "eps", "scale", "iters", "hc_eps",
    "clamp", "k", "renorm", "factor"))
def _layer(X, lp, cos, sin, *, heads, nope, rope, vd, rank, eps, scale,
           iters, hc_eps, clamp, k, renorm, factor):
    """One layer over the streams X [T, n, C]: a boundary around the
    attention, a boundary around the FFN."""
    hc = dict(eps=eps, iters=iters, hc_eps=hc_eps, clamp=clamp)
    maps = _hc_maps(X, lp["hc_attn"], **hc)
    y = _attention(
        _rms_norm(_hc_pre(X, maps), lp["attn_norm"], eps), lp, cos, sin,
        heads=heads, nope=nope, rope=rope, vd=vd, rank=rank, eps=eps,
        scale=scale)
    X = _hc_post(X, maps, y)
    maps = _hc_maps(X, lp["hc_mlp"], **hc)
    h = _rms_norm(_hc_pre(X, maps), lp["mlp_norm"], eps)
    if "router" in lp:
        y = _expert_ffn(h, lp, k=k, renorm=renorm, factor=factor)
    else:
        y = _swiglu(h, lp["w_gate"], lp["w_up"], lp["w_down"])
    return _hc_post(X, maps, y)


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, final_norm, head, *, eps):
    """The head a block of columns at a time: 131,072 x 3,584 in float32
    is 1.9 GB, more than the engine leaves."""
    h = _rms_norm(x, final_norm, eps)
    d, v = head.shape
    blk = VOCAB_BLOCK if v % VOCAB_BLOCK == 0 else v
    logits = jax.lax.map(
        lambda i: h @ _f32(jax.lax.dynamic_slice(head, (0, i * blk),
                                                 (d, blk))),
        jnp.arange(v // blk))                              # [v / blk, T, blk]
    return jax.nn.log_softmax(
        logits.swapaxes(0, 1).reshape(h.shape[0], v), axis=-1)


def logprob_rows(params: dict, hf: dict, ids: list[int],
                 n_served: int, pad_to: int):
    """log P(. | ids[:p]) over the whole vocabulary, [n_served, V], for
    the last `n_served` positions of `ids` (prompt + served tokens), from
    the full causal forward. `pad_to` pads the sequence (causal, so padding
    at the end changes nothing) so that sequences of different lengths
    share one compiled program."""
    if hf.get("scoring_func") != "sigmoid" or not hf.get("q_lora_rank"):
        raise NotImplementedError(
            "references/xing4_0.py covers low-rank queries and a sigmoid "
            "router with a selection bias (Xing4.0)")
    eps = float(hf.get("rms_norm_eps", 1e-6))
    n = len(ids)
    if pad_to < n:
        raise ValueError(f"pad_to {pad_to} < sequence length {n}")
    tok = jnp.asarray(list(ids) + [0] * (pad_to - n), jnp.int32)
    cos, sin, scale = rope_tables(hf, pad_to)
    streams = hf["hc_mult"]
    with jax.default_matmul_precision("highest"):
        h = params["embed"][tok].astype(jnp.float32)
        X = jnp.broadcast_to(h[:, None, :], (pad_to, streams, h.shape[1]))
        for lp in params["layers"]:
            X = _layer(
                X, lp, cos, sin, heads=hf["num_attention_heads"],
                nope=hf["qk_nope_head_dim"], rope=hf["qk_rope_head_dim"],
                vd=hf["v_head_dim"], rank=hf["kv_lora_rank"], eps=eps,
                scale=scale, iters=hf["hc_sinkhorn_iters"],
                hc_eps=float(hf["hc_eps"]),
                clamp=(float(hf["mhc_h_res_clamp_min"]),
                       float(hf["mhc_h_res_clamp_max"])),
                k=hf["num_experts_per_tok"],
                renorm=bool(hf.get("norm_topk_prob", False)),
                factor=float(hf.get("routed_scaling_factor", 1.0)))
        # row p predicts token p+1: rows n-n_served-1 .. n-2
        rows = jnp.sum(X[n - n_served - 1:n - 1], axis=1)
        return _head(rows, params["final_norm"], params["lm_head"], eps=eps)


def token_logprobs(params: dict, hf: dict, ids: list[int],
                   n_served: int, pad_to: int) -> np.ndarray:
    """log P(ids[p] | ids[:p]) for the last `n_served` positions of `ids`:
    `logprob_rows` at the served tokens."""
    rows = logprob_rows(params, hf, ids, n_served, pad_to)
    served = jnp.asarray(ids[len(ids) - n_served:], jnp.int32)
    out = jnp.take_along_axis(rows, served[:, None], axis=1)[:, 0]
    return np.asarray(out, np.float64)
