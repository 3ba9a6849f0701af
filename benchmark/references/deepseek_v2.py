"""The plain reference of the DeepSeek-V2 family (`"reference":
"deepseek_v2"` in a configuration's file): what `correct` compares the
served tokens with.

The published forward (`modeling_deepseek.py` beside the source
config.json) in `jax.numpy`, float32 throughout under
`jax.default_matmul_precision("highest")`: no cache, no pages, no kernels,
none of the program's model code. Per layer, with `h` the normed input:

- queries `q = W_q h` (no low-rank query path: `q_lora_rank` null), 16 x
  (nope + rope); latent `[c ; k_r] = W_kva h`; `c <- RMSNorm(c)`; rotary
  embedding on `q_r` and on the ONE `k_r` all heads share, over adjacent
  pairs `(x[2i], x[2i+1])` with YaRN frequencies;
- EXPANDED attention: `[k_n ; v] = W_kvb c` per head, `k = [k_n ; k_r]`,
  scores `q . k x s` with `s = (nope + rope)^-0.5 x mscale^2`, causal
  softmax, heads' `v` concatenated, `W_o`. (The program serves the
  absorbed form; the two check each other.)
- the first `first_k_dense_replace` layers: SwiGLU of `intermediate_size`;
  the others: `p = softmax(W_g h)` over the routed experts, the
  `num_experts_per_tok` largest (greedy, one group), weights `p` as they
  are or renormalised (`norm_topk_prob`), times `routed_scaling_factor`;
  each expert applied to the tokens routed to it, ONE EXPERT AT A TIME
  (an expert is 35 MB in float32, so the sum fits beside the engine);
  plus the shared experts, one SwiGLU of `n_shared_experts` expert widths,
  on every token.

Departures from the published code, none of which changes a value:
rotation is applied to the adjacent pairs in place (the published code
permutes pairs to halves first, queries and keys alike, which leaves
every dot product the same); the factor on cos / sin, `mscale /
mscale_all_dim`, is applied as published (1 for the published values).

It reads the engine's own parameter tree (models/llama.py names: `wq`,
`w_kva`, `kv_norm`, `w_kvb`, `wo`, `router`, `we_*`, `ws_*`, `w_*`) and
runs prompt + served tokens at once, teacher-forced.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np


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


@functools.partial(jax.jit, static_argnames=(
    "heads", "nope", "rope", "vd", "rank", "eps", "scale"))
def _attention(x, lp, cos, sin, *, heads, nope, rope, vd, rank, eps, scale):
    t = x.shape[0]
    h = _rms_norm(x, lp["attn_norm"], eps)
    q = (h @ _f32(lp["wq"])).reshape(t, heads, nope + rope)
    kva = h @ _f32(lp["w_kva"])
    c = _rms_norm(kva[:, :rank], lp["kv_norm"], eps)
    q_r = _rope_pairs(q[..., nope:], cos, sin)
    k_r = _rope_pairs(kva[:, None, rank:], cos, sin)          # [T, 1, rope]
    kv = (c @ _f32(lp["w_kvb"])).reshape(t, heads, nope + vd)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_r, (t, heads, rope))], axis=-1)
    qf = jnp.concatenate([q[..., :nope], q_r], axis=-1)
    scores = jnp.einsum("thd,shd->hts", qf, k) * scale
    causal = jnp.tril(jnp.ones((t, t), bool))
    probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), axis=-1)
    out = jnp.einsum("hts,shv->thv", probs, kv[..., nope:])
    return x + out.reshape(t, heads * vd) @ _f32(lp["wo"])


def _swiglu(h, gate, up, down):
    return (jax.nn.silu(h @ _f32(gate)) * (h @ _f32(up))) @ _f32(down)


@functools.partial(jax.jit, static_argnames=("eps",))
def _dense_ffn(x, lp, *, eps):
    h = _rms_norm(x, lp["mlp_norm"], eps)
    return x + _swiglu(h, lp["w_gate"], lp["w_up"], lp["w_down"])


@functools.partial(jax.jit, static_argnames=("eps", "k", "renorm", "factor"))
def _expert_ffn(x, lp, *, eps, k, renorm, factor):
    h = _rms_norm(x, lp["mlp_norm"], eps)
    probs = jax.nn.softmax(h @ _f32(lp["router"]), axis=-1)     # [T, E]
    top_w, top_i = jax.lax.top_k(probs, k)
    if renorm:
        top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
    top_w = top_w * factor
    n_experts = probs.shape[1]
    # weight of expert e on token t: its p where e is among t's k, else 0
    weight = jnp.sum(
        jnp.where(top_i[..., None] == jnp.arange(n_experts), top_w[..., None],
                  0.0), axis=1)                                  # [T, E]

    def one(acc, e):
        y = _swiglu(h, lp["we_gate"][e], lp["we_up"][e], lp["we_down"][e])
        return acc + y * weight[:, e][:, None], None

    out = jax.lax.scan(one, jnp.zeros_like(h), jnp.arange(n_experts))[0]
    if "ws_gate" in lp:
        out = out + _swiglu(h, lp["ws_gate"], lp["ws_up"], lp["ws_down"])
    return x + out


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, final_norm, head, *, eps):
    return jax.nn.log_softmax(_rms_norm(x, final_norm, eps) @ _f32(head),
                              axis=-1)


def logprob_rows(params: dict, hf: dict, ids: list[int],
                 n_served: int, pad_to: int):
    """log P(. | ids[:p]) over the whole vocabulary, [n_served, V], for
    the last `n_served` positions of `ids` (prompt + served tokens), from
    the full causal forward. `pad_to` pads the sequence (causal, so padding
    at the end changes nothing) so that sequences of different lengths
    share one compiled program."""
    if hf.get("q_lora_rank") is not None or hf.get("scoring_func",
                                                   "softmax") != "softmax":
        raise NotImplementedError(
            "references/deepseek_v2.py covers full-rank queries and softmax "
            "scoring (DeepSeek-V2-Lite)")
    eps = float(hf.get("rms_norm_eps", 1e-6))
    n = len(ids)
    if pad_to < n:
        raise ValueError(f"pad_to {pad_to} < sequence length {n}")
    tok = jnp.asarray(list(ids) + [0] * (pad_to - n), jnp.int32)
    cos, sin, scale = rope_tables(hf, pad_to)
    with jax.default_matmul_precision("highest"):
        x = params["embed"][tok].astype(jnp.float32)
        for lp in params["layers"]:
            x = _attention(
                x, lp, cos, sin, heads=hf["num_attention_heads"],
                nope=hf["qk_nope_head_dim"], rope=hf["qk_rope_head_dim"],
                vd=hf["v_head_dim"], rank=hf["kv_lora_rank"], eps=eps,
                scale=scale)
            if "router" in lp:
                x = _expert_ffn(
                    x, lp, eps=eps, k=hf["num_experts_per_tok"],
                    renorm=bool(hf.get("norm_topk_prob", False)),
                    factor=float(hf.get("routed_scaling_factor", 1.0)))
            else:
                x = _dense_ffn(x, lp, eps=eps)
        # row p predicts token p+1: rows n-n_served-1 .. n-2
        rows = x[n - n_served - 1:n - 1]
        return _head(rows, params["final_norm"], params["lm_head"], eps=eps)


def token_logprobs(params: dict, hf: dict, ids: list[int],
                   n_served: int, pad_to: int) -> np.ndarray:
    """log P(ids[p] | ids[:p]) for the last `n_served` positions of `ids`:
    `logprob_rows` at the served tokens."""
    rows = logprob_rows(params, hf, ids, n_served, pad_to)
    served = jnp.asarray(ids[len(ids) - n_served:], jnp.int32)
    out = jnp.take_along_axis(rows, served[:, None], axis=1)[:, 0]
    return np.asarray(out, np.float64)
