"""The plain reference of the MiMo-V2 family (`"reference":
"mimo_v2_flash"` in a configuration's file): what `correct` compares the
served tokens with.

The layer equations of the published `config.json` (`model_type`
`mimo_v2_flash`) in `jax.numpy`, float32 throughout under
`jax.default_matmul_precision("highest")`: no cache, no pages, no kernels,
none of the program's model code. Layer `l` is of kind
`hybrid_layer_pattern[l]` (0 full attention, 1 window attention); with `h`
the normed input (`RMSNorm`, eps `layernorm_epsilon`):

- attention, both kinds: `q = W_q h` as `num_attention_heads` x `head_dim`
  (192); `k = W_k h` as K heads x 192; `v = W_v h` as K heads x
  `v_head_dim` (128), `v <- attention_value_scale x v`; K is
  `num_key_value_heads` (full) or `swa_num_key_value_heads` (window); query
  head i reads KV head `i // (heads / K)`. Rotary embedding on dimensions
  [0, `int(head_dim x partial_rotary_factor)`) of every q and k head,
  rotate-half over those, base `rope_theta` (full) or `swa_rope_theta`
  (window), no scaling; the other dimensions pass through. Scores `q . k /
  sqrt(head_dim)` under an explicit [T, T] mask: `j <= i` (full), `i -
  sliding_window < j <= i` (window). A window layer's softmax runs over
  `[scores ; sink_head]`, the learned sink logit of the head as one more
  column, and the column is then dropped: a row of weights sums to less
  than 1. `x <- x + W_o [o_1 ; ... ; o_heads]`.
- the layers whose `moe_layer_freq` entry is 0: SwiGLU of
  `intermediate_size`. The others: `s = sigmoid(W_g h)` over ALL
  `router_width` experts; the `num_experts_per_tok` with the largest `s +
  c` (`c` the router's correction bias, `noaux_tc`; one group); weights
  `s` WITHOUT `c` on those, divided by their sum (`norm_topk_prob`); each
  expert a SwiGLU of `moe_intermediate_size`, applied ONE EXPERT AT A TIME
  (an expert is 100 MB in float32). No shared expert.
- final RMSNorm, untied head, log-softmax.

THE SAME SHARE AS THE PROGRAM. The configuration's file stands for one chip
of an expert-parallel group: it is handed `n_routed_experts` = the experts
HELD (ids `expert_offset` .. + held of the `router_width` the router
scores) and the sliced vocabulary. The absent experts' part of a layer's
sum is left out, here as there: the group's exchange would add it, and on
one chip nothing stands in for it.

Not served, so not here: the three multi-token-prediction layers (they are
not in `config`); `n_group` / `topk_group` other than 1; a sink in full
layers; a `routed_scaling_factor`.

Assumed, because the published modelling file is not on this machine (each
is listed under `assumed` in the configuration's file; none changes a
shape, a byte or an operation count): (1) inside the 64 rotated dimensions
the pairing is rotate-half (`x[i]` with `x[i + 32]`), HF's convention; (2)
`attention_value_scale` multiplies `v` right after its projection (so the
cache holds scaled values); (3) the correction bias and the sinks are
parameters like any other: the program seeds them N(0, 0.02) / N(0, 1) so
that both are judged, where HF initialises them to zero.

Attention runs ONE KV HEAD at a time (a `lax.map`): 64 heads of [T, T]
scores at 2,616 tokens are 1.7 GB, which does not fit beside the engine.

It reads the engine's own parameter tree (models/llama.py names: `wq`,
`wk`, `wv`, `wo`, `sink`, `router`, `router_bias`, `we_*`, `w_*`) and runs
prompt + served tokens at once, teacher-forced. The controls
(`benchmark/controls/mimo_v2_flash.py`) run this file with ONE thing
changed, through the keyword arguments of `logprob_rows`.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def _f32(w):
    return w.astype(jnp.float32)


def _rms_norm(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * _f32(w)


def rope_tables(theta: float, rot: int, n: int):
    """cos / sin [n, rot / 2] (float64 -> float32) at base `theta`."""
    inv = 1.0 / float(theta) ** (np.arange(0, rot, 2, dtype=np.float64) / rot)
    ang = np.arange(n, dtype=np.float64)[:, None] * inv[None, :]
    return (jnp.asarray(f(ang), jnp.float32) for f in (np.cos, np.sin))


def _rope_halves(x, cos, sin, rot: int):
    """x [T, H, d]: dimensions [0, rot) rotated (x[i] pairs with
    x[i + rot/2]), the rest as they are; cos / sin [T, rot / 2]."""
    x1, x2 = x[..., :rot // 2], x[..., rot // 2:rot]
    c, s = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate(
        [x1 * c - x2 * s, x2 * c + x1 * s, x[..., rot:]], axis=-1)


@functools.partial(jax.jit, static_argnames=(
    "heads", "kv_heads", "kd", "vd", "rot", "eps", "window", "value_scale",
    "use_sink"))
def _attention(x, lp, cos, sin, *, heads, kv_heads, kd, vd, rot, eps, window,
               value_scale, use_sink):
    t = x.shape[0]
    g = heads // kv_heads
    h = _rms_norm(x, lp["attn_norm"], eps)
    q = (h @ _f32(lp["wq"])).reshape(t, heads, kd)
    k = (h @ _f32(lp["wk"])).reshape(t, kv_heads, kd)
    v = (h @ _f32(lp["wv"])).reshape(t, kv_heads, vd) * value_scale
    q = _rope_halves(q, cos, sin, rot).reshape(t, kv_heads, g, kd)
    k = _rope_halves(k, cos, sin, rot)
    i, j = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
    mask = j <= i                                              # [T, T]
    if window:
        mask = mask & (j > i - window)
    sink = (_f32(lp["sink"]) if use_sink
            else jnp.zeros((heads,), jnp.float32)).reshape(kv_heads, g)

    def one_kv_head(args):
        q_k, k_k, v_k, sink_k = args          # [T, G, kd], [T, kd], [T, vd]
        scores = jnp.einsum("tgd,sd->gts", q_k, k_k) * kd ** -0.5
        scores = jnp.where(mask[None], scores, -jnp.inf)
        if use_sink:
            # the sink as a concatenated column, dropped after the softmax
            col = jnp.broadcast_to(sink_k[:, None, None], (g, t, 1))
            probs = jax.nn.softmax(
                jnp.concatenate([scores, col], axis=-1), axis=-1)[..., :t]
        else:
            probs = jax.nn.softmax(scores, axis=-1)
        return jnp.einsum("gts,sv->tgv", probs, v_k)           # [T, G, vd]

    out = jax.lax.map(one_kv_head, (
        q.transpose(1, 0, 2, 3), k.transpose(1, 0, 2), v.transpose(1, 0, 2),
        sink))                                                 # [K, T, G, vd]
    out = out.transpose(1, 0, 2, 3).reshape(t, heads * vd)
    return x + out @ _f32(lp["wo"])


def _swiglu(h, gate, up, down):
    return (jax.nn.silu(h @ _f32(gate)) * (h @ _f32(up))) @ _f32(down)


@functools.partial(jax.jit, static_argnames=("eps",))
def _dense_ffn(x, lp, *, eps):
    h = _rms_norm(x, lp["mlp_norm"], eps)
    return x + _swiglu(h, lp["w_gate"], lp["w_up"], lp["w_down"])


@functools.partial(jax.jit, static_argnames=(
    "eps", "k", "renorm", "offset", "use_bias"))
def _expert_ffn(x, lp, *, eps, k, renorm, offset, use_bias):
    h = _rms_norm(x, lp["mlp_norm"], eps)
    scores = jax.nn.sigmoid(h @ _f32(lp["router"]))     # [T, router_width]
    chosen = scores + lp["router_bias"] if use_bias else scores
    _, top_i = jax.lax.top_k(chosen, k)
    top_w = jnp.take_along_axis(scores, top_i, axis=-1)
    if renorm:
        top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
    width, held = scores.shape[1], lp["we_gate"].shape[0]
    # weight of expert e on token t: its score where e is among t's k
    weight = jnp.sum(
        jnp.where(top_i[..., None] == jnp.arange(width), top_w[..., None],
                  0.0), axis=1)                                  # [T, width]

    def one(acc, e):
        # held expert e is expert offset + e of the router's `width`; the
        # experts other chips hold are nobody's here
        y = _swiglu(h, lp["we_gate"][e], lp["we_up"][e], lp["we_down"][e])
        return acc + y * weight[:, offset + e][:, None], None

    out = jax.lax.scan(one, jnp.zeros_like(h), jnp.arange(held))[0]
    return x + out


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, final_norm, head, *, eps):
    return jax.nn.log_softmax(_rms_norm(x, final_norm, eps) @ _f32(head),
                              axis=-1)


def logprob_rows(params: dict, hf: dict, ids: list[int],
                 n_served: int, pad_to: int, *, window: bool = True,
                 sink: bool = True, value_scale: bool = True,
                 selection_bias: bool = True):
    """log P(. | ids[:p]) over the vocabulary as run, [n_served, V], for
    the last `n_served` positions of `ids` (prompt + served tokens), from
    the full causal forward. `pad_to` pads the sequence (causal, so padding
    at the end changes nothing) so that sequences of different lengths
    share one compiled program. The keyword arguments switch ONE mechanism
    off each: they are the controls' (`benchmark/controls/`), never the
    harness's."""
    for key, want in (("n_group", (None, 1)), ("topk_group", (None, 1)),
                      ("routed_scaling_factor", (None, 1, 1.0)),
                      ("n_shared_experts", (None, 0)),
                      ("add_full_attention_sink_bias", (None, False)),
                      ("attention_bias", (None, False))):
        if hf.get(key) not in want:
            raise NotImplementedError(
                f"references/mimo_v2_flash.py: {key}={hf.get(key)!r}")
    eps = float(hf.get("layernorm_epsilon", 1e-5))
    heads, kd = hf["num_attention_heads"], hf["head_dim"]
    rot = int(kd * hf.get("partial_rotary_factor", 1.0))
    n = len(ids)
    if pad_to < n:
        raise ValueError(f"pad_to {pad_to} < sequence length {n}")
    tok = jnp.asarray(list(ids) + [0] * (pad_to - n), jnp.int32)
    tables = {
        0: tuple(rope_tables(hf["rope_theta"], rot, pad_to)),
        1: tuple(rope_tables(hf["swa_rope_theta"], rot, pad_to)),
    }
    scale = float(hf.get("attention_value_scale") or 1.0) if value_scale else 1.0
    with jax.default_matmul_precision("highest"):
        x = params["embed"][tok].astype(jnp.float32)
        for l, lp in enumerate(params["layers"]):
            kind = hf["hybrid_layer_pattern"][l]
            cos, sin = tables[kind]
            x = _attention(
                x, lp, cos, sin, heads=heads,
                kv_heads=hf["swa_num_key_value_heads" if kind
                            else "num_key_value_heads"],
                kd=hf["swa_head_dim"] if kind else kd,
                vd=hf["swa_v_head_dim" if kind else "v_head_dim"],
                rot=rot, eps=eps,
                window=hf["sliding_window"] if kind and window else 0,
                value_scale=scale,
                use_sink=bool(kind and sink
                              and hf.get("add_swa_attention_sink_bias")))
            if hf["moe_layer_freq"][l]:
                x = _expert_ffn(
                    x, lp, eps=eps, k=hf["num_experts_per_tok"],
                    renorm=bool(hf.get("norm_topk_prob", False)),
                    offset=int(hf.get("expert_offset", 0)),
                    use_bias=selection_bias)
            else:
                x = _dense_ffn(x, lp, eps=eps)
        # row p predicts token p+1: rows n-n_served-1 .. n-2
        rows = x[n - n_served - 1:n - 1]
        return _head(rows, params["final_norm"], params["lm_head"], eps=eps)


def token_logprobs(params: dict, hf: dict, ids: list[int],
                   n_served: int, pad_to: int, **switches) -> np.ndarray:
    """log P(ids[p] | ids[:p]) for the last `n_served` positions of `ids`:
    `logprob_rows` at the served tokens."""
    rows = logprob_rows(params, hf, ids, n_served, pad_to, **switches)
    served = jnp.asarray(ids[len(ids) - n_served:], jnp.int32)
    out = jnp.take_along_axis(rows, served[:, None], axis=1)[:, 0]
    return np.asarray(out, np.float64)
