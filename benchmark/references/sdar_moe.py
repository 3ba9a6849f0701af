"""The plain reference of the SDAR expert family (`"reference": "sdar_moe"`
in a configuration's file): what `correct` compares the served tokens with.

The layer is the family's published one in `jax.numpy`, float32 throughout
under `jax.default_matmul_precision("highest")`: no cache, no pages, no
kernels, none of the program's model code. With `h` the normed input:

- `q = W_q h`, `k = W_k h`, `v = W_v h`, 32 query heads over 4 KV heads of
  128 (no bias); an RMSNorm over the 128 values of EVERY query and key head
  (`q_norm`, `k_norm`), then the rotary embedding over the two halves of a
  head (rotate-half, base `rope_theta`, no scaling); scores `q . k x
  head_dim^-0.5`; softmax over the positions the mask admits; `W_o`;
- `p = softmax(W_g h)` over the experts, the `num_experts_per_tok` largest,
  renormalised to sum 1 (`norm_topk_prob`); each expert a SwiGLU, applied
  ONE EXPERT AT A TIME (so that the sum fits beside the engine); no shared
  expert, no dense layer;
- final RMSNorm, the head, and a log-softmax from which the mask token's
  logit is EXCLUDED (it is never sampled).

The GENERATION is by diffusion over blocks of B = `block_length` positions
(`hf` carries the keys the served config.json states beside the model's
own: `block_length`, `denoising_steps` T, `remasking_strategy`,
`mask_token_id`). The mask is causal by BLOCK, `k_pos // B <= q_pos // B`;
the logits at a position predict THAT position (no shift). A prompt of L
tokens is encoded for its first `P = floor(L / B) * B` tokens; its last `L
- P` are the given, never-masked head of the first generated block. A block
starts with the mask token wherever it has no token; a denoising pass runs
the whole sequence so far, the block at its end, and fills `B / T` of the
block's masked positions (fewer if fewer are left); a served token's
log-probability is read from the pass that FILLED it. Which positions a
pass fills is the strategy's: `sequential`, the leftmost, is the one that
is replayed from the ids alone, which is all a reference is handed; any
other is refused.

How the replay is computed, and why it is the published procedure: a pass
of block b runs `[final tokens before p0] + [the block as it stood]`. Under
the block-causal mask the rows before `p0` do not depend on the block, and
they are the SAME rows in every pass of every later block: rows of the
final sequence's own forward. So one forward carries the final sequence
AND, appended as extra rows, every pass's block (B rows a pass, at the
block's positions), a pass's rows seeing the final sequence before `p0`
and themselves. Each pass's rows are exactly what a forward over the whole
sequence so far would give them; nothing is cached between layers or
passes, and a layer is computed at a time.

Departures from the published description: none that changes a value. What
the published config.json does not state (the block keys, the norm a head)
is listed in the configuration's file under `assumed`.

It reads the engine's own parameter tree (models/llama.py names: `wq`,
`wk`, `wv`, `q_norm`, `k_norm`, `wo`, `router`, `we_*`).
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


def _rope(x, cos, sin):
    """x [T, H, d] rotated over its two halves; cos / sin [T, d/2]."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    c, s = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def rope_tables(hf: dict, positions: np.ndarray):
    dim, theta = hf["head_dim"], float(hf.get("rope_theta", 10000.0))
    inv = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    ang = positions.astype(np.float64)[:, None] * inv[None, :]
    return (jnp.asarray(np.cos(ang), jnp.float32),
            jnp.asarray(np.sin(ang), jnp.float32))


@functools.partial(jax.jit, static_argnames=(
    "heads", "kv_heads", "hd", "eps", "qk_norm"))
def _attention(x, lp, cos, sin, mask, *, heads, kv_heads, hd, eps,
               qk_norm=True):
    t = x.shape[0]
    h = _rms_norm(x, lp["attn_norm"], eps)
    q = (h @ _f32(lp["wq"])).reshape(t, heads, hd)
    k = (h @ _f32(lp["wk"])).reshape(t, kv_heads, hd)
    v = (h @ _f32(lp["wv"])).reshape(t, kv_heads, hd)
    if qk_norm:
        q = _rms_norm(q, lp["q_norm"], eps)
        k = _rms_norm(k, lp["k_norm"], eps)
    q, k = _rope(q, cos, sin), _rope(k, cos, sin)
    g = heads // kv_heads

    def group(args):
        # the g query heads of one KV head: [g, T, T] scores at a time
        qg, kg, vg = args
        scores = jnp.einsum("tgd,sd->gts", qg, kg) * hd ** -0.5
        probs = jax.nn.softmax(
            jnp.where(mask[None], scores, -jnp.inf), axis=-1)
        return jnp.einsum("gts,sd->tgd", probs, vg)

    out = jax.lax.map(group, (
        q.reshape(t, kv_heads, g, hd).transpose(1, 0, 2, 3),
        k.transpose(1, 0, 2), v.transpose(1, 0, 2)))      # [K, T, g, d]
    out = out.transpose(1, 0, 2, 3).reshape(t, heads * hd)
    return x + out @ _f32(lp["wo"])


def _swiglu(h, gate, up, down):
    return (jax.nn.silu(h @ _f32(gate)) * (h @ _f32(up))) @ _f32(down)


@functools.partial(jax.jit, static_argnames=("eps", "k", "renorm"))
def _expert_ffn(x, lp, *, eps, k, renorm):
    h = _rms_norm(x, lp["mlp_norm"], eps)
    probs = jax.nn.softmax(h @ _f32(lp["router"]), axis=-1)     # [T, E]
    top_w, top_i = jax.lax.top_k(probs, k)
    if renorm:
        top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
    n_experts = probs.shape[1]
    # weight of expert e on token t: its p where e is among t's k, else 0
    weight = jnp.sum(
        jnp.where(top_i[..., None] == jnp.arange(n_experts), top_w[..., None],
                  0.0), axis=1)                                  # [T, E]

    def one(acc, e):
        y = _swiglu(h, lp["we_gate"][e], lp["we_up"][e], lp["we_down"][e])
        return acc + y * weight[:, e][:, None], None

    return x + jax.lax.scan(one, jnp.zeros_like(h), jnp.arange(n_experts))[0]


@functools.partial(jax.jit, static_argnames=("eps", "mask_id"))
def _head(x, final_norm, head, *, eps, mask_id):
    h = _rms_norm(x, final_norm, eps)
    # the head a block of columns at a time: in float32 all of it is 1.2 GB
    v = head.shape[1]
    nb = next(b for b in (16, 8, 4, 2, 1) if v % b == 0)
    logits = jax.lax.map(
        lambda c: h @ _f32(jax.lax.dynamic_slice_in_dim(
            head, c * (v // nb), v // nb, axis=1)),
        jnp.arange(nb))                                   # [nb, T, V / nb]
    logits = logits.transpose(1, 0, 2).reshape(h.shape[0], v)
    logits = jnp.where(jnp.arange(v) == mask_id, -jnp.inf, logits)
    return jax.nn.log_softmax(logits, axis=-1)


def plan_passes(hf: dict, n: int, n_served: int) -> list[tuple]:
    """The denoising passes that filled the last `n_served` of `n` tokens:
    [(block's first position, [position filled when the pass ran] x B,
    [position this pass fills] x B)], by `sequential`: the leftmost
    `B / T` masked positions a pass."""
    b, steps = hf["block_length"], hf["denoising_steps"]
    if hf.get("remasking_strategy") != "sequential":
        raise ValueError("only `sequential` is replayed from the ids alone")
    first = n - n_served                  # the prompt's length
    passes = []
    for p0 in range(first // b * b, n, b):
        given = [p0 + j < first for j in range(b)]
        masked = [j for j in range(b) if not given[j]]
        when = {j: i // (b // steps) for i, j in enumerate(masked)}
        for k in sorted(set(when.values())):
            fills = [when.get(j) == k and p0 + j < n for j in range(b)]
            if any(fills):
                passes.append((
                    p0, [given[j] or when.get(j, k) < k for j in range(b)],
                    fills))
    return passes


def forward_rows(params: dict, hf: dict, ids: list[int], n_served: int,
                 pad_to: int, *, in_block=True, prompt_block=True,
                 commit=True, qk_norm=True, renorm=None):
    """(log-probabilities [rows, V] of every pass's block, the passes):
    row `B * i + j` is position j of pass i's block. The keywords are the
    CONTROLS' (benchmark/controls/sdar_moe.py), each the reference with one
    thing changed: `in_block` False = a causal line inside a generated
    block; `prompt_block` False = the prompt encoded causally; `commit`
    False = the cache keeps what a block's LAST denoising pass wrote (the
    masks of that pass at the positions it filled) for the blocks after it;
    `qk_norm` False = no norm a head; `renorm` overrides
    `norm_topk_prob`."""
    b = hf["block_length"]
    mask_id = hf["mask_token_id"]
    eps = float(hf.get("rms_norm_eps", 1e-6))
    n = len(ids)
    if pad_to < n:
        raise ValueError(f"pad_to {pad_to} < sequence length {n}")
    passes = plan_passes(hf, n, n_served)
    # every sequence of a run shares one compiled program: the passes are
    # padded to the most `n_served` tokens can take (rows that see only
    # themselves, read by nobody)
    most = hf["denoising_steps"] * (n_served // b + 2)
    if len(passes) > most:
        raise ValueError(f"{len(passes)} passes for {n_served} tokens")
    first = n - n_served
    base = np.asarray(list(ids) + [0] * (pad_to - n), np.int64)
    if not commit:
        # what the cache holds of a generated block without its commit
        # pass: its last denoising pass's input
        for p0, filled, _ in passes:
            for j in range(b):
                if p0 + j < n and not filled[j]:
                    base[p0 + j] = mask_id
                elif p0 + j < n:
                    base[p0 + j] = ids[p0 + j]
    tok = np.concatenate([base, np.zeros(b * most, np.int64)])
    pos = np.concatenate([np.arange(pad_to), np.zeros(b * most, np.int64)])
    t = pad_to + b * most
    q_idx = np.arange(pad_to)
    see = np.zeros((t, t), bool)
    blk = q_idx // b
    causal = q_idx[None, :] <= q_idx[:, None]
    see[:pad_to, :pad_to] = blk[None, :] <= blk[:, None]
    if not prompt_block:
        enc = first // b * b
        see[:enc, :pad_to] = causal[:enc]
    if not in_block:
        see[first // b * b:pad_to, :pad_to] = causal[first // b * b:]
    see[np.arange(pad_to, t), np.arange(pad_to, t)] = True
    for i, (p0, filled, _) in enumerate(passes):
        r0 = pad_to + b * i
        for j in range(b):
            tok[r0 + j] = ids[p0 + j] if filled[j] else mask_id
            pos[r0 + j] = p0 + j
        see[r0:r0 + b, :p0] = True
        see[r0:r0 + b, r0:r0 + b] = (
            True if in_block else np.tril(np.ones((b, b), bool)))
    cos, sin = rope_tables(hf, pos)
    mask = jnp.asarray(see)
    with jax.default_matmul_precision("highest"):
        x = params["embed"][jnp.asarray(tok, jnp.int32)].astype(jnp.float32)
        for lp in params["layers"]:
            x = _attention(
                x, lp, cos, sin, mask, heads=hf["num_attention_heads"],
                kv_heads=hf["num_key_value_heads"], hd=hf["head_dim"],
                eps=eps, qk_norm=qk_norm)
            x = _expert_ffn(
                x, lp, eps=eps, k=hf["num_experts_per_tok"],
                renorm=bool(hf.get("norm_topk_prob", False))
                if renorm is None else renorm)
        rows = _head(x[pad_to:], params["final_norm"], params["lm_head"],
                     eps=eps, mask_id=mask_id)
    return rows, passes


def logprob_rows(params: dict, hf: dict, ids: list[int], n_served: int,
                 pad_to: int, **control) -> np.ndarray:
    """log P(. at position p) over the vocabulary, [n_served, V], for the
    last `n_served` positions of `ids`, each from the denoising pass that
    FILLED it (at that position: no shift)."""
    rows, passes = forward_rows(params, hf, ids, n_served, pad_to, **control)
    rows = np.asarray(rows)
    b, first = hf["block_length"], len(ids) - n_served
    at = np.full(n_served, -1)
    for i, (p0, _, fills) in enumerate(passes):
        for j in np.flatnonzero(fills):
            at[p0 + j - first] = b * i + j
    if (at < 0).any():
        raise ValueError("a served position that no pass filled")
    return rows[at]


def token_logprobs(params: dict, hf: dict, ids: list[int], n_served: int,
                   pad_to: int, **control) -> np.ndarray:
    """The log-probability of each of the last `n_served` ids, each read
    at its own position from the denoising pass that filled it."""
    rows = logprob_rows(params, hf, ids, n_served, pad_to, **control)
    served = np.asarray(ids[len(ids) - n_served:])
    return rows[np.arange(n_served), served].astype(np.float64)
