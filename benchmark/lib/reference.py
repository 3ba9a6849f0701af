"""The plain reference that decides `correct`.

A straightforward Llama-family forward in `jax.numpy`: RMSNorm, rotary
embedding (half-split, HF layout), grouped-query causal attention, SwiGLU;
float32 throughout under `jax.default_matmul_precision("highest")`; no KV
cache, no pages, no kernels, none of the program's model code. It reads the
engine's own parameter tree (int8 leaves {"q", "s"} are dequantized to
float32 — weights are compared as served; the W8A8 rounding of
ACTIVATIONS and the int8 rounding of cached K/V are the served side's
departures, and the configuration's tolerance covers them) and runs the
whole prompt + served tokens at once, teacher-forced: the log-probability
of each served token is compared position by position, so a flipped
near-tie cannot derail the comparison.

Memory: one layer at a time, and within a layer the wide matrices are
dequantized a column (or row) block at a time, so beside a 7B engine's
weights and KV pool the reference needs a few hundred MB.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np


def _f32(leaf, cols=None, rows=None):
    """A weight leaf (plain array or {"q": int8 [in,out], "s": f32 [out]})
    as float32, optionally only a block of its columns or rows
    (start, size)."""
    q, s = (leaf["q"], leaf["s"]) if isinstance(leaf, dict) else (leaf, None)
    if cols is not None:
        q = jax.lax.dynamic_slice_in_dim(q, cols[0], cols[1], axis=1)
        if s is not None:
            s = jax.lax.dynamic_slice_in_dim(s, cols[0], cols[1], axis=0)
    if rows is not None:
        q = jax.lax.dynamic_slice_in_dim(q, rows[0], rows[1], axis=0)
    w = q.astype(jnp.float32)
    return w if s is None else w * s.astype(jnp.float32)[None, :]


def _out_dim(leaf) -> int:
    return (leaf["q"] if isinstance(leaf, dict) else leaf).shape[1]


def _blocks(n: int) -> int:
    """How many blocks a dimension of n is cut into: at most 8, dividing
    n (14336 -> 8 x 1792)."""
    return next(b for b in (8, 4, 2, 1) if n % b == 0)


def _mm_cols(x, leaf):
    """x [T, in] @ leaf [in, out], one block of output columns at a time."""
    out = _out_dim(leaf)
    nb = _blocks(out)
    size = out // nb

    def one(j):
        return x @ _f32(leaf, cols=(j * size, size))

    y = jax.lax.map(one, jnp.arange(nb))          # [nb, T, size]
    return jnp.moveaxis(y, 0, 1).reshape(x.shape[0], out)


def _mm_rows(x, leaf):
    """x [T, in] @ leaf [in, out], one block of input rows at a time."""
    n_in = x.shape[1]
    nb = _blocks(n_in)
    size = n_in // nb

    def one(acc, j):
        xs = jax.lax.dynamic_slice_in_dim(x, j * size, size, axis=1)
        return acc + xs @ _f32(leaf, rows=(j * size, size)), None

    acc0 = jnp.zeros((x.shape[0], _out_dim(leaf)), jnp.float32)
    return jax.lax.scan(one, acc0, jnp.arange(nb))[0]


def _rms_norm(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32)


def _rope(x, cos, sin):
    """x [T, H, hd]; cos/sin [T, hd/2]. Half-split rotation."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    c, s = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


@functools.partial(jax.jit, static_argnames=("heads", "kv_heads", "hd", "eps"))
def _layer(x, lp, cos, sin, *, heads, kv_heads, hd, eps):
    """One pre-norm transformer layer over x [T, D], causal."""
    t = x.shape[0]
    h = _rms_norm(x, lp["attn_norm"], eps)
    q = (h @ _f32(lp["wq"])).reshape(t, heads, hd)
    k = (h @ _f32(lp["wk"])).reshape(t, kv_heads, hd)
    v = (h @ _f32(lp["wv"])).reshape(t, kv_heads, hd)
    q, k = _rope(q, cos, sin), _rope(k, cos, sin)
    g = heads // kv_heads            # query head k*g + j reads kv head k
    q = q.reshape(t, kv_heads, g, hd)
    scores = jnp.einsum("tkgd,skd->kgts", q, k) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((t, t), bool))
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    attn = jnp.einsum("kgts,skd->tkgd", probs, v).reshape(t, heads * hd)
    x = x + attn @ _f32(lp["wo"])
    h = _rms_norm(x, lp["mlp_norm"], eps)
    gate = jax.nn.silu(_mm_cols(h, lp["w_gate"]))
    up = _mm_cols(h, lp["w_up"])
    return x + _mm_rows(gate * up, lp["w_down"])


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, final_norm, head, *, eps):
    """log-softmax over the vocabulary for the rows of x [R, D]."""
    logits = _mm_cols(_rms_norm(x, final_norm, eps), head)
    return jax.nn.log_softmax(logits, axis=-1)


def token_logprobs(params: dict, hf: dict, ids: list[int],
                   n_served: int, pad_to: int) -> np.ndarray:
    """log P(ids[p] | ids[:p]) for the last `n_served` positions of `ids`
    (prompt + served tokens), from the full causal forward. `pad_to`
    pads the sequence (causal, so padding at the end changes nothing) so
    that sequences of different lengths share one compiled program."""
    heads = hf["num_attention_heads"]
    kv_heads = hf.get("num_key_value_heads", heads)
    hd = hf.get("head_dim") or hf["hidden_size"] // heads
    eps = float(hf.get("rms_norm_eps", 1e-5))
    n = len(ids)
    if hf.get("rope_scaling") or "bq" in params["layers"][0]:
        raise NotImplementedError(
            "the reference covers plain rotary embedding without "
            "attention bias; extend it with the configuration that needs more")
    if pad_to < n:
        raise ValueError(f"pad_to {pad_to} < sequence length {n}")
    tok = jnp.asarray(list(ids) + [0] * (pad_to - n), jnp.int32)
    inv = 1.0 / (float(hf.get("rope_theta", 10000.0))
                 ** (np.arange(0, hd // 2, dtype=np.float64) / (hd // 2)))
    ang = np.arange(pad_to, dtype=np.float64)[:, None] * inv[None, :]
    cos, sin = (jnp.asarray(f(ang), jnp.float32) for f in (np.cos, np.sin))
    with jax.default_matmul_precision("highest"):
        x = params["embed"][tok].astype(jnp.float32)
        for lp in params["layers"]:
            x = _layer(x, lp, cos, sin, heads=heads, kv_heads=kv_heads,
                       hd=hd, eps=eps)
        head = params.get("lm_head")
        if head is None:
            head = params["embed"].T
        # row p predicts token p+1: rows n-n_served-1 .. n-2
        rows = x[n - n_served - 1:n - 1]
        lp_all = _head(rows, params["final_norm"], head, eps=eps)
        served = jnp.asarray(ids[n - n_served:], jnp.int32)
        out = jnp.take_along_axis(lp_all, served[:, None], axis=1)[:, 0]
    return np.asarray(out, np.float64)


def compare(served: list[list[float]], reference: list[np.ndarray],
            tolerance: dict) -> dict:
    """`correct`: mean and worst |served - reference| log-probability over
    every judged position, against the configuration's tolerance."""
    gaps = np.concatenate([
        np.abs(np.asarray(s, np.float64) - r)
        for s, r in zip(served, reference)
    ])
    mean, worst = float(gaps.mean()), float(gaps.max())
    return {
        "positions": int(gaps.size), "gap_mean": mean, "gap_max": worst,
        "ok": bool(np.isfinite(gaps).all()
                   and mean <= tolerance["gap_mean"]
                   and worst <= tolerance["gap_max"]),
    }
