"""Manifold-constrained hyper-connections (mHC, arXiv 2512.24880): the
residual path of a model that carries `hc_mult` = n streams a token
(`xing4_0`). Beside `llama.py` as `mamba2.py` stands there: `layer_step`
calls `maps` / `pre` / `post` around each sublayer where `cfg.hc_mult > 1`
and nowhere else.

A token's residual is `X in R^{n x C}`, carried as ONE row of n x C
values, stream i in columns [i C, (i + 1) C): `X [B, T, n C]` in the
model's dtype (a last axis of whole lane tiles, like every other
activation; an axis of 4 would be padded to a tile of sublanes). A
boundary around sublayer `F` has its own parameters (`init_mhc_params`)
and is, all in float32:

- `maps` (scope `mhc.maps`): `u = RMSNorm(vec(X); w)`, `p = u Phi`, split
  into `p_pre [n]`, `p_post [n]`, `p_res [n, n]`;
  `H_pre = sigmoid(alpha_pre p_pre + b_pre)`,
  `H_post = 2 sigmoid(alpha_post p_post + b_post)`,
  `M = exp(clamp(alpha_res p_res + B_res, -c, c))`, then
  `hc_sinkhorn_iters` times `M <- M / (rowsum(M) + hc_eps)`,
  `M <- M / (colsum(M) + hc_eps)`; `H_res = M`, doubly stochastic.
- `pre` (scope `mhc.pre`): `x_in = sum_i H_pre[i] X[i]`, the sublayer's
  input before its own pre-norm.
- `post` (scope `mhc.post`): `X'[i] = sum_j H_res[i, j] X[j] + H_post[i] y`.

The maps of a token are 2n + n^2 = 24 numbers. They are worked with the
ROWS in the minor axis, each entry of the n x n matrix a vector of its own
(`_sinkhorn`): a row sum is then three elementwise adds and an
iteration is elementwise from end to end, which XLA fuses; as an
`[R, n, n]` array every sum is a reduction over a padded tile and every
iteration two more launches. The function is jitted on its own so that a
step program traces and lowers it once for its 2 x layers boundaries
(as `llama._mamba_layer`). The bytes a boundary must move are X read and
X written (benchmark/lib/shapes_xing.py); the maps' matmul is `[R, n C] x
[n C, 24]`, nothing beside them.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

# The seeded parameters (a checkpoint's come from training; the paper
# starts alpha at 0.01, where every map is its bias and `Phi` moves
# nothing). With `u` of unit RMS and Phi ~ N(0, 1 / nC) each `p` is
# ~N(0, 1): alpha 1 makes every map input-dependent by about as much as
# its bias spreads it; B_res = RES_DIAG on the diagonal keeps a stream
# mostly its own (H_res's diagonal ~0.6-0.8) while the rest is mixed. The
# benchmark's controls show that each piece is then seen by `correct`
# (benchmark/controls/xing4_0.py; change a value here and read them again).
ALPHA = (1.0, 1.0, 1.0)     # pre, post, res
BIAS_STD = 0.5
RES_DIAG = 2.0


def init_mhc_params(cfg, key, dtype=jnp.bfloat16) -> dict:
    """One boundary's parameters: `w [nC]` and `phi [nC, 2n + n^2]` in the
    model's dtype, the scalars and biases in float32."""
    n, width = cfg.hc_mult, cfg.hc_mult * cfg.hidden_size
    k_phi, k_pre, k_post, k_res = jax.random.split(key, 4)
    normal = jax.random.normal
    return {
        "w": jnp.ones((width,), dtype),
        "phi": (normal(k_phi, (width, 2 * n + n * n), jnp.float32)
                * width ** -0.5).astype(dtype),
        "alpha": jnp.asarray(ALPHA, jnp.float32),
        "b_pre": BIAS_STD * normal(k_pre, (n,), jnp.float32),
        "b_post": BIAS_STD * normal(k_post, (n,), jnp.float32),
        "b_res": RES_DIAG * jnp.eye(n, dtype=jnp.float32)
        + BIAS_STD * normal(k_res, (n, n), jnp.float32),
    }


def _sinkhorn(m: list, iters: int, eps: float) -> list:
    """`m`: n x n vectors [R], positive. Rows, then columns, `iters`
    times; elementwise throughout. A device-side loop of `iters` trips
    over the n^2 vectors: unrolled, the ~1,700 operations of a boundary
    compile on the CPU backend in ~18 s a boundary (every test and
    rehearsal pays it), and the TPU compiler makes one fusion an
    iteration of either form."""
    n = len(m)

    def sweep(_, flat):
        m = [list(flat[i * n:(i + 1) * n]) for i in range(n)]
        for i in range(n):
            s = functools.reduce(jnp.add, m[i]) + eps
            m[i] = [v / s for v in m[i]]
        for j in range(n):
            s = functools.reduce(jnp.add, [m[i][j] for i in range(n)]) + eps
            for i in range(n):
                m[i][j] = m[i][j] / s
        return tuple(v for row in m for v in row)

    flat = jax.lax.fori_loop(
        0, iters, sweep, tuple(v for row in m for v in row))
    return [list(flat[i * n:(i + 1) * n]) for i in range(n)]


@functools.partial(jax.jit, static_argnames=(
    "n", "iters", "eps", "clamp", "norm_eps"))
def _maps(hp: dict, x, *, n, iters, eps, clamp, norm_eps):
    # RMSNorm(vec(X); w) Phi with the row's factor applied to the 24
    # products, not to the nC values: the matmul then reads X itself (an
    # elementwise operand XLA fuses into it) and no float32 copy of the
    # streams, twice their bytes, is written and read back
    v = x.astype(jnp.float32)
    r = jax.lax.rsqrt(jnp.mean(v * v, axis=-1, keepdims=True) + norm_eps)
    p = (jnp.dot(
        v * hp["w"].astype(jnp.float32), hp["phi"].astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST,
    ) * r).T                                             # [2n + n^2, R]
    a_pre, a_post, a_res = hp["alpha"]
    h_pre = jax.nn.sigmoid(a_pre * p[:n] + hp["b_pre"][:, None])
    h_post = 2.0 * jax.nn.sigmoid(
        a_post * p[n:2 * n] + hp["b_post"][:, None])
    logits = jnp.clip(
        a_res * p[2 * n:] + hp["b_res"].reshape(n * n, 1), -clamp, clamp)
    m = _sinkhorn(
        [[jnp.exp(logits[i * n + j]) for j in range(n)] for i in range(n)],
        iters, eps,
    )
    h_res = jnp.stack([jnp.stack(row) for row in m])     # [n, n, R]
    return h_pre, h_post, h_res


def maps(hp: dict, cfg, x: jnp.ndarray):
    """x [B, T, n C] -> (H_pre [n, R], H_post [n, R], H_res [n, n, R])
    float32, R = B x T rows in the minor axis."""
    with jax.named_scope("mhc.maps"):
        return _maps(
            hp, x.reshape(-1, x.shape[-1]), n=cfg.hc_mult,
            iters=cfg.hc_sinkhorn_iters, eps=cfg.hc_eps,
            clamp=cfg.hc_res_clamp, norm_eps=cfg.rms_norm_eps,
        )


def _streams(x: jnp.ndarray, n: int) -> list:
    d = x.shape[-1] // n
    return [x[..., i * d:(i + 1) * d].astype(jnp.float32) for i in range(n)]


@jax.named_scope("mhc.pre")
def pre(h_maps, x: jnp.ndarray) -> jnp.ndarray:
    """The sublayer's input [B, T, C]: the streams mixed by `H_pre`."""
    h_pre = h_maps[0]
    n, (b, t, _) = h_pre.shape[0], x.shape
    xs = _streams(x, n)
    out = functools.reduce(jnp.add, [
        h_pre[i].reshape(b, t, 1) * xs[i] for i in range(n)])
    return out.astype(x.dtype)


@jax.named_scope("mhc.post")
def post(h_maps, x: jnp.ndarray, y: jnp.ndarray) -> jnp.ndarray:
    """The streams after the sublayer, [B, T, n C]: mixed among
    themselves by `H_res`, the sublayer's output `y` [B, T, C] added to
    each by `H_post`."""
    _, h_post, h_res = h_maps
    n, (b, t, _) = h_post.shape[0], x.shape
    xs, yf = _streams(x, n), y.astype(jnp.float32)
    out = [
        functools.reduce(jnp.add, [
            h_res[i, j].reshape(b, t, 1) * xs[j] for j in range(n)])
        + h_post[i].reshape(b, t, 1) * yf
        for i in range(n)
    ]
    return jnp.concatenate(out, axis=-1).astype(x.dtype)


def expand(h: jnp.ndarray, n: int) -> jnp.ndarray:
    """Copy-in: every stream starts as the embedding."""
    return jnp.concatenate([h] * n, axis=-1)


def collapse(x: jnp.ndarray, n: int) -> jnp.ndarray:
    """Sum-out: the hidden state the final norm reads."""
    return functools.reduce(jnp.add, _streams(x, n)).astype(x.dtype)
