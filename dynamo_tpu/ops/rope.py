"""Rotary position embeddings, HF rotate-half convention; Llama-3.1 and
YaRN (deepseek_v2) frequency scaling.

HF convention (first-half/second-half pairing) is used so HF safetensors
weights load without permutation. Frequencies are computed in float32 and
the rotation applied in float32 before casting back — bf16 phase error
compounds at long context.
"""

from __future__ import annotations

import math

import jax.numpy as jnp
import numpy as np

from dynamo_tpu.models.config import ModelConfig


def rope_inv_freq(cfg: ModelConfig, theta: float | None = None) -> np.ndarray:
    """Per-pair inverse frequencies [rope width // 2] (the rope width is
    `qk_rope_head_dim` under latent attention, `rotary_dim` where only
    the head's leading dimensions rotate, else `head_dim`), with
    optional llama3 NTK-by-parts scaling (matches HF
    `Llama3RotaryEmbedding`) or YaRN (`yarn_inv_freq`). `theta`: the
    base of one kind of layer where the kinds differ (default
    `rope_theta`)."""
    half = (cfg.qk_rope_head_dim or cfg.rotary_dim or cfg.head_dim) // 2
    theta = cfg.rope_theta if theta is None else theta
    inv = 1.0 / (theta ** (np.arange(0, half, dtype=np.float64) / half))
    sc = cfg.rope_scaling
    if sc and sc.get("type", sc.get("rope_type")) == "yarn":
        return yarn_inv_freq(inv, cfg.rope_theta, sc).astype(np.float32)
    if sc and sc.get("rope_type") in ("llama3",):
        factor = sc["factor"]
        low = sc["low_freq_factor"]
        high = sc["high_freq_factor"]
        orig = sc["original_max_position_embeddings"]
        wavelen = 2 * np.pi / inv
        # three bands: long wavelengths (> orig/low) fully scaled by 1/factor,
        # short (< orig/high) untouched, smooth ramp between — the clip on
        # `smooth` collapses the interpolation to 1/factor in the long band.
        smooth = (orig / wavelen - low) / (high - low)
        smooth = np.clip(smooth, 0.0, 1.0)
        inv = np.where(
            wavelen > orig / high,
            (1 - smooth) * inv / factor + smooth * inv,
            inv,
        )
    return inv.astype(np.float32)


def yarn_inv_freq(inv: np.ndarray, theta: float, sc: dict) -> np.ndarray:
    """YaRN (as `DeepseekV2YarnRotaryEmbedding`): a blend of the plain
    frequencies `inv` and the same / `factor`, by a linear ramp over the
    pair index between the dimensions that turn `beta_fast` and
    `beta_slow` times within the original context. Fast pairs (short
    wavelengths) keep their frequency, slow ones are interpolated."""
    half = inv.shape[0]
    dim, orig = 2 * half, sc["original_max_position_embeddings"]

    def correction_dim(turns):
        return dim * math.log(orig / (turns * 2 * math.pi)) / (
            2 * math.log(theta))

    low = max(math.floor(correction_dim(sc["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(sc["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(half, dtype=np.float64) - low) / (high - low),
                   0.0, 1.0)
    return inv / sc["factor"] * ramp + inv * (1.0 - ramp)


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention-temperature term: 0.1 x mscale x ln(factor) + 1."""
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def softmax_scale(cfg: ModelConfig) -> float:
    """What scores are multiplied by before the softmax: head_dim^-0.5,
    times YaRN's `mscale_all_dim` term squared where the configuration
    has one (192^-0.5 x 1.2608^2 at DeepSeek-V2-Lite's factor 40 x 0.707,
    192^-0.5 x 1.4159^2 at Xing4.0's factor 64 x 1). The rule, whatever
    the family: the factor on cos / sin is yarn_mscale(mscale) /
    yarn_mscale(mscale_all_dim), which is 1 where mscale ==
    mscale_all_dim; only that is served, another pair is refused."""
    scale = cfg.head_dim ** -0.5
    sc = cfg.rope_scaling
    if sc and sc.get("type", sc.get("rope_type")) == "yarn":
        if sc.get("mscale", 1.0) != sc.get("mscale_all_dim", 0.0):
            raise ValueError(
                f"yarn rope_scaling with mscale={sc.get('mscale', 1.0)!r} "
                f"and mscale_all_dim={sc.get('mscale_all_dim', 0.0)!r}: "
                "only mscale == mscale_all_dim (a factor of 1 on cos / "
                "sin) is served"
            )
        scale *= yarn_mscale(sc["factor"], sc["mscale_all_dim"]) ** 2
    return scale


def pairs_to_halves(x: jnp.ndarray) -> jnp.ndarray:
    """[..., d] with rotary pairs as adjacent values (x[2i], x[2i+1]), the
    deepseek_v2 checkpoint layout, to the half-split layout `apply_rope`
    rotates (the published modelling code makes the same permutation).
    Applied to queries and keys alike, so their dot product is the one of
    the adjacent-pair rotation."""
    return jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)


def rope_cos_sin(inv_freq: jnp.ndarray, positions: jnp.ndarray):
    """cos/sin tables for integer positions [...]: returns [..., head_dim]
    (frequencies tiled twice, HF layout)."""
    angles = positions[..., None].astype(jnp.float32) * inv_freq  # [..., half]
    angles = jnp.concatenate([angles, angles], axis=-1)
    return jnp.cos(angles), jnp.sin(angles)


def apply_rope(x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray) -> jnp.ndarray:
    """Rotate `x` [..., H, head_dim] by per-position cos/sin [..., head_dim]
    (broadcast over the head axis).

    Formulated as one trailing concat of the two rotated halves (rather
    than building the full-width `rotate_half` tensor first) so XLA fuses
    the whole rotation into a single pass over x — the full-width
    intermediate materialized f32 copies of every q/k tensor."""
    orig_dtype = x.dtype
    rot = cos.shape[-1]
    if rot < x.shape[-1]:
        # partial rotary: the leading `rot` dimensions rotate (rotate-half
        # over those), the rest pass through
        return jnp.concatenate(
            [apply_rope(x[..., :rot], cos, sin), x[..., rot:]], axis=-1
        )
    half = x.shape[-1] // 2
    x1 = x[..., :half].astype(jnp.float32)
    x2 = x[..., half:].astype(jnp.float32)
    c1 = cos[..., None, :half]
    c2 = cos[..., None, half:]
    s1 = sin[..., None, :half]
    s2 = sin[..., None, half:]
    out = jnp.concatenate([x1 * c1 - x2 * s1, x2 * c2 + x1 * s2], axis=-1)
    return out.astype(orig_dtype)
