"""Ring flash attention over the sp axis vs a single-device causal oracle
(8 virtual CPU devices; the long-context context-parallel path)."""

from __future__ import annotations

import jax
import jax.numpy as jnp

import numpy as np

from dynamo_tpu.ops.ring_attention import ring_attention_sharded, ring_self_attention
from dynamo_tpu.parallel import mesh as meshmod


def causal_oracle(q, k, v):
    b, t, h, hd = q.shape
    kh = k.shape[2]
    g = h // kh
    qg = q.reshape(b, t, kh, g, hd).astype(np.float32)
    s = np.einsum("btkgd,bskd->bkgts", qg, k.astype(np.float32)) / np.sqrt(hd)
    mask = np.tril(np.ones((t, t), bool))
    s = np.where(mask[None, None, None], s, -1e30)
    p = np.exp(s - s.max(-1, keepdims=True))
    p = p / p.sum(-1, keepdims=True)
    out = np.einsum("bkgts,bskd->btkgd", p, v.astype(np.float32))
    return out.reshape(b, t, h, hd)


def _run(sp, tp, dp, b, t, h, kh, hd, seed=0):
    devices = jax.devices()[: sp * tp * dp]
    mesh = meshmod.build_mesh(meshmod.MeshConfig(sp=sp, tp=tp, dp=dp), devices)
    rng = np.random.RandomState(seed)
    q = rng.randn(b, t, h, hd).astype(np.float32)
    k = rng.randn(b, t, kh, hd).astype(np.float32)
    v = rng.randn(b, t, kh, hd).astype(np.float32)
    out = ring_attention_sharded(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), mesh
    )
    ref = causal_oracle(q, k, v)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-4, atol=2e-4)


def test_ring_sp8():
    _run(sp=8, tp=1, dp=1, b=1, t=64, h=4, kh=4, hd=16)


def test_ring_sp4_with_gqa():
    _run(sp=4, tp=1, dp=2, b=2, t=32, h=8, kh=2, hd=16)


def test_ring_composes_with_tp():
    # heads over tp, sequence over sp, batch over dp — all at once
    _run(sp=2, tp=2, dp=2, b=2, t=32, h=4, kh=2, hd=16)


def test_ring_single_shard_degenerates():
    # sp=1: the ring is one local flash step
    _run(sp=1, tp=1, dp=1, b=1, t=48, h=4, kh=4, hd=16)


def test_ring_matches_inside_jit_with_long_t():
    _run(sp=8, tp=1, dp=1, b=1, t=256, h=4, kh=2, hd=32)


def test_model_forward_ring_matches_gather():
    """llama.forward with AttnSpec.ring on an sp=2 mesh must reproduce the
    single-device gather path bit-for-bit in f32 (whole-prompt prefill)."""
    from dynamo_tpu.models import llama
    from dynamo_tpu.models.config import get_config

    cfg = get_config("tiny").with_(dtype="float32")
    rng = np.random.RandomState(0)
    b, t, page = 2, 32, 8
    params = llama.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    tokens = rng.randint(1, cfg.vocab_size, (b, t)).astype(np.int32)
    positions = np.tile(np.arange(t, dtype=np.int32), (b, 1))
    wslots = np.concatenate(
        [np.arange(page * (1 + 8 * i), page * (1 + 8 * i) + t) for i in range(b)]
    ).astype(np.int32)
    smat = np.stack(
        [np.arange(page * (1 + 8 * i), page * (1 + 8 * i) + t) for i in range(b)]
    ).astype(np.int32)

    kv = llama.init_kv_cache(cfg, 512, dtype=jnp.float32)
    ref_hidden, ref_kv = llama.forward(
        params, cfg, jnp.asarray(tokens), jnp.asarray(positions), kv,
        jnp.asarray(wslots), jnp.asarray(smat),
    )

    mesh = meshmod.build_mesh(
        meshmod.MeshConfig(sp=2, dp=2), jax.devices()[:4]
    )
    kv2 = llama.init_kv_cache(cfg, 512, dtype=jnp.float32)
    spec = llama.AttnSpec.ring(jnp.asarray(smat), mesh, page_size=page)
    with jax.set_mesh(mesh):
        hidden, kv2 = jax.jit(llama.forward, static_argnums=(1,))(
            params, cfg, jnp.asarray(tokens), jnp.asarray(positions), kv2,
            jnp.asarray(wslots), spec,
        )
    np.testing.assert_allclose(
        np.asarray(hidden), np.asarray(ref_hidden), rtol=1e-5, atol=1e-5
    )
    np.testing.assert_allclose(
        np.asarray(kv2.k[0]), np.asarray(ref_kv.k[0]), rtol=1e-6, atol=1e-6
    )
