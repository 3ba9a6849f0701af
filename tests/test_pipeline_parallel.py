"""Pipeline-parallel stage execution (GPipe microbatching over pp) vs the
single-device forward — stage-local weights and KV pools, activations
rotated with ppermute."""

from __future__ import annotations

import jax
import jax.numpy as jnp

import numpy as np
import pytest

from dynamo_tpu.models import llama
from dynamo_tpu.models.config import get_config
from dynamo_tpu.parallel import mesh as meshmod
from dynamo_tpu.parallel.pipeline import (
    pp_forward,
    pp_sharded_put,
    stack_layer_params,
)

CFG = get_config("tiny").with_(dtype="float32", num_layers=4)


def _inputs(b, t, page=8):
    rng = np.random.RandomState(0)
    tokens = rng.randint(1, CFG.vocab_size, (b, t)).astype(np.int32)
    positions = np.tile(np.arange(t, dtype=np.int32), (b, 1))
    wslots = np.stack(
        [np.arange(page * (1 + 8 * i), page * (1 + 8 * i) + t) for i in range(b)]
    ).astype(np.int32)
    smat = wslots.copy()
    return tokens, positions, wslots, smat


def _run_pp(pp, tp, dp, m, b=4, t=16):
    devices = jax.devices()[: pp * tp * dp]
    mesh = meshmod.build_mesh(
        meshmod.MeshConfig(pp=pp, tp=tp, dp=dp), devices
    )
    tokens, positions, wslots, smat = _inputs(b, t)

    params = llama.init_params(CFG, jax.random.PRNGKey(0), dtype=jnp.float32)
    kv = llama.init_kv_cache(CFG, 1024, dtype=jnp.float32)
    ref_hidden, ref_kv = llama.forward(
        params, CFG, jnp.asarray(tokens), jnp.asarray(positions), kv,
        jnp.asarray(wslots.reshape(-1)), jnp.asarray(smat),
    )

    stacked = stack_layer_params(params)
    kv2 = llama.init_kv_cache(CFG, 1024, dtype=jnp.float32)
    k_st, v_st = kv2.stacked()
    stacked, k_st, v_st = pp_sharded_put(mesh, stacked, k_st, v_st)
    with jax.set_mesh(mesh):
        hidden, (k_out, v_out) = jax.jit(
            pp_forward, static_argnums=(1, 8, 9),
        )(
            stacked, CFG, jnp.asarray(tokens), jnp.asarray(positions),
            k_st, v_st, jnp.asarray(wslots), jnp.asarray(smat), mesh, m,
        )
    np.testing.assert_allclose(
        np.asarray(hidden), np.asarray(ref_hidden), rtol=2e-4, atol=2e-4
    )
    # stage-local pools carry the same KV as the reference per layer;
    # rows [1:] only — inactive pipeline steps park writes on the trash
    # page (slot 0), which holds garbage by the engine's contract
    for layer in (0, CFG.num_layers - 1):
        np.testing.assert_allclose(
            np.asarray(k_out[layer])[8:], np.asarray(ref_kv.k[layer])[8:],
            rtol=1e-5, atol=1e-5,
        )
        np.testing.assert_allclose(
            np.asarray(v_out[layer])[8:], np.asarray(ref_kv.v[layer])[8:],
            rtol=1e-5, atol=1e-5,
        )


def test_pp2_two_microbatches():
    _run_pp(pp=2, tp=1, dp=1, m=2)


def test_pp4_fill_drain():
    _run_pp(pp=4, tp=1, dp=1, m=4)


def test_pp_composes_with_tp():
    _run_pp(pp=2, tp=2, dp=1, m=2)


def test_pp_single_microbatch():
    _run_pp(pp=2, tp=1, dp=1, m=1)


def test_pp_rejects_moe_and_ragged_batch():
    mesh = meshmod.build_mesh(
        meshmod.MeshConfig(pp=2), jax.devices()[:2]
    )
    tokens, positions, wslots, smat = _inputs(3, 8)
    params = stack_layer_params(
        llama.init_params(CFG, jax.random.PRNGKey(0), dtype=jnp.float32)
    )
    k_st, v_st = llama.init_kv_cache(CFG, 512, dtype=jnp.float32).stacked()
    with pytest.raises(ValueError):
        pp_forward(
            params, CFG, jnp.asarray(tokens), jnp.asarray(positions),
            k_st, v_st, jnp.asarray(wslots), jnp.asarray(smat), mesh, 2,
        )
    moe_cfg = get_config("tiny-moe")
    with pytest.raises(NotImplementedError):
        pp_forward(
            params, moe_cfg, jnp.asarray(tokens[:2]), jnp.asarray(positions[:2]),
            k_st, v_st, jnp.asarray(wslots[:2]), jnp.asarray(smat[:2]), mesh, 2,
        )
