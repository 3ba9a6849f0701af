"""Mesh/sharding tests on the virtual 8-device CPU mesh.

The tp-sharded forward must compile, run, and agree numerically with the
single-device forward (GSPMD inserts the collectives)."""

import jax
import jax.numpy as jnp
import pytest

import numpy as np

from dynamo_tpu.models import config as cfgmod, llama
from dynamo_tpu.parallel import mesh as meshmod

CFG = cfgmod.get_config("tiny").with_(dtype="float32")


def test_mesh_shapes():
    mc = meshmod.MeshConfig.for_devices(8)
    assert mc.tp == 8 and mc.dp == 1
    m = meshmod.build_mesh(mc)
    assert m.axis_names == meshmod.AXES
    assert m.devices.size == 8

    mc2 = meshmod.MeshConfig(tp=2, dp=4)
    m2 = meshmod.build_mesh(mc2)
    assert m2.shape["tp"] == 2 and m2.shape["dp"] == 4


def test_tp_forward_matches_single_device():
    params = llama.init_params(CFG, jax.random.PRNGKey(0), dtype=jnp.float32)
    toks = np.random.RandomState(0).randint(1, 200, size=(1, 8))
    slots = np.arange(8, 16)[None]

    def run(p, kv):
        hidden, kv2 = llama.forward(
            p, CFG,
            jnp.asarray(toks, jnp.int32),
            jnp.arange(8, dtype=jnp.int32)[None],
            kv,
            jnp.asarray(slots.ravel(), jnp.int32),
            jnp.asarray(slots, jnp.int32),
        )
        return llama.logits(params if p is params else p, CFG, hidden), kv2

    ref_logits, _ = run(params, llama.init_kv_cache(CFG, 64, dtype=jnp.float32))

    # tp=2 sharded: kv heads (2) over tp
    mc = meshmod.MeshConfig(tp=2)
    m = meshmod.build_mesh(mc)
    sp = meshmod.shard_params(params, CFG, m)
    kv = llama.init_kv_cache(CFG, 64, dtype=jnp.float32)
    kv = llama.KVCache(
        k=tuple(jax.device_put(x, meshmod.kv_cache_sharding(m)) for x in kv.k),
        v=tuple(jax.device_put(x, meshmod.kv_cache_sharding(m)) for x in kv.v),
    )
    with jax.set_mesh(m):
        tp_logits, kv_out = run(sp, kv)

    np.testing.assert_allclose(
        np.asarray(tp_logits), np.asarray(ref_logits), rtol=1e-4, atol=1e-4
    )
    # KV pools kept their sharding (no accidental gather-to-host-layout)
    assert kv_out.k[0].sharding.is_equivalent_to(
        meshmod.kv_cache_sharding(m), kv_out.k[0].ndim
    )


def test_validate_model_mesh_rejects_indivisible_widths():
    """hidden/intermediate width checks (same clear-message contract as
    the head-count checks): the row-parallel wo/w_down shard their input
    dim over tp, and the tp_overlap ring executor needs even row blocks."""
    wide = CFG.with_(num_heads=8, num_kv_heads=8)  # heads pass at tp=8
    mc = meshmod.MeshConfig(tp=8)

    # widths divide -> fine
    meshmod.validate_model_mesh(wide, mc)

    with pytest.raises(ValueError, match=r"hidden_size=100.*not divisible by tp=8"):
        meshmod.validate_model_mesh(wide.with_(hidden_size=100), mc)
    with pytest.raises(
        ValueError, match=r"intermediate_size=\s*100.*not divisible by\s*tp=8"
    ):
        meshmod.validate_model_mesh(wide.with_(intermediate_size=100), mc)
    # unchanged contract for the head checks
    with pytest.raises(ValueError, match="num_kv_heads=2"):
        meshmod.validate_model_mesh(CFG, mc)


def test_tp_sharded_param_layout():
    mc = meshmod.MeshConfig(tp=2)
    m = meshmod.build_mesh(mc)
    params = llama.init_params(CFG, jax.random.PRNGKey(0), dtype=jnp.float32)
    sp = meshmod.shard_params(params, CFG, m)
    wq = sp["layers"][0]["wq"]
    # column-parallel: each shard holds half the out features
    shard_shapes = {s.data.shape for s in wq.addressable_shards}
    assert shard_shapes == {(CFG.hidden_size, CFG.q_size // 2)}


@pytest.mark.parametrize("quantize", [False, True], ids=["bf16", "int8"])
def test_init_params_under_target_shardings_matches_eager_init(quantize):
    """Random init creates every dense leaf directly under its target
    sharding (no device ever holds the whole tree — for an 8B bf16 model
    that would be one chip's whole HBM) and the values do not depend on
    the sharding: bitwise equal to the unsharded init."""
    cfg = cfgmod.get_config("tiny").with_(num_kv_heads=4)
    key = jax.random.PRNGKey(3)
    mesh = meshmod.build_mesh(meshmod.MeshConfig(tp=4))
    eager = llama.init_params(cfg, key, quantize=quantize)
    sharded = llama.init_params(
        cfg, key, quantize=quantize, shardings=meshmod.param_shardings(cfg, mesh)
    )
    wq = sharded["layers"][0]["wq"]
    wq = wq["q"] if quantize else wq
    assert wq.sharding.spec == jax.sharding.PartitionSpec(None, "tp")
    assert len({s.device for s in wq.addressable_shards}) == 4
    assert wq.addressable_shards[0].data.shape[1] == wq.shape[1] // 4
    same = jax.tree.map(
        lambda a, b: bool((np.asarray(a) == np.asarray(b)).all()),
        eager, sharded,
    )
    assert all(jax.tree.leaves(same))


@pytest.mark.parametrize("mesh", [
    dict(tp=4), dict(tp=2), dict(sp=2)], ids=["tp4", "tp2", "sp2"])
def test_engine_creates_kv_pools_under_their_shardings(mesh):
    """The pool is sized to each device's free memory, so a layer's whole
    unsharded pool is tp times what a device holds: it must never be
    built on one device first (that OOMed chip 0 of a four-chip host).
    Every mesh's engine holds ONE type of cache, its pools under the
    shardings `_kv_sharding` names."""
    from dynamo_tpu.engine import EngineConfig, JaxEngine

    cfg = cfgmod.get_config("tiny").with_(num_kv_heads=4)
    eng = JaxEngine(EngineConfig(
        model=cfg, mesh=meshmod.MeshConfig(**mesh), num_pages=32,
        page_size=16, kv_quantization="int8", max_model_len=256,
        prefill_chunk=256,
    ))
    tp, n = eng.config.mesh.tp, eng.config.mesh.num_devices
    assert type(eng.kv) is llama.KVCache
    k0, ks0 = eng.kv.k[0], eng.kv.ks[0]
    assert all(x.sharding == eng._kv_sharding for x in eng.kv.k + eng.kv.v)
    assert k0.sharding.spec == jax.sharding.PartitionSpec(None, "tp")
    assert k0.addressable_shards[0].data.shape == (k0.shape[0], k0.shape[1] // tp)
    assert ks0.sharding.spec == jax.sharding.PartitionSpec(None, "tp", None)
    assert len({s.device for s in ks0.addressable_shards}) == n
