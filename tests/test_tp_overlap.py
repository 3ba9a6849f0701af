"""Manual-TP overlap algebra (parallel/tp_overlap.py): RS+AG == psum,
chunked-ring all-gather bit-identity, the layer_step(tp_overlap=True)
equivalence suite vs the serialized-psum baseline and tp=1, and the
ledger's 0.5x exposed-bytes invariant — all on the CPU 8-virtual-device
mesh."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.models import llama
from dynamo_tpu.models.config import get_config
from dynamo_tpu.parallel import mesh as meshmod
from dynamo_tpu.parallel import tp_overlap as ov

# tiny widened to 8 query + 8 kv heads so the head shards survive tp=8
# (same shape the multichip smoke serves)
CFG = get_config("tiny").with_(
    dtype="float32", num_layers=2, num_heads=8, num_kv_heads=8
)
TP = 8


def _mesh(tp=TP):
    return meshmod.build_mesh(
        meshmod.MeshConfig(tp=tp), jax.devices()[:tp]
    )


def _inputs(b, t, page=8):
    rng = np.random.RandomState(0)
    tokens = rng.randint(1, CFG.vocab_size, (b, t)).astype(np.int32)
    positions = np.tile(np.arange(t, dtype=np.int32), (b, 1))
    wslots = np.stack(
        [np.arange(page * (1 + 8 * i), page * (1 + 8 * i) + t) for i in range(b)]
    ).astype(np.int32)
    smat = wslots.copy()
    return tokens, positions, wslots, smat


def _overlap_forward(mesh, params, tokens, positions, kv, wslots, attn,
                     **kw):
    """`tp_overlap_forward` under ONE jit, as the engine's step programs
    call it. Eager, the shard_map's body is dispatched (and each new
    shape of each operation compiled) operation by operation on every
    device of the mesh, ring step by ring step: minutes for what one
    compilation does in seconds."""
    fwd = jax.jit(
        lambda p, tok, pos, kv, ws, attn: ov.tp_overlap_forward(
            p, CFG, tok, pos, kv, ws, attn, mesh, **kw))
    with jax.set_mesh(mesh):
        return fwd(params, jnp.asarray(tokens), jnp.asarray(positions), kv,
                   jnp.asarray(wslots.reshape(-1)), attn)


# ---------------------------------------------------------------------------
# ring primitive algebra
# ---------------------------------------------------------------------------


def _shmap(fn, mesh, n_in, out_specs):
    P = jax.sharding.PartitionSpec
    return jax.shard_map(
        fn, mesh=mesh, in_specs=(P("tp", None),) * n_in,
        out_specs=out_specs, check_vma=False,
    )


def test_ring_all_gather_bit_identical():
    mesh = _mesh()
    P = jax.sharding.PartitionSpec
    x = np.random.RandomState(1).randn(TP * 4, 24).astype(np.float32)

    ring = _shmap(lambda s: ov.ring_all_gather(s, "tp"), mesh, 1, P(None, None))
    ref = _shmap(
        lambda s: jax.lax.all_gather(s, "tp", tiled=True), mesh, 1,
        P(None, None),
    )
    got, want = np.asarray(ring(x)), np.asarray(ref(x))
    assert np.array_equal(got, want)
    assert np.array_equal(got, x)  # gather of a scatter is the identity


def test_rs_plus_ag_equals_psum():
    mesh = _mesh()
    P = jax.sharding.PartitionSpec
    # per-shard PARTIAL sums, like the row-parallel projection outputs
    y = np.random.RandomState(2).randn(TP, TP * 4, 24).astype(np.float32)

    def decomposed(part):
        scat = ov.ring_reduce_scatter(part, "tp")
        return ov.ring_all_gather(scat, "tp")

    got = _shmap(decomposed, mesh, 1, P(None, None))(
        y.reshape(TP * TP * 4, 24)
    )
    want = _shmap(
        lambda part: jax.lax.psum(part, "tp"), mesh, 1, P(None, None)
    )(y.reshape(TP * TP * 4, 24))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)
    # and both equal the plain sum over shards
    np.testing.assert_allclose(np.asarray(got), y.sum(0),
                               rtol=1e-5, atol=1e-5)


def test_ring_ag_matmul_matches_gathered_matmul():
    mesh = _mesh()
    P = jax.sharding.PartitionSpec
    rng = np.random.RandomState(3)
    x = rng.randn(TP * 4, 32).astype(np.float32)   # rows scattered
    w1 = rng.randn(32, TP * 8).astype(np.float32)  # column-parallel
    w2 = rng.randn(32, TP * 16).astype(np.float32)

    def fused(xs, w1s, w2s):
        return tuple(ov.ring_ag_matmul(xs, (w1s, w2s), "tp"))

    def serial(xs, w1s, w2s):
        xf = jax.lax.all_gather(xs, "tp", tiled=True)
        return xf @ w1s, xf @ w2s

    specs = (P("tp", None), P(None, "tp"), P(None, "tp"))
    out = (P(None, "tp"), P(None, "tp"))
    got = jax.shard_map(fused, mesh=mesh, in_specs=specs,
                           out_specs=out, check_vma=False)(x, w1, w2)
    want = jax.shard_map(serial, mesh=mesh, in_specs=specs,
                            out_specs=out, check_vma=False)(x, w1, w2)
    # row-only chunking: no reduction is reordered, so the fused ring
    # reproduces the gathered matmul bit-for-bit (the documented
    # within-shard FP invariant)
    assert np.array_equal(np.asarray(got[0]), np.asarray(want[0]))
    assert np.array_equal(np.asarray(got[1]), np.asarray(want[1]))


def test_pad_rows_and_scatter_roundtrip():
    mesh = _mesh()
    P = jax.sharding.PartitionSpec
    x = np.random.RandomState(4).randn(13, 8).astype(np.float32)  # 13 % 8 != 0

    def roundtrip(xr):
        xs = ov.scatter_rows(ov.pad_rows(xr, TP), "tp")
        return ov.ring_all_gather(xs, "tp")

    got = jax.shard_map(
        roundtrip, mesh=mesh, in_specs=(P(),), out_specs=P(None, None),
        check_vma=False,
    )(x)
    assert got.shape == (16, 8)
    assert np.array_equal(np.asarray(got)[:13], x)
    assert np.all(np.asarray(got)[13:] == 0.0)


# ---------------------------------------------------------------------------
# layer_step equivalence: overlap vs serialized psum vs tp=1
# ---------------------------------------------------------------------------


def _layer_io(b, t):
    tokens, positions, wslots, smat = _inputs(b, t)
    params = llama.init_params(CFG, jax.random.PRNGKey(0), dtype=jnp.float32)
    x = np.asarray(params["embed"])[tokens].astype(np.float32)
    from dynamo_tpu.ops.rope import rope_cos_sin, rope_inv_freq

    cos, sin = rope_cos_sin(
        jnp.asarray(rope_inv_freq(CFG)), jnp.asarray(positions)
    )
    return params, x, cos, sin, tokens, positions, wslots, smat


@pytest.mark.parametrize("b,t", [(4, 16), (3, 5)])  # (3, 5): padded rows
def test_layer_step_overlap_equivalence(b, t):
    mesh = _mesh()
    params, x, cos, sin, _, positions, wslots, smat = _layer_io(b, t)
    lp = params["layers"][0]
    kv = llama.init_kv_cache(CFG, 512, dtype=jnp.float32)

    legs = {}
    for overlap in (False, True):
        run = ov.single_layer_executor(
            CFG, mesh, b, t, page_size=8, overlap=overlap
        )
        x_out, k_out, v_out = run(
            lp, kv.k[0], kv.v[0], jnp.asarray(x), cos, sin,
            jnp.asarray(wslots.reshape(-1)), jnp.asarray(smat),
            jnp.asarray(positions),
        )
        if overlap:
            x_out = np.asarray(x_out)[: b * t].reshape(b, t, -1)
        legs[overlap] = (np.asarray(x_out), np.asarray(k_out),
                         np.asarray(v_out))

    np.testing.assert_allclose(legs[True][0], legs[False][0],
                               rtol=2e-5, atol=2e-5)
    # KV rows written by the layer are bit-identical: both legs compute
    # k/v from the same full-row activations with unreordered matmuls
    assert np.array_equal(legs[True][1], legs[False][1])
    assert np.array_equal(legs[True][2], legs[False][2])


def test_forward_overlap_matches_tp1_greedy():
    mesh = _mesh()
    b, t = 4, 16
    tokens, positions, wslots, smat = _inputs(b, t)
    params = llama.init_params(CFG, jax.random.PRNGKey(0), dtype=jnp.float32)

    kv1 = llama.init_kv_cache(CFG, 512, dtype=jnp.float32)
    ref_hidden, ref_kv = llama.forward(
        params, CFG, jnp.asarray(tokens), jnp.asarray(positions), kv1,
        jnp.asarray(wslots.reshape(-1)), jnp.asarray(smat),
    )

    kv8 = llama.init_kv_cache(CFG, 512, dtype=jnp.float32)
    hidden, kv_out = _overlap_forward(
        mesh, params, tokens, positions, kv8, wslots, jnp.asarray(smat),
        page_size=8)
    np.testing.assert_allclose(np.asarray(hidden), np.asarray(ref_hidden),
                               rtol=2e-4, atol=2e-4)
    for layer in (0, CFG.num_layers - 1):
        np.testing.assert_allclose(
            np.asarray(kv_out.k[layer])[8:], np.asarray(ref_kv.k[layer])[8:],
            rtol=1e-5, atol=1e-5,
        )
    # the gated serving property: greedy streams byte-identical to tp=1
    lg_ref = llama.logits(params, CFG, ref_hidden[:, -1])
    lg_ov = llama.logits(params, CFG, hidden[:, -1])
    assert np.array_equal(
        np.asarray(jnp.argmax(lg_ref, -1)), np.asarray(jnp.argmax(lg_ov, -1))
    )


def test_tp_overlap_forward_refuses_moe_and_sp_ring():
    """The two REMAINING refusals: MoE routing (all-to-all expert
    dispatch doesn't decompose into row rings) and the sp ring prefill
    (the ring owns the token axis the executor wants to scatter).
    Quantized KV composes since the packed-KV executor rev — see the
    equivalence tests below."""
    mesh = _mesh()
    b, t = 2, 8
    tokens, positions, wslots, smat = _inputs(b, t)
    params = llama.init_params(CFG, jax.random.PRNGKey(0), dtype=jnp.float32)
    kv = llama.init_kv_cache(CFG, 512, dtype=jnp.float32)
    with pytest.raises(ValueError, match="dense"):
        ov.tp_overlap_forward(
            params, get_config("tiny-moe"), jnp.asarray(tokens),
            jnp.asarray(positions), kv, jnp.asarray(wslots.reshape(-1)),
            jnp.asarray(smat), mesh,
        )
    ring_spec = llama.AttnSpec.ring(jnp.asarray(smat), mesh, page_size=8)
    with pytest.raises(ValueError, match="ring"):
        ov.tp_overlap_forward(
            params, CFG, jnp.asarray(tokens), jnp.asarray(positions), kv,
            jnp.asarray(wslots.reshape(-1)), ring_spec, mesh,
        )


def test_forward_overlap_quantized_weights_matches_tp1_bitwise():
    """int8 quantized WEIGHTS under the executor: ring_rs_matmul carries
    the row-parallel projections' int32 accumulator across the ring
    (integer addition is associative), and the global activation scale is
    a pmax of per-shard absmaxes — so quantized layers are bitwise
    tp=1-identical, a property the serialized per-shard-scale manual-tp
    path never had."""
    from dynamo_tpu.ops.quant import quantize_params

    mesh = _mesh()
    b, t = 4, 16
    tokens, positions, wslots, smat = _inputs(b, t)
    params = quantize_params(
        llama.init_params(CFG, jax.random.PRNGKey(0), dtype=jnp.float32), CFG
    )

    kv1 = llama.init_kv_cache(CFG, 512, dtype=jnp.float32)
    # both sides compiled, as the engine runs both: a norm XLA fuses
    # rounds differently from the eager one, and one bit of that flips
    # an int8 activation bucket
    ref_hidden, _ = jax.jit(llama.forward, static_argnums=1)(
        params, CFG, jnp.asarray(tokens), jnp.asarray(positions), kv1,
        jnp.asarray(wslots.reshape(-1)), jnp.asarray(smat),
    )
    kv8 = llama.init_kv_cache(CFG, 512, dtype=jnp.float32)
    hidden, _ = _overlap_forward(
        mesh, params, tokens, positions, kv8, wslots, jnp.asarray(smat),
        page_size=8)
    np.testing.assert_allclose(np.asarray(hidden), np.asarray(ref_hidden),
                               rtol=2e-4, atol=2e-4)
    lg_ref = llama.logits(params, CFG, ref_hidden[:, -1])
    lg_ov = llama.logits(params, CFG, hidden[:, -1])
    assert np.array_equal(
        np.asarray(jnp.argmax(lg_ref, -1)), np.asarray(jnp.argmax(lg_ov, -1))
    )


# ---------------------------------------------------------------------------
# compile-variant census: the overlap executor adds no variant family
# ---------------------------------------------------------------------------


async def test_compile_census_flat_with_tp_overlap_pallas():
    """tp_overlap=1 on the pallas+quantized backend must not mint a new
    compile-variant family per shape bucket: the executor REPLACES the
    per-layer forward inside the same dispatch entry points, so serving
    the same workload compiles no more executables than the GSPMD leg
    (process-global census, engine/telemetry.py compile listener)."""
    from dynamo_tpu.engine import EngineConfig, JaxEngine, telemetry
    from dynamo_tpu.llm.protocols.common import (
        PreprocessedRequest, SamplingOptions, StopConditions,
    )
    from dynamo_tpu.parallel.mesh import MeshConfig
    from dynamo_tpu.runtime.pipeline.context import Context

    def eng(tp_overlap):
        return JaxEngine(EngineConfig(
            model=CFG, dtype="float32", mesh=MeshConfig(tp=2),
            attn_backend="pallas", kv_quantization="int8",
            page_size=128, num_pages=8, max_batch_size=2,
            max_model_len=256, prefill_chunk=128, tp_overlap=tp_overlap,
            seed=0,
        ))

    async def serve(engine):
        pre = PreprocessedRequest(
            token_ids=[5, 17, 42, 9, 88, 3],
            stop_conditions=StopConditions(max_tokens=4, ignore_eos=True),
            sampling_options=SamplingOptions(greedy=True),
        )
        frames = [
            f async for f in await engine.generate(Context(pre.to_dict()))
        ]
        return [t for f in frames for t in f.get("token_ids") or []]

    telemetry.install_compile_listener()
    deltas, tokens = {}, {}
    for overlap in (False, True):
        engine = eng(overlap)
        c0 = telemetry.compile_stats()["compile_events"]
        tokens[overlap] = await serve(engine)
        deltas[overlap] = telemetry.compile_stats()["compile_events"] - c0
        if overlap:
            assert engine._tp_overlap_manual
            assert engine.metrics()["tp_overlap_dispatches"] > 0
        await engine.close()

    assert tokens[True] == tokens[False]
    assert deltas[True] <= deltas[False], (
        f"tp_overlap minted extra compile variants: {deltas}"
    )


# ---------------------------------------------------------------------------
# ledger: measured exposed bytes halve, total bytes conserved
# ---------------------------------------------------------------------------


def test_collective_ledger_exposed_ratio_half():
    mesh = _mesh()
    b, t = 4, 16  # b*t % tp == 0: no ring padding, ratio exact
    params, x, cos, sin, _, positions, wslots, smat = _layer_io(b, t)
    lp = params["layers"][0]
    kv = llama.init_kv_cache(CFG, 512, dtype=jnp.float32)
    args = (
        lp, kv.k[0], kv.v[0], jnp.asarray(x), cos, sin,
        jnp.asarray(wslots.reshape(-1)), jnp.asarray(smat),
        jnp.asarray(positions),
    )

    measured = {}
    for overlap in (False, True):
        run = ov.single_layer_executor(
            CFG, mesh, b, t, page_size=8, overlap=overlap
        )
        with ov.record_collectives() as led:
            jax.block_until_ready(run(*args))
        measured[overlap] = (led.exposed, led.overlapped, led.total)

    base_exposed, base_hidden, base_total = measured[False]
    ov_exposed, ov_hidden, ov_total = measured[True]
    assert base_hidden == 0  # serialized leg has nothing overlapped
    assert ov_exposed * 2 == base_exposed  # the 0.5x invariant
    # wire bytes are conserved: RS+AG re-schedules, it does not remove
    assert ov_total == base_total
    # closed form agrees with the measured collectives
    want = ov.collective_bytes_per_layer(
        CFG.hidden_size, b * t, TP, itemsize=4, overlap=True
    )
    assert ov_exposed == want
    assert base_exposed == ov.collective_bytes_per_layer(
        CFG.hidden_size, b * t, TP, itemsize=4, overlap=False
    )


def test_collective_bytes_formula():
    # tp=1 is free; ratio is exactly 0.5 for every tp > 1
    assert ov.collective_bytes_per_layer(64, 32, 1) == 0
    for tp in (2, 4, 8):
        base = ov.collective_bytes_per_layer(64, 32, tp, overlap=False)
        half = ov.collective_bytes_per_layer(64, 32, tp, overlap=True)
        assert base == 2 * half
        assert base == 2 * (2 * (tp - 1) * 32 * 64 * 4 // tp)
