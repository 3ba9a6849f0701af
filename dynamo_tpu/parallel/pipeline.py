"""Pipeline-parallel stage execution: GPipe microbatching over the pp axis.

The reference treats PP as an engine-internal concern and force-disables
it in its own workers (SURVEY §2.4, examples/llm/components/worker.py:
83-85) — models that don't fit one worker's memory go through engine
configs it never exercises. TPU-native, PP is one more mesh axis: layers
are split into contiguous stages, each stage's weights AND its per-layer
KV pools live on its pp shard, and microbatches stream through the
classic fill/drain schedule with `lax.ppermute` carrying activations
stage-to-stage over ICI.

SPMD shape (everything inside one `jax.shard_map` over ('pp',)):
- stacked params: every per-layer tensor stacked to [L, ...] and sharded
  P('pp') on the layer dim — each shard sees its [L/P, ...] stage slice;
- schedule: P + M - 1 steps; at step s, stage p processes microbatch
  m = s - p when 0 <= m < M. Every shard executes every step (SPMD);
  inactive (stage, step) pairs compute on garbage but their KV writes
  are routed to the trash page and their outputs discarded, so the
  lockstep costs idle FLOPs (the pipeline bubble), never correctness;
- stage P-1's outputs accumulate into the result buffer; a final psum
  over 'pp' replicates it (other stages contribute zeros).

v1 scope: dense models (no MoE routing inside the pipeline), gather-mode
attention. The engine serves pp-sharded models by jitting this forward;
tp composes (kernel shard_maps nest on the same mesh's tp axis) since
stage slices preserve the head dimension. With `tp_overlap=True` each
stage's layers run in the manual-tp ring-executor mode
(parallel/tp_overlap.py) — the residual stays row-scattered across the
whole fill/drain schedule, so stage-to-stage `ppermute` carries 1/tp of
the activation bytes; the single-mesh executor additionally serves the
pallas + packed-KV kernels, which stay pp=1 in v1 (the stage step has
no paged-kernel family).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from dynamo_tpu.models import llama
from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.ops.norm import rms_norm
from dynamo_tpu.ops.rope import rope_cos_sin, rope_inv_freq

_P = jax.sharding.PartitionSpec
_COL = _P("pp", None, "tp")
_ROW = _P("pp", "tp", None)
# single source of truth for per-layer-tensor placement: stage dim over
# pp, column/row-parallel dims over tp (manual-tp inside the shard_map)
LAYER_SPECS = {
    "attn_norm": _P("pp"), "mlp_norm": _P("pp"),
    "wq": _COL, "wk": _COL, "wv": _COL, "wo": _ROW,
    "w_gate": _COL, "w_up": _COL, "w_down": _ROW,
    "bq": _P("pp", "tp"), "bk": _P("pp", "tp"), "bv": _P("pp", "tp"),
}


def stack_layer_params(params: dict) -> dict:
    """Per-layer list-of-dicts -> dict of [L, ...] stacked arrays (plus
    the non-layer leaves unchanged). The stacked form shards P('pp') on
    the leading dim."""
    layers = params["layers"]
    stacked = {
        k: jnp.stack([lp[k] for lp in layers]) for k in layers[0]
    }
    out = dict(params)
    out["layers"] = stacked
    return out


def pp_sharded_put(mesh, stacked_params, k_stacked, v_stacked):
    """Place stacked params/pools (use `KVCache.stacked()` for the pool
    arrays): layer dim over pp, KV width over tp."""

    def put(x, spec):
        return jax.device_put(x, jax.sharding.NamedSharding(mesh, spec))

    out = dict(stacked_params)
    out["layers"] = {
        k: put(v, LAYER_SPECS[k]) for k, v in stacked_params["layers"].items()
    }
    out["embed"] = put(stacked_params["embed"], _P())
    out["final_norm"] = put(stacked_params["final_norm"], _P())
    if "lm_head" in stacked_params:
        out["lm_head"] = put(stacked_params["lm_head"], _P())
    return (
        out,
        put(k_stacked, _P("pp", None, "tp")),
        put(v_stacked, _P("pp", None, "tp")),
    )


def pp_forward(
    params: dict,            # stacked (stack_layer_params), pp-sharded
    cfg: ModelConfig,
    tokens: jnp.ndarray,     # [B, T] int32
    positions: jnp.ndarray,  # [B, T]
    k_pool: jnp.ndarray,     # [L, N, KW] pp-sharded on L
    v_pool: jnp.ndarray,
    write_slots: jnp.ndarray,   # [B, T] (0 = trash)
    slot_matrix: jnp.ndarray,   # [B, C]
    mesh,
    n_microbatches: int = 2,
    tp_overlap: bool = False,
):
    """Returns (hidden [B, T, D] after final norm, (k_pool, v_pool)).

    `tp_overlap` (tp > 1 meshes): run each stage's layers in the
    latency-hiding manual-tp mode (parallel/tp_overlap.py) — the
    residual stream stays ROW-SCATTERED over tp across the whole
    schedule, including the stage-to-stage ppermute rotation (which then
    carries 1/tp of the activation bytes), so a stage's collectives are
    two ring reduce-scatters per layer instead of two all-reduces and
    nothing re-gathers until the out_specs reassembly (layout, not a
    collective)."""
    if cfg.num_experts:
        raise NotImplementedError("pp v1 covers dense models")
    if cfg.hc_mult > 1:
        raise NotImplementedError(
            f"pp v1 carries one residual stream [B, T, D] between stages; "
            f"'{cfg.name}' carries {cfg.hc_mult} a token (models/mhc.py)")
    b = tokens.shape[0]
    m = n_microbatches
    if b % m:
        raise ValueError(f"batch {b} not divisible by {m} microbatches")
    pp = mesh.shape["pp"]
    tpn = mesh.shape.get("tp", 1)
    overlap = tp_overlap and tpn > 1

    x = params["embed"][tokens]
    if cfg.scale_embeddings:  # gemma: sqrt(d)-scaled embedding outputs
        x = x * jnp.asarray(cfg.hidden_size ** 0.5, x.dtype)
    inv_freq = jnp.asarray(rope_inv_freq(cfg))
    cos, sin = rope_cos_sin(inv_freq, positions)

    mb = b // m
    # [M, mb, ...] microbatch-major views
    x_mb = x.reshape(m, mb, *x.shape[1:])
    cos_mb = cos.reshape(m, mb, *cos.shape[1:])
    sin_mb = sin.reshape(m, mb, *sin.shape[1:])
    pos_mb = positions.reshape(m, mb, positions.shape[1])
    ws_mb = write_slots.reshape(m, mb, write_slots.shape[-1])
    sm_mb = slot_matrix.reshape(m, mb, slot_matrix.shape[-1])

    P = _P
    layer_specs = {k: LAYER_SPECS[k] for k in params["layers"]}

    t = tokens.shape[1]
    mb_rows = mb * t
    rows_p = -(-mb_rows // tpn) * tpn  # ring-padded rows per microbatch

    def stage_prog(layers_local, k_local, v_local, x_mb, cos_mb, sin_mb,
                   pos_mb, ws_mb, sm_mb):
        stage = jax.lax.axis_index("pp")
        if overlap:
            from dynamo_tpu.parallel import tp_overlap as _ov

            # scatter every microbatch's flattened rows over tp once,
            # up front: [M, mb, T, D] -> [M, rows_p/tp, D] per shard
            tp_idx = jax.lax.axis_index("tp")
            xf = x_mb.reshape(m, mb_rows, x_mb.shape[-1])
            if rows_p != mb_rows:
                xf = jnp.pad(xf, ((0, 0), (0, rows_p - mb_rows), (0, 0)))
            x_mb = jax.lax.dynamic_slice_in_dim(
                xf, tp_idx * (rows_p // tpn), rows_p // tpn, axis=1
            )

        def run_stage(x_in, cos1, sin1, ws1, sm1, pos1, k_local, v_local):
            def body(x, xs):
                lp, kvk, kvv = xs
                x, kvk, kvv, _, _ = llama.layer_step(
                    lp, cfg, x, cos1, sin1, kvk, kvv,
                    ws1.reshape(-1), llama.AttnSpec.gather(sm1), pos1,
                    tp_axis="tp", tp_overlap=overlap,
                    bt_shape=(mb, t) if overlap else None, layer=0,
                )
                return x, (kvk, kvv)

            x_out, (k_new, v_new) = jax.lax.scan(
                body, x_in, (layers_local, k_local, v_local)
            )
            return x_out, k_new, v_new

        n_steps = pp + m - 1
        state = jnp.zeros_like(x_mb[0])
        outs = jnp.zeros_like(x_mb)
        for s in range(n_steps):
            mb_idx = jnp.clip(s - stage, 0, m - 1)
            active = (s - stage >= 0) & (s - stage < m)
            x_in = jnp.where(
                stage == 0, x_mb[jnp.clip(s, 0, m - 1)], state
            )
            cos1 = cos_mb[mb_idx]
            sin1 = sin_mb[mb_idx]
            pos1 = pos_mb[mb_idx]
            sm1 = sm_mb[mb_idx]
            # inactive steps write the trash page, never real slots
            ws1 = jnp.where(active, ws_mb[mb_idx], 0)
            x_out, k_local, v_local = run_stage(
                x_in, cos1, sin1, ws1, sm1, pos1, k_local, v_local
            )
            # last stage banks its (active) output for microbatch mb_idx
            is_last = stage == pp - 1
            outs = outs.at[mb_idx].set(
                jnp.where(active & is_last, x_out, outs[mb_idx])
            )
            # rotate activations to the next stage for the next step
            state = jax.lax.ppermute(
                x_out, "pp", [(i, (i + 1) % pp) for i in range(pp)]
            )
        # replicate the result: only stage P-1 holds nonzero outs
        outs = jax.lax.psum(
            jnp.where(stage == pp - 1, outs, jnp.zeros_like(outs)), "pp"
        )
        return outs, k_local, v_local

    outs, k_pool, v_pool = jax.shard_map(
        stage_prog,
        mesh=mesh,
        in_specs=(
            layer_specs, P("pp", None, "tp"), P("pp", None, "tp"),
            P(), P(), P(), P(), P(), P(),
        ),
        out_specs=(
            # overlap keeps the banked outputs row-scattered; the spec
            # reassembles the global [M, rows_p, D] for free
            P(None, "tp", None) if overlap else P(),
            P("pp", None, "tp"), P("pp", None, "tp"),
        ),
        check_vma=False,
    )(params["layers"], k_pool, v_pool, x_mb, cos_mb, sin_mb,
      pos_mb, ws_mb, sm_mb)

    if overlap:
        outs = outs[:, :mb_rows].reshape(m, mb, t, outs.shape[-1])
    hidden = outs.reshape(b, *outs.shape[2:])
    hidden = rms_norm(
        hidden, params["final_norm"], cfg.rms_norm_eps,
        weight_offset=cfg.norm_weight_offset,
    )
    return hidden, (k_pool, v_pool)
