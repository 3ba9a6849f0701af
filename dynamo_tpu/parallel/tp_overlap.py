"""Latency-hiding manual-TP layer executor: reduce-scatter residual
stream + software-pipelined ring collectives.

The GSPMD tp path pays two serialized full-width all-reduces per layer
(models/llama.py after `wo` and after `w_down`) during which the MXU
sits idle. This module removes that stall with the Megatron-style
sequence-parallel decomposition (Korthikanti et al., 2022) plus the
Wang et al. 2023 chunked-collective overlap:

- **Reduce-scatter residual stream.** Each per-layer `psum` splits into
  reduce-scatter + all-gather; the residual add and RMS-norm between
  them run on the SCATTERED view (activation rows — batch*tokens —
  sharded over tp), so the replicated-activation window between the two
  projections disappears. Rows shard over tp for decode/mixed steps and
  over tokens for prefill chunks — both are the same flattened
  [B*T, D] row axis, which is what the executor scatters.
- **Software-pipelined rings.** The all-gather half never runs as a
  standalone collective: it rides `ring_ag_matmul`, a `lax.ppermute`
  ring interleaved with slices of the next column-parallel matmul
  (wq/wk/wv, w_gate/w_up) — chunk i's matmul runs while chunk i+1 is on
  the wire (the permute for step i+1 is issued BEFORE step i's matmuls,
  which is what lets the latency-hiding scheduler overlap them). The
  reduce-scatter half runs as a chunked `lax.ppermute` ring too
  (`ring_reduce_scatter`), so no collective in the layer is a
  full-width blocking all-reduce.

Byte accounting (the bench's 0.5x invariant, docs/parallelism.md):
ring RS+AG moves the SAME total wire bytes as a ring all-reduce —
2(n-1)/n * S per device either way; sequence parallelism adds no
communication. What halves is the EXPOSED bytes: the traffic of
standalone collectives on the critical path. The overlap executor
exposes only the two reduce-scatters ((n-1)/n * S each) — the
all-gather halves ride the column-matmul rings as overlapped traffic —
so exposed bytes per layer read exactly 0.5x the baseline's two
all-reduces. `CollectiveLedger` measures both kinds off the traced
collectives; `collective_bytes_per_layer` is the closed-form the engine
counters and the bench invariant use.

FP reduction-order invariant (greedy byte-identity): the rings chunk
only the activation ROW axis, never the matmul contraction axis, so
every per-shard partial product is bitwise identical to the serialized
manual-TP path. Cross-shard summation order differs (the RS ring
accumulates block j in cyclic order j+1, .., j-1, j; psum's order is
XLA's choice) — exactly the class of difference the GSPMD tp path
already carries vs tp=1 — and greedy streams stay byte-identical to
tp=1 (gated by scripts/multichip_smoke.py and tests/test_tp_overlap.py).

Composition matrix (docs/parallelism.md "TP comm/compute overlap"):
composes with mixed batching, the step pipeline, spec decode,
the pallas serving backend (the kernels' per-layer shard_maps collapse
into the executor's single one — `tp_overlap_forward` takes the full
AttnSpec and the shard body reruns the kernels on shard-local pools
with a mesh-free spec), int8/int4 packed KV pools (block tables, packed
pools and scale channels ride as shard-local operands; the tp-blocked
scale layout restricts per shard to exactly the kv_tp=1 layout over its
local channels) and int8 quantized weights (`ring_ag_matmul` dispatches
per chunk through `ops/quant.mm`; the row-parallel projections run
`ring_rs_matmul`, whose INT32 ring reduce-scatter keeps quantized
outputs bitwise equal to tp=1). Refuses — engine falls back to GSPMD +
XLA latency-hiding flags — MoE routing (expert dispatch/combine
all-to-alls own the layer layout) and sp>1 ring prefill (the ring owns
the token axis the executor would scatter).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from dynamo_tpu.ops.norm import rms_norm
from dynamo_tpu.ops.quant import is_quantized, mm
from dynamo_tpu.ops.rope import rope_cos_sin, rope_inv_freq

_P = jax.sharding.PartitionSpec


# ---------------------------------------------------------------------------
# collective-bytes ledger
# ---------------------------------------------------------------------------


class CollectiveLedger:
    """Trace-time wire-byte meter for the manual-TP collectives.

    The ring primitives below (and `psum_allreduce`, the serialized
    baseline's all-reduce spelling) add their per-device wire bytes here
    WHILE THEY TRACE — chunk shapes are static, so the counts are
    measured off the actual collectives in the jaxpr, not re-derived
    from a formula. `exposed` counts standalone collectives on the
    critical path (all-reduce, reduce-scatter); `overlapped` counts
    traffic hidden under matmul slices (the ring-AG-fused gathers).
    Arm with `record_collectives()` around the TRACING call (a jit
    cache hit re-traces nothing and records nothing).
    """

    def __init__(self):
        self.exposed = 0
        self.overlapped = 0

    @property
    def total(self) -> int:
        return self.exposed + self.overlapped


_ledger: CollectiveLedger | None = None


class record_collectives:
    """Context manager arming a fresh CollectiveLedger (module-global:
    tracing is single-threaded per process in practice, and the bench
    arms it only around one-shot trace calls)."""

    def __enter__(self) -> CollectiveLedger:
        global _ledger
        self._prev = _ledger
        _ledger = CollectiveLedger()
        return _ledger

    def __exit__(self, *exc):
        global _ledger
        _ledger = self._prev
        return False


def _note(kind: str, nbytes: int) -> None:
    if _ledger is not None:
        setattr(_ledger, kind, getattr(_ledger, kind) + int(nbytes))


def collective_bytes_per_layer(
    hidden_size: int, rows: int, tp: int, itemsize: int = 4,
    overlap: bool = False,
) -> int:
    """Closed-form EXPOSED per-layer collective bytes per device.

    Baseline: two ring all-reduces of the [rows, hidden] residual tensor
    (2(n-1)/n * S wire bytes each). Overlap: two ring reduce-scatters
    ((n-1)/n * S each) — the all-gather halves ride the column-matmul
    rings and count as overlapped, not exposed. The ratio is exactly
    0.5 for every tp > 1; total wire bytes are conserved (sequence
    parallelism adds no communication, it re-schedules it)."""
    if tp <= 1:
        return 0
    s = rows * hidden_size * itemsize
    per_rs = (tp - 1) * s // tp
    return 2 * (2 * per_rs if not overlap else per_rs)


def psum_allreduce(x: jnp.ndarray, axis_name) -> jnp.ndarray:
    """The serialized manual-TP all-reduce, routed through the ledger:
    ring all-reduce wire bytes are 2(n-1)/n * S per device."""
    n = jax.lax.axis_size(axis_name)
    if n > 1:
        _note("exposed", 2 * (n - 1) * x.size * x.dtype.itemsize // n)
    return jax.lax.psum(x, axis_name)


# XLA latency-hiding scheduler / async-collective flags for the GSPMD
# fallback path (engines whose shapes the manual executor refuses).
# These are the TPU-backend scheduler knobs that let XLA overlap its own
# GSPMD-inserted collectives with adjacent compute — the flag-level
# sibling of what the ring executor does by construction.
_XLA_OVERLAP_FLAGS = (
    "--xla_tpu_enable_latency_hiding_scheduler=true",
    "--xla_tpu_enable_async_collective_fusion=true",
    "--xla_tpu_enable_async_collective_fusion_fuse_all_gather=true",
    "--xla_tpu_enable_async_collective_fusion_multiple_steps=true",
    "--xla_tpu_overlap_compute_collective_tc=true",
)


def request_gspmd_overlap_flags() -> list[str]:
    """Append the latency-hiding flags to XLA_FLAGS (TPU backends only —
    callers gate on backend; the CPU XLA rejects unknown TPU flags).
    Flags already present (any value) are left untouched so an explicit
    launch-env choice wins. Returns the flags newly added; XLA reads the
    env at compile time, so they cover executables compiled after this
    call — engine init runs before any step function compiles."""
    import os

    cur = os.environ.get("XLA_FLAGS", "")
    added = [f for f in _XLA_OVERLAP_FLAGS if f.split("=")[0] not in cur]
    if added:
        os.environ["XLA_FLAGS"] = " ".join([cur, *added]).strip()
    return added


# ---------------------------------------------------------------------------
# ring primitives
# ---------------------------------------------------------------------------


def _ring_perm(n: int) -> list[tuple[int, int]]:
    return [(i, (i + 1) % n) for i in range(n)]


def ring_all_gather(x: jnp.ndarray, axis_name) -> jnp.ndarray:
    """Chunked ppermute ring all-gather over the leading axis:
    bit-identical to `lax.all_gather(..., tiled=True)` (pure data
    movement, no arithmetic). Standalone spelling — counts as EXPOSED;
    the layer executor prefers `ring_ag_matmul`, which hides the same
    traffic under matmul slices."""
    n = jax.lax.axis_size(axis_name)
    if n == 1:
        return x
    idx = jax.lax.axis_index(axis_name)
    m = x.shape[0]
    perm = _ring_perm(n)
    _note("exposed", (n - 1) * x.size * x.dtype.itemsize)
    out = jnp.zeros((n * m,) + x.shape[1:], x.dtype)
    out = jax.lax.dynamic_update_slice_in_dim(out, x, idx * m, axis=0)
    chunk = x
    for step in range(1, n):
        chunk = jax.lax.ppermute(chunk, axis_name, perm)
        src = (idx - step) % n
        out = jax.lax.dynamic_update_slice_in_dim(
            out, chunk, src * m, axis=0
        )
    return out


def ring_ag_matmul(
    x: jnp.ndarray, weights: tuple, axis_name,
) -> list[jnp.ndarray]:
    """All-gather-fused column-parallel matmuls: gather the row-scattered
    activation `x` [m, D] around the ring WHILE each shard multiplies the
    resident chunk into its local weight shards ([D, F/n] each).

    One gather ring serves every weight in `weights` (wq/wk/wv share a
    ring, w_gate/w_up share a ring). The permute for chunk i+1 is issued
    BEFORE chunk i's matmuls — the double-buffered shape the
    latency-hiding scheduler overlaps; on backends that run it
    sequentially the result is the same bits, just unhidden.

    Returns full-row outputs [n*m, F/n], one per weight, each block
    bitwise identical to `all_gather(x) @ w` — the ring splits only the
    row axis, never the contraction axis, so no summation is reordered.
    """
    n = jax.lax.axis_size(axis_name)
    if n == 1:
        return [mm(x, w) for w in weights]
    idx = jax.lax.axis_index(axis_name)
    m = x.shape[0]
    perm = _ring_perm(n)
    _note("overlapped", (n - 1) * x.size * x.dtype.itemsize)
    outs = None
    chunk = x
    for step in range(n):
        # issue the send first: chunk i+1 is on the wire during chunk
        # i's matmuls (the overlap this module exists for)
        nxt = (
            jax.lax.ppermute(chunk, axis_name, perm)
            if step < n - 1 else None
        )
        src = (idx - step) % n
        ys = [mm(chunk, w) for w in weights]
        if outs is None:
            outs = [
                jnp.zeros((n * m,) + y.shape[1:], y.dtype) for y in ys
            ]
        outs = [
            jax.lax.dynamic_update_slice_in_dim(o, y, src * m, axis=0)
            for o, y in zip(outs, ys)
        ]
        chunk = nxt
    return outs


def ring_reduce_scatter(y: jnp.ndarray, axis_name) -> jnp.ndarray:
    """Chunked ppermute ring reduce-scatter over the leading axis:
    [n*m, ...] partial sums in, [m, ...] fully-reduced block `idx` out.
    Block j accumulates in cyclic shard order j+1, .., j-1, j — the
    documented cross-shard reduction order (see module docstring)."""
    n = jax.lax.axis_size(axis_name)
    if n == 1:
        return y
    idx = jax.lax.axis_index(axis_name)
    m = y.shape[0] // n
    perm = _ring_perm(n)
    _note("exposed", (n - 1) * y.size * y.dtype.itemsize // n)

    def blk(j):
        return jax.lax.dynamic_slice_in_dim(y, j * m, m, axis=0)

    acc = blk((idx - 1) % n)
    for step in range(1, n):
        acc = jax.lax.ppermute(acc, axis_name, perm)
        acc = acc + blk((idx - 1 - step) % n)
    return acc


def ring_rs_matmul(x: jnp.ndarray, w, axis_name) -> jnp.ndarray:
    """Row-parallel projection ending in a ring reduce-scatter — the RS
    half of the decomposed psum, with the matmul folded in so quantized
    weights dequantize EXACTLY once.

    Plain weights: local matmul, pad rows to a tp multiple, ring RS of
    the partial products (bitwise what the callers previously spelled
    inline). Quantized weights ({"q","s"}, ops/quant.py): the per-row
    dynamic activation scale is computed GLOBALLY — a pmax over tp of the
    per-row absmax, the same value tp=1 sees (max of maxes reorders
    nothing) — each shard quantizes its contraction slice against it and
    dots to int32 partials, and the ring reduce-scatter runs in INT32.
    Integer addition is associative, so the scattered accumulator rows
    are bitwise equal to tp=1's before the one shared f32 dequant
    epilogue: quantized row-parallel outputs stay byte-identical to tp=1
    (the serialized manual path's per-shard local scales cannot offer
    that). The tiny pmax rides the ledger as exposed bytes, so quantized
    layers read slightly above the exact 0.5x of the unquantized
    invariant — documented, not gated.

    `x` [R, F_local] full rows (contraction dim sharded); returns the
    row-scattered [ceil(R/tp)*tp/tp, D] block for this shard."""
    n = jax.lax.axis_size(axis_name)
    if not is_quantized(w):
        return ring_reduce_scatter(pad_rows(mm(x, w), n), axis_name)
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1, keepdims=True)
    if n > 1:
        _note("exposed", 2 * (n - 1) * amax.size * amax.dtype.itemsize // n)
        amax = jax.lax.pmax(amax, axis_name)
    xs = jnp.where(amax > 0, amax / 127.0, 1.0)
    xi = jnp.clip(jnp.round(xf / xs), -127, 127).astype(jnp.int8)
    acc = jax.lax.dot_general(
        xi, w["q"], (((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )
    acc = ring_reduce_scatter(pad_rows(acc, n), axis_name)
    xs_rows = scatter_rows(pad_rows(xs, n), axis_name)
    out = acc.astype(jnp.float32) * xs_rows * w["s"]
    return out.astype(x.dtype)


def scatter_rows(x: jnp.ndarray, axis_name) -> jnp.ndarray:
    """Slice this shard's row block out of a replicated [n*m, ...] array
    (free under shard_map — no collective)."""
    n = jax.lax.axis_size(axis_name)
    if n == 1:
        return x
    idx = jax.lax.axis_index(axis_name)
    m = x.shape[0] // n
    return jax.lax.dynamic_slice_in_dim(x, idx * m, m, axis=0)


def pad_rows(x: jnp.ndarray, tp: int) -> jnp.ndarray:
    """Zero-pad the leading (row) axis to a tp multiple so it scatters
    evenly. Zero rows are inert through norms and matmuls; callers slice
    the real rows back after the final gather."""
    r = x.shape[0]
    rp = -(-r // tp) * tp
    if rp == r:
        return x
    return jnp.pad(x, ((0, rp - r),) + ((0, 0),) * (x.ndim - 1))


# ---------------------------------------------------------------------------
# whole-forward shard_map wrapper
# ---------------------------------------------------------------------------


def _layer_in_specs(layers: list[dict]) -> list[dict]:
    """Per-layer PartitionSpecs matching parallel/mesh.param_shardings —
    the shard_map in_specs must agree with the GSPMD placement so entry
    is a no-op reslice, not a reshard. Quantized leaves ({"q","s"}
    dicts) mirror `mesh.shard_params`: q at the weight's spec, the
    per-output-channel scale on the spec's last axis (sharded for
    column-parallel, replicated for row-parallel)."""
    col, row = _P(None, "tp"), _P("tp", None)
    spec = {
        "attn_norm": _P(), "mlp_norm": _P(),
        "wq": col, "wk": col, "wv": col, "wo": row,
        "w_gate": col, "w_up": col, "w_down": row,
        "bq": _P("tp"), "bk": _P("tp"), "bv": _P("tp"),
    }

    def leaf(k, v):
        s = spec[k]
        if is_quantized(v):
            return {"q": s, "s": _P(s[-1]) if len(s) else _P()}
        return s

    return [{k: leaf(k, lp[k]) for k in lp} for lp in layers]


def single_layer_executor(
    cfg, mesh, b: int, t: int, page_size: int = 16, overlap: bool = True,
):
    """One `layer_step` under shard_map — the test harness behind
    tests/test_tp_overlap.py's serialized-vs-overlapped per-layer
    comparison and its amortization-free measured byte ratio.

    The overlap leg returns the residual STILL SCATTERED (out_spec
    P('tp', None) reassembles the global [Rp, D] for free — shard_map
    concatenation is layout, not a collective), so a
    `record_collectives()` armed around this trace sees EXACTLY one
    layer's collectives: two ring reduce-scatters exposed + the two
    matmul-ring gathers overlapped, against the serialized leg's two
    all-reduces. Returns a fresh jitted callable
    `(lp, kv_k, kv_v, x, cos, sin, write_slots, slot_matrix, positions)
    -> (x_out, kv_k, kv_v)`; callers slice `[:b*t]` and reshape the
    overlap leg's rows."""
    from dynamo_tpu.models import llama

    tp = mesh.shape["tp"]

    def prog(lp, kv_k, kv_v, x, cos, sin, ws, sm, pos):
        attn = llama.AttnSpec.gather(sm, page_size=page_size)
        if overlap:
            xs = scatter_rows(pad_rows(x.reshape(b * t, -1), tp), "tp")
            xs, kv_k, kv_v, _, _ = llama.layer_step(
                lp, cfg, xs, cos, sin, kv_k, kv_v, ws, attn, pos,
                tp_axis="tp", tp_overlap=True, bt_shape=(b, t), layer=0,
            )
        else:
            xs, kv_k, kv_v, _, _ = llama.layer_step(
                lp, cfg, x, cos, sin, kv_k, kv_v, ws, attn, pos,
                tp_axis="tp", layer=0,
            )
        return xs, kv_k, kv_v

    def run(lp, kv_k, kv_v, x, cos, sin, ws, sm, pos):
        return jax.shard_map(
            prog,
            mesh=mesh,
            in_specs=(
                _layer_in_specs([lp])[0], _P(None, "tp"), _P(None, "tp"),
                _P(), _P(), _P(), _P(), _P(), _P(),
            ),
            out_specs=(
                _P("tp", None) if overlap else _P(),
                _P(None, "tp"), _P(None, "tp"),
            ),
            check_vma=False,
        )(lp, kv_k, kv_v, x, cos, sin, ws, sm, pos)

    return jax.jit(run)


def tp_overlap_forward(
    params: dict,
    cfg,                        # ModelConfig
    tokens: jnp.ndarray,        # [B, T] int32
    positions: jnp.ndarray,     # [B, T] int32
    kv,                         # llama.KVCache (any tier: bf16 / int8 / int4 packed)
    write_slots: jnp.ndarray,   # [B*T] int32 flat slots (0 = trash)
    attn,                       # llama.AttnSpec (any non-ring shape), or a
    #                             raw [B, C] slot matrix (legacy gather form)
    mesh,
    page_size: int = 16,        # legacy raw-slot-matrix form only
    q_lens: jnp.ndarray | None = None,   # legacy form: ragged query lengths
    embeds: jnp.ndarray | None = None,
    embeds_mask: jnp.ndarray | None = None,
):
    """Drop-in for `llama.forward` on tp>1 tp-only meshes: the layer
    stack runs inside ONE `shard_map` over ('tp',) with the residual
    stream row-scattered and every collective a chunked ring
    (`llama.layer_step(..., tp_overlap=True)` per layer).

    Serves every AttnSpec shape except the sp ring: gather oracles,
    pallas prefill page-scatter + flash prefill, fused decode,
    ragged mixed/spec-verify — the kernels' own per-layer shard_maps
    COLLAPSE into this one. The shard body rebuilds the spec with
    `mesh=None` (kernels run directly on the shard's local heads) and
    `kv_tp=1` (each shard's scale-pool slab IS the kv_tp=1 layout over
    its local channels — ops/quant.kv_scale_subl is tp-blocked by
    construction); block tables, packed pools and scale channels ride as
    shard-local operands. Quantized KV pools (int8 dense, int32-packed,
    int4 nibble) pass through on their engine shardings; quantized
    weights ride `ring_ag_matmul`/`ring_rs_matmul`.

    Embedding lookup, rope tables, final norm and logits stay OUTSIDE
    the wrapper — the embed table is vocab-sharded and GSPMD already
    handles its gather; the wrapper covers exactly the per-layer segment
    where the serialized psums lived. Returns (hidden [B, T, D], kv)
    like `llama.forward`."""
    from dynamo_tpu.models import llama  # deferred: llama imports us lazily

    if not isinstance(attn, llama.AttnSpec):
        attn = llama.AttnSpec.gather(
            attn, page_size=page_size, lengths=q_lens
        )
    if cfg.num_experts:
        raise ValueError("tp_overlap manual executor covers dense models")
    if attn.ring:
        raise ValueError(
            "tp_overlap manual executor does not serve the sp ring "
            "prefill (the ring owns the token axis)"
        )

    tp = mesh.shape["tp"]
    b, t = tokens.shape
    quantized = kv.quantized

    x = params["embed"][tokens]
    if cfg.scale_embeddings:
        x = x * jnp.asarray(cfg.hidden_size ** 0.5, x.dtype)
    if embeds is not None:
        x = jnp.where(embeds_mask[..., None], embeds.astype(x.dtype), x)
    inv_freq = jnp.asarray(rope_inv_freq(cfg))
    cos, sin = rope_cos_sin(inv_freq, positions)

    def prog(layers, k_pools, v_pools, ks_pools, vs_pools,
             x, cos, sin, ws, attn_l, pos):
        r = b * t
        xf = pad_rows(x.reshape(r, cfg.hidden_size), tp)
        x_scat = scatter_rows(xf, "tp")
        # shard-local spec: same control arrays (replicated operands),
        # no kernel-level mesh (this shard_map already owns the layout),
        # kv_tp=1 scale-row layout (the local slab's own layout)
        local = llama.AttnSpec(
            slot_matrix=attn_l.slot_matrix,
            block_tables=attn_l.block_tables,
            lengths=attn_l.lengths,
            write_pos=attn_l.write_pos,
            write_tables=attn_l.write_tables,
            q_pos0=attn_l.q_pos0,
            page_size=attn_l.page_size,
            interpret=attn_l.interpret,
            mesh=None,
            kv_tp=1,
            prefix_cols=attn_l.prefix_cols,
            int4_groups=attn_l.int4_groups,
        )
        # lists, not tuples: the out_specs pytrees below are list-shaped
        new_k, new_v, new_ks, new_vs = [], [], [], []
        for i, lp in enumerate(layers):
            x_scat, kp, vp, ksp, vsp = llama.layer_step(
                lp, cfg, x_scat, cos, sin, k_pools[i], v_pools[i],
                ws, local, pos,
                kv_ks=ks_pools[i] if quantized else None,
                kv_vs=vs_pools[i] if quantized else None,
                tp_axis="tp", tp_overlap=True, bt_shape=(b, t), layer=i,
            )
            new_k.append(kp)
            new_v.append(vp)
            if quantized:
                new_ks.append(ksp)
                new_vs.append(vsp)
        xf = ring_all_gather(x_scat, "tp")[:r]
        return xf.reshape(b, t, cfg.hidden_size), new_k, new_v, new_ks, new_vs

    layers = params["layers"]
    nl = len(layers)
    kv_spec = [_P(None, "tp")] * nl
    scale_spec = [_P(None, "tp", None)] * nl if quantized else []
    hidden, new_k, new_v, new_ks, new_vs = jax.shard_map(
        prog,
        mesh=mesh,
        in_specs=(
            _layer_in_specs(layers), kv_spec, kv_spec,
            scale_spec, scale_spec,
            _P(), _P(), _P(), _P(),
            jax.tree.map(lambda _: _P(), attn), _P(),
        ),
        out_specs=(
            _P(), kv_spec, kv_spec, scale_spec, scale_spec,
        ),
        check_vma=False,
    )(
        layers, list(kv.k), list(kv.v),
        list(kv.ks) if quantized else [],
        list(kv.vs) if quantized else [],
        x, cos, sin, write_slots, attn, positions,
    )

    kv = llama.KVCache(
        k=tuple(new_k), v=tuple(new_v),
        ks=tuple(new_ks) if quantized else None,
        vs=tuple(new_vs) if quantized else None,
    )
    hidden = rms_norm(
        hidden, params["final_norm"], cfg.rms_norm_eps,
        weight_offset=cfg.norm_weight_offset,
    )
    return hidden, kv
