"""Device-path KV transfer between engines: the NIXL-RDMA equivalent.

The reference moves KV blocks between prefill and decode workers with
one-sided RDMA (reference: vLLM patch nixl.py, patch:1067 — agent
registration, base addresses, remote block reads) plus layout rearrange
for TP mismatches (patch:935). TPU-native, the same job is three steps
that never touch the host:

  1. jitted page gather on the source engine's mesh;
  2. `jax.device_put` onto the destination pool's sharding — XLA moves
     the buffers device-to-device (ICI within a slice, DCN across), and
     a TP-degree mismatch is just a different NamedSharding: the
     resharding collective IS the kv_rearrange;
  3. jitted page scatter into the destination pool (donated, in place).

This is the colocated/shared-backend fast path (both engines visible to
one process — separate pools for prefill/decode SLO isolation, or
different tp degrees on one slice). Engines in different OS processes
fall back to the host-staged msgpack plane in `llm/disagg` — single-
controller JAX cannot address another process's devices; a cross-process
device path is a multi-controller (SPMD) deployment property, not a
transfer-API property.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from dynamo_tpu.utils import faults


def _expand_slots(page_ids, page_size: int, n_tokens: int) -> np.ndarray:
    slots = (
        np.asarray(page_ids, np.int32)[:, None] * page_size
        + np.arange(page_size, dtype=np.int32)
    ).reshape(-1)
    return slots[:n_tokens]


def device_transfer_kv(
    src_engine,
    dst_engine,
    src_page_ids: list[int],
    dst_page_ids: list[int],
    n_tokens: int,
) -> None:
    """Move `n_tokens` positions of KV from src pages to dst pages with
    no host staging. Engines may differ in mesh/tp (pools resharded in
    step 2); page sizes must match (repack via llm.kv_rearrange first)."""
    # chaos hook (docs/robustness.md): 'fail' surfaces as FaultError to
    # the disagg caller, whose fallback is recomputing the prefill
    faults.fire("kv_transfer")
    if src_engine.page_size != dst_engine.page_size:
        raise ValueError(
            f"page-size mismatch {src_engine.page_size} != "
            f"{dst_engine.page_size}: repack_pages first"
        )
    for engine in (src_engine, dst_engine):
        engine._refuse_plane("device-path KV transfer")
    src_slots = jnp.asarray(
        _expand_slots(src_page_ids, src_engine.page_size, n_tokens)
    )
    dst_slots = jnp.asarray(
        _expand_slots(dst_page_ids, dst_engine.page_size, n_tokens)
    )

    if src_engine._kv_quant != dst_engine._kv_quant:
        # exact tier compare: bf16/int8/int4 are three distinct packed
        # representations; a cross-tier move would be a requantization
        # hop (quantized pools carry bytes quantized exactly once)
        from dynamo_tpu.llm.protocols.common import KvQuantMismatchError

        raise KvQuantMismatchError(
            f"device-path KV transfer needs matching kv_quantization on "
            f"both engines (src={src_engine._kv_quant!r}, "
            f"dst={dst_engine._kv_quant!r}; mixed bf16/quantized pairs go "
            f"through the host-staged plane, which converts on injection)"
        )
    if (
        src_engine._kv_quant == "int4"
        and src_engine._kv_int4_groups != dst_engine._kv_int4_groups
    ):
        from dynamo_tpu.llm.protocols.common import KvQuantMismatchError

        raise KvQuantMismatchError(
            f"device-path KV transfer needs matching kv_quantization "
            f"scale grouping (src int4 groups="
            f"{src_engine._kv_int4_groups}, dst="
            f"{dst_engine._kv_int4_groups})"
        )

    # 1. gather on the source mesh: [L, n, kw] stacked rows (+ [L, n, S]
    # scale rows on quantized engines — packed bytes over the wire: half
    # the bytes at int8, a quarter at int4)
    with src_engine._kv_lock:
        rows = src_engine._extract_fn(src_engine.kv, src_slots)

    # 2. reshard onto the destination pool's layout (device-to-device;
    # the tp-mismatch rearrange happens here as an XLA collective)
    dst_sh = dst_engine._kv_sharding
    row_sharding = jax.sharding.NamedSharding(
        dst_sh.mesh, jax.sharding.PartitionSpec(None, None, "tp")
    )
    rows = tuple(jax.device_put(r, row_sharding) for r in rows)

    # 3. scatter into the destination pool, in place
    with dst_engine._kv_lock:
        dst_engine.kv = dst_engine._inject_fn(dst_engine.kv, dst_slots, *rows)

    # custody churn stamps (engine/kv_ledger.py): pages moved out of the
    # source pool / into the destination pool this transfer. Page refs
    # are caller-managed on both ends, so this is telemetry, not a hold.
    for eng, event, pids in (
        (src_engine, "xfer_out", src_page_ids),
        (dst_engine, "xfer_in", dst_page_ids),
    ):
        ledger = getattr(eng, "kv_ledger", None)
        if ledger is not None:
            ledger.note_transfer(event, len(pids))
