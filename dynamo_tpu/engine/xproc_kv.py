"""Cross-PROCESS device-path KV transfer — the multi-controller NIXL
equivalent.

`engine/kv_transfer.py` covers the colocated case (both engines visible
to one process). Production xPyD on TPU pods is multi-controller SPMD:
one OS process per host, prefill workers on some hosts, decode workers
on others. The reference moves KV between those processes with
one-sided RDMA (reference: vLLM patch nixl.py, patch:1067 — agent
registration, base addresses, remote block reads). The TPU-native
answer is a jax.distributed group spanning the workers plus ONE jitted
collective over a transfer mesh:

  1. both processes join `jax.distributed` (parallel/multihost.py) and
     build the same ("host", "dev") transfer mesh — host coordinate 0 =
     the prefill worker's devices, 1 = the decode worker's;
  2. the payload becomes a global array [2, T, ...] sharded
     P("host", "dev"): the prefill worker contributes its KV rows as
     host-slice 0 (sliced onto its lane devices with intra-process
     device-to-device puts — the bytes never leave device memory), the
     decode worker contributes zeros;
  3. `transfer()` runs a jitted host-axis flip on BOTH processes
     (multi-controller lockstep): XLA lowers it to the cross-process
     device collective (ICI within a slice, DCN across), after which
     the decode worker's addressable shards hold the KV — still on its
     devices, ready for the engine's inject scatter (which is also
     where a TP-degree mismatch reshards: engine._inject_fn scatters
     into the destination pool's own sharding).

The CONTROL plane (which request, shapes, first token) stays on the hub
data plane exactly like the host-staged path — the reference's NIXL
does the same (metadata over the message bus, payload over RDMA). Only
the bulk KV bytes ride the device path.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from jax.sharding import NamedSharding, PartitionSpec as P


def transfer_mesh(prefill_devices, decode_devices):
    """Point-to-point ("host", "dev") transfer mesh from the two
    workers' device lists; lanes = min(len(a), len(b)) devices each."""
    n = min(len(prefill_devices), len(decode_devices))
    devs = np.empty((2, n), dtype=object)
    devs[0, :] = list(prefill_devices[:n])
    devs[1, :] = list(decode_devices[:n])
    return jax.sharding.Mesh(devs, ("host", "dev"))


class XProcKvBridge:
    """Device-path bulk-KV lane between two processes of one
    jax.distributed group.

    Both processes construct the bridge with the same transfer mesh and
    call `transfer` LOCKSTEP with the same shapes/dtypes (control-plane
    metadata) — multi-controller SPMD discipline, the same way every
    collective in a multi-host serving step runs.
    """

    def __init__(self, mesh, role: str, ledger=None):
        if tuple(mesh.axis_names) != ("host", "dev"):
            raise ValueError("transfer mesh must have ('host', 'dev') axes")
        if mesh.shape["host"] != 2:
            raise ValueError("bridge is point-to-point: host axis size 2")
        if role not in ("prefill", "decode"):
            raise ValueError(f"role {role!r}: expected 'prefill' or 'decode'")
        self.mesh = mesh
        self.role = role
        # optional KvLedger (engine/kv_ledger.py): each transfer_kv
        # stamps xfer_out/xfer_in churn on this process's ledger
        self.ledger = ledger
        self.lanes = mesh.shape["dev"]
        self._row = 0 if role == "prefill" else 1
        self._my_devices = list(mesh.devices[self._row])
        # payload [2, T, ...]: host axis selects the worker, T splits
        # over the transfer lanes
        self._sharding = NamedSharding(mesh, P("host", "dev"))

        # ONE-WAY ppermute host 0 -> 1: a host-axis flip would be
        # bidirectional, shipping the decode side's zero slice back over
        # the same (slowest) link and doubling wire bytes. Built once;
        # jax caches compilations per payload shape family.
        def oneway(x):
            return jax.lax.ppermute(x, "host", [(0, 1)])

        self._xfer = jax.jit(
            jax.shard_map(
                oneway,
                mesh=mesh,
                in_specs=P("host", "dev"),
                out_specs=P("host", "dev"),
                check_vma=False,
            )
        )

    def transfer(self, payload, shape: tuple, dtype) -> Optional[jax.Array]:
        """Move one [T, ...] array prefill -> decode on the device path.

        The prefill worker passes `payload` (device or host array of
        shape `shape`); the decode worker passes None. T pads up to a
        lane multiple internally. Returns the received device array on
        the decode side, None on the prefill side.
        """
        t = shape[0]
        n = self.lanes
        t_pad = -(-t // n) * n
        if payload is None:
            local = jnp.zeros((1, t_pad, *shape[1:]), dtype)
        else:
            local = jnp.asarray(payload, dtype)
            if local.shape != tuple(shape):
                raise ValueError(f"payload {local.shape} != declared {shape}")
            if t_pad != t:
                pad = [(0, t_pad - t)] + [(0, 0)] * (local.ndim - 1)
                local = jnp.pad(local, pad)
            local = local[None]
        # slice this worker's host-slice onto its lane devices:
        # intra-process device-to-device, no host staging
        chunk = t_pad // n
        shards = [
            jax.device_put(local[:, j * chunk:(j + 1) * chunk], d)
            for j, d in enumerate(self._my_devices)
        ]
        garr = jax.make_array_from_single_device_arrays(
            (2, t_pad, *shape[1:]),
            self._sharding,
            shards,
        )
        out = self._xfer(garr)
        if self.role == "prefill":
            return None
        # reassemble the local view from this worker's shards (still on
        # its devices; the engine's inject scatter reshards from here)
        mine = sorted(
            (s for s in out.addressable_shards),
            key=lambda s: s.index[1].start or 0,
        )
        assert mine, "decode worker received no addressable KV shard"
        # gather the lane shards onto one local device (intra-process
        # device-to-device; the engine's inject scatter reshards next)
        home = self._my_devices[0]
        got = jnp.concatenate(
            [jax.device_put(s.data[0], home) for s in mine], axis=0
        )
        return got[:t]

    def transfer_kv(
        self,
        k,
        v,
        shape: tuple,
        dtype,
        ks=None,
        vs=None,
        scale_shape: Optional[tuple] = None,
    ):
        """K + V (+ int8-KV scale arrays), PACKED: k/v ride one lockstep
        exchange (concatenated on the lane dim), scales another — two
        collective dispatches instead of four. Arrays are
        [T, ...]-leading. Returns (k, v, ks, vs) on the decode side
        (scales None when absent); (None, None, None, None) on the
        prefill side."""
        t = shape[0]
        packed = (
            jnp.concatenate([jnp.asarray(k), jnp.asarray(v)], axis=0)
            if k is not None else None
        )
        r = self.transfer(packed, (2 * t, *shape[1:]), dtype)
        rk, rv = (r[:t], r[t:]) if r is not None else (None, None)
        rks = rvs = None
        if scale_shape is not None:
            spacked = (
                jnp.concatenate([jnp.asarray(ks), jnp.asarray(vs)], axis=0)
                if ks is not None else None
            )
            rs = self.transfer(
                spacked, (2 * t, *scale_shape[1:]), np.float32
            )
            if rs is not None:
                rks, rvs = rs[:t], rs[t:]
        if self.ledger is not None:
            self.ledger.note_transfer(
                "xfer_out" if self.role == "prefill" else "xfer_in", t
            )
        return rk, rv, rks, rvs
