"""TP comm/compute overlap bench: serialized psums vs the ring executor.

Subprocess behind bench.py's `tp_overlap` BENCH_OUT section
(BENCH_TP_OVERLAP=1): bench.py initializes jax against the real
backend long before the section runs, and this bench needs its OWN
8-virtual-device CPU mesh — so it runs as a child process that forces
the platform before the jax import and prints ONE JSON line on stdout.

What it measures (parallel/tp_overlap.py, docs/parallelism.md):

- **Per-layer step wall, serialized vs overlapped** — the same
  `layer_step` under `single_layer_executor` with the two psums intact
  vs decomposed into ring reduce-scatter + matmul-fused all-gather
  (warmup + best-of-N). On virtual CPU devices the rings run
  sequentially, so this wall is a scheduling-shape datum, not a
  speedup claim — the TPU latency-hiding scheduler is what cashes the
  overlap in; the invariant CI gates on is the byte ledger.
- **Measured collective bytes** — `record_collectives()` armed around
  each leg's trace: exposed bytes (standalone collectives on the
  critical path) must read EXACTLY 0.5x the serialized leg's, total
  wire bytes must be conserved (RS+AG re-schedules traffic, it does
  not remove any), and both must match `collective_bytes_per_layer`'s
  closed form.
- **Greedy byte-identity** — `tp_overlap_forward` argmax tokens vs the
  tp=1 `llama.forward` (the FP reduction-order invariant the serving
  path relies on).
- **Pallas + packed-KV legs** (`pallas_legs` in the JSON): the same
  invariants on the PRODUCTION serving combination — pallas prefill
  kernels (interpret mode on CPU) over int32-PACKED int8 and int4
  pools, whole-forward through `tp_overlap_forward` vs (a) tp=1 with
  the same kernels and (b) the GSPMD-fallback leg (per-layer kernel
  shard_maps + GSPMD-inserted psums, what `tp_overlap=False` serves).
  Gated: greedy byte-identity vs tp=1, the per-layer-segment exposed
  bytes exactly 0.5x the serialized closed form, total wire bytes
  conserved, and per-layer wall bounded vs the fallback leg (see
  PALLAS_WALL_SLACK — virtual CPU devices serialize the ring chunk
  ops a real rig overlaps, so the CPU gate bounds the known
  serialization cost rather than asserting a speedup).

Run:  python scripts/tp_overlap_bench.py        (~4 min on CPU)
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from dynamo_tpu.models import config as cfgmod, llama  # noqa: E402
from dynamo_tpu.parallel import mesh as meshmod  # noqa: E402
from dynamo_tpu.parallel import tp_overlap as ov  # noqa: E402

TP = 8
B = int(os.environ.get("BENCH_TP_OVERLAP_B", "4"))
T = int(os.environ.get("BENCH_TP_OVERLAP_T", "16"))
REPS = int(os.environ.get("BENCH_TP_OVERLAP_REPS", "30"))

# tiny widened to 8 query + 8 kv heads so the head shards survive tp=8
# (the same shape the multichip smoke serves)
CFG = cfgmod.get_config("tiny").with_(
    dtype="float32", num_layers=2, num_heads=8, num_kv_heads=8
)


def _inputs(b, t, page=8):
    rng = np.random.RandomState(0)
    tokens = rng.randint(1, CFG.vocab_size, (b, t)).astype(np.int32)
    positions = np.tile(np.arange(t, dtype=np.int32), (b, 1))
    wslots = np.stack(
        [np.arange(page * (1 + 8 * i), page * (1 + 8 * i) + t) for i in range(b)]
    ).astype(np.int32)
    return tokens, positions, wslots, wslots.copy()


# CPU-noise slack on the pallas-leg wall gate. Both legs run the same 8
# sequential interpret-kernel shard bodies, but the overlap executor's
# decomposed rings issue ~n chunked ppermute+matmul ops where GSPMD
# fuses one psum — traffic a real rig hides under the MXU, but on
# virtual CPU devices every chunk op is serialized wall time (measured
# ~2.8x on an idle 8-core host). The default slack bounds that known
# serialization cost so a genuine compute regression in the executor
# (say, re-quantizing per ring chunk) still reads red; on the real rig
# set BENCH_TP_OVERLAP_WALL_SLACK=1.0 to assert the actual "no worse
# than fallback" property the overlap claims.
WALL_SLACK = float(os.environ.get("BENCH_TP_OVERLAP_WALL_SLACK", "1.5"))
PALLAS_WALL_SLACK = float(
    os.environ.get("BENCH_TP_OVERLAP_PALLAS_WALL_SLACK", "4.0")
)


def _pallas_leg(tier: str, params, mesh) -> dict:
    """One pallas+packed-KV leg: interpret-mode page-scatter write +
    flash prefill over int32-packed `tier` pools, tp=8 overlap executor
    vs tp=1 and vs the GSPMD fallback (per-layer kernel shard_maps)."""
    page = 8
    tokens, positions, wslots, _ = _inputs(B, T, page=page)
    ppseq = T // page
    btables = np.stack(
        [np.arange(1 + 8 * i, 1 + 8 * i + ppseq) for i in range(B)]
    ).astype(np.int32)
    wtables = btables.reshape(-1)
    smat = (
        btables[:, :, None] * page + np.arange(page, dtype=np.int32)
    ).reshape(B, -1)
    groups = 1 if tier == "int4" else 0

    def spec(kv_tp, with_mesh):
        return llama.AttnSpec.gather(
            jnp.asarray(smat), write_tables=jnp.asarray(wtables),
            page_size=page, interpret=True,
            mesh=mesh if with_mesh else None,
            block_tables=jnp.asarray(btables),
            q_pos0=jnp.zeros(B, jnp.int32),
            lengths=jnp.full(B, T, jnp.int32),
            kv_tp=kv_tp, int4_groups=groups,
        )

    def fresh_kv(tp):
        return llama.init_kv_cache(
            CFG, 512, kv_quant=tier, page_size=page, tp=tp, packed=True
        )

    tok_j, pos_j = jnp.asarray(tokens), jnp.asarray(positions)
    ws_j = jnp.asarray(wslots.reshape(-1))

    # tp=1 reference: same interpret kernels, mesh-free spec
    ref_hidden, _ = llama.forward(
        params, CFG, tok_j, pos_j, fresh_kv(1), ws_j, spec(1, False)
    )
    ref_tok = np.asarray(
        jnp.argmax(llama.logits(params, CFG, ref_hidden[:, -1]), -1)
    )

    # overlap executor leg — ledger armed around the trace
    spec8 = spec(TP, False)
    ov_fn = jax.jit(
        lambda p, kv: ov.tp_overlap_forward(
            p, CFG, tok_j, pos_j, kv, ws_j, spec8, mesh
        )
    )
    kv8 = fresh_kv(TP)
    with jax.set_mesh(mesh):
        with ov.record_collectives() as led:
            hidden = jax.block_until_ready(ov_fn(params, kv8)[0])
        ov_walls = []
        for _ in range(REPS):
            t0 = time.perf_counter()
            jax.block_until_ready(ov_fn(params, kv8)[0])
            ov_walls.append(time.perf_counter() - t0)
    ov_tok = np.asarray(
        jnp.argmax(llama.logits(params, CFG, hidden[:, -1]), -1)
    )

    # GSPMD fallback leg: sharded params, per-layer kernel shard_maps,
    # XLA-inserted psums (what tp_overlap=False serves on this shape)
    sh_params = meshmod.shard_params(params, CFG, mesh)
    kv_sh = meshmod.kv_cache_sharding(mesh)
    kv8_fb = jax.tree.map(lambda a: jax.device_put(a, kv_sh), fresh_kv(TP))
    fb_spec = spec(TP, True)
    fb_fn = jax.jit(
        lambda p, kv: llama.forward(
            p, CFG, tok_j, pos_j, kv, ws_j, fb_spec
        )
    )
    with jax.set_mesh(mesh):
        fb_hidden = jax.block_until_ready(fb_fn(sh_params, kv8_fb)[0])
        fb_walls = []
        for _ in range(REPS):
            t0 = time.perf_counter()
            jax.block_until_ready(fb_fn(sh_params, kv8_fb)[0])
            fb_walls.append(time.perf_counter() - t0)
    fb_tok = np.asarray(
        jnp.argmax(llama.logits(params, CFG, fb_hidden[:, -1]), -1)
    )

    # byte ledger: per-layer segment exposed = exactly half the
    # serialized closed form; the one standalone final all-gather
    # (residual reassembly after the last layer) rides on top
    nl = CFG.num_layers
    rs = (TP - 1) * B * T * CFG.hidden_size * 4 // TP
    seg_exposed = led.exposed - rs
    serialized = nl * ov.collective_bytes_per_layer(
        CFG.hidden_size, B * T, TP, itemsize=4, overlap=False
    )
    assert seg_exposed * 2 == serialized, (tier, led.exposed, serialized)
    assert led.total - rs == serialized, (tier, led.total, serialized)

    identical = bool(np.array_equal(ref_tok, ov_tok))
    assert identical, (tier, ref_tok, ov_tok)
    assert np.array_equal(ref_tok, fb_tok), (tier, ref_tok, fb_tok)

    ov_layer = min(ov_walls) / nl
    fb_layer = min(fb_walls) / nl
    assert ov_layer <= fb_layer * PALLAS_WALL_SLACK, (
        tier, ov_layer, fb_layer
    )

    return {
        "kv_tier": tier,
        "backend": "pallas-interpret",
        "kv_packed": True,
        "layer_step_wall_s": round(ov_layer, 6),
        "fallback_layer_step_wall_s": round(fb_layer, 6),
        "exposed_bytes": led.exposed,
        "overlapped_bytes": led.overlapped,
        "total_bytes": led.total,
        "final_gather_bytes": rs,
        "exposed_ratio": seg_exposed / serialized,
        "total_bytes_conserved": True,
        "greedy_byte_identical_vs_tp1": identical,
        "wall_gate_slack": PALLAS_WALL_SLACK,
    }


def run() -> dict:
    assert jax.device_count() == 8, jax.device_count()
    mesh = meshmod.build_mesh(meshmod.MeshConfig(tp=TP))
    tokens, positions, wslots, smat = _inputs(B, T)
    params = llama.init_params(CFG, jax.random.PRNGKey(0), dtype=jnp.float32)
    x = np.asarray(params["embed"])[tokens].astype(np.float32)
    from dynamo_tpu.ops.rope import rope_cos_sin, rope_inv_freq

    cos, sin = rope_cos_sin(
        jnp.asarray(rope_inv_freq(CFG)), jnp.asarray(positions)
    )
    kv = llama.init_kv_cache(CFG, 512, dtype=jnp.float32)
    lp = params["layers"][0]
    args = (
        lp, kv.k[0], kv.v[0], jnp.asarray(x), cos, sin,
        jnp.asarray(wslots.reshape(-1)), jnp.asarray(smat),
        jnp.asarray(positions),
    )

    legs = {}
    for name, overlap in (("serialized", False), ("overlap", True)):
        step = ov.single_layer_executor(
            CFG, mesh, B, T, page_size=8, overlap=overlap
        )
        # arm the ledger around the TRACE (first call compiles): the
        # executor returns the overlap leg still scattered, so the
        # ledger sees exactly one layer's collectives — no amortization
        with ov.record_collectives() as led:
            jax.block_until_ready(step(*args))
        walls = []
        for _ in range(REPS):
            t0 = time.perf_counter()
            jax.block_until_ready(step(*args))
            walls.append(time.perf_counter() - t0)
        legs[name] = {
            "layer_step_wall_s": round(min(walls), 6),
            "exposed_bytes": led.exposed,
            "overlapped_bytes": led.overlapped,
            "total_bytes": led.total,
        }

    base, over = legs["serialized"], legs["overlap"]
    ratio = over["exposed_bytes"] / base["exposed_bytes"]
    # the tentpole invariant: EXACTLY half the exposed bytes, total
    # wire bytes conserved, closed form agreeing with the measurement
    assert over["exposed_bytes"] * 2 == base["exposed_bytes"], legs
    assert over["total_bytes"] == base["total_bytes"], legs
    assert base["overlapped_bytes"] == 0, legs
    itemsize = 4
    for leg, flag in (("serialized", False), ("overlap", True)):
        want = ov.collective_bytes_per_layer(
            CFG.hidden_size, B * T, TP, itemsize=itemsize, overlap=flag
        )
        assert legs[leg]["exposed_bytes"] == want, (leg, want, legs[leg])

    # greedy byte-identity vs tp=1 (the serving property the engine
    # relies on; scripts/multichip_smoke.py gates the full engine path)
    kv1 = llama.init_kv_cache(CFG, 512, dtype=jnp.float32)
    ref_hidden, _ = llama.forward(
        params, CFG, jnp.asarray(tokens), jnp.asarray(positions), kv1,
        jnp.asarray(wslots.reshape(-1)), jnp.asarray(smat),
    )
    kv8 = llama.init_kv_cache(CFG, 512, dtype=jnp.float32)
    with jax.set_mesh(mesh):
        ov_hidden, _ = ov.tp_overlap_forward(
            params, CFG, jnp.asarray(tokens), jnp.asarray(positions), kv8,
            jnp.asarray(wslots.reshape(-1)), jnp.asarray(smat), mesh,
            page_size=8,
        )
    ref_tok = np.asarray(
        jnp.argmax(llama.logits(params, CFG, ref_hidden[:, -1]), -1)
    )
    ov_tok = np.asarray(
        jnp.argmax(llama.logits(params, CFG, ov_hidden[:, -1]), -1)
    )
    identical = bool(np.array_equal(ref_tok, ov_tok))
    assert identical, (ref_tok, ov_tok)

    # the production serving combination: pallas kernels + packed
    # quantized pools through the same executor, both KV tiers
    pallas_legs = {
        tier: _pallas_leg(tier, params, mesh) for tier in ("int8", "int4")
    }

    return {
        "devices": 8,
        "tp": TP,
        "model": CFG.name,
        "rows": B * T,
        "hidden_size": CFG.hidden_size,
        "dtype_itemsize": itemsize,
        "reps": REPS,
        "legs": legs,
        "exposed_ratio": ratio,            # the gated 0.5x invariant
        "total_bytes_conserved": True,
        "layer_step_overlap_speedup": round(
            base["layer_step_wall_s"] / over["layer_step_wall_s"], 4
        ),
        "greedy_byte_identical_vs_tp1": identical,
        "pallas_legs": pallas_legs,
        "note": (
            "CPU virtual devices run the rings sequentially: the wall "
            "delta is scheduling shape, not the TPU speedup; the gated "
            "invariants are the byte ledger and greedy byte-identity"
        ),
    }


if __name__ == "__main__":
    out = run()
    print(
        "tp_overlap: exposed_ratio={} wall serialized={}s overlap={}s "
        "identical={}".format(
            out["exposed_ratio"],
            out["legs"]["serialized"]["layer_step_wall_s"],
            out["legs"]["overlap"]["layer_step_wall_s"],
            out["greedy_byte_identical_vs_tp1"],
        ),
        file=sys.stderr,
    )
    for tier, leg in out["pallas_legs"].items():
        print(
            "tp_overlap pallas+{}: exposed_ratio={} wall overlap={}s "
            "fallback={}s identical={}".format(
                tier, leg["exposed_ratio"], leg["layer_step_wall_s"],
                leg["fallback_layer_step_wall_s"],
                leg["greedy_byte_identical_vs_tp1"],
            ),
            file=sys.stderr,
        )
    print(json.dumps(out))
