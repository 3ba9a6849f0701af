"""8-device multichip smoke: the sharded-path hang guard.

an early multichip run hit rc=124 (timeout) and shipped silently because no
pre-merge gate exercised the sharded path (ROADMAP open item 1). This
script is that gate: it forces 8 virtual CPU devices, serves greedy
requests through a tp=8 engine with the step pipeline ON (the r05
suspect), and byte-compares against a single-device engine of the same
config — a sharded-path hang reads as the CI job's own timeout (red),
and a sharded-path divergence reads as the mismatch assert (red).

Four legs: gather tp=8 vs tp=1, the gather tp_overlap executor (cold +
warm waves), and the pallas+int8 packed-KV tp_overlap executor (cold +
warm waves, executor-attribution counters proving no GSPMD fallback) —
each byte-compared against its own tp=1 reference.

Run:  python scripts/multichip_smoke.py        (~2-6 min on CPU)
CI:   pre-merge.yml `multichip-smoke` job, wrapped in `timeout` so a
      hang can never eat the runner.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import asyncio  # noqa: E402

import jax  # noqa: E402

from dynamo_tpu.engine import EngineConfig, JaxEngine  # noqa: E402
from dynamo_tpu.llm.protocols.common import (  # noqa: E402
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)
from dynamo_tpu.models import config as cfgmod  # noqa: E402
from dynamo_tpu.parallel.mesh import MeshConfig  # noqa: E402
from dynamo_tpu.runtime.pipeline.context import Context  # noqa: E402

# tiny widened to 8 kv heads so tp=8 actually shards the attention
CFG = cfgmod.get_config("tiny").with_(num_heads=8, num_kv_heads=8)

PROMPTS = (
    [5, 17, 42, 9, 88, 3],
    [11, 3, 7, 29, 31],
    [2, 44, 8, 19, 23, 61, 12],
)
MAX_TOKENS = 16

# live engines, so the timeout path can still read their phase stats —
# that hang left a bare rc=124 with nothing to bisect on
_ENGINES: list = []


def make_engine(tp: int, tp_overlap: bool = False) -> JaxEngine:
    engine = JaxEngine(
        EngineConfig(
            model=CFG,
            dtype="float32",
            mesh=MeshConfig(tp=tp),
            page_size=8,
            num_pages=96,
            max_batch_size=4,
            max_model_len=128,
            prefill_chunk=32,
            # the r05 suspect paths stay ON: pipelined mixed steps over
            # the sharded mesh are exactly what a smoke must cover
            mixed_batching=True,
            step_pipeline=True,
            tp_overlap=tp_overlap,
            seed=0,
        )
    )
    _ENGINES.append(engine)
    return engine


def make_pallas_engine(tp: int, tp_overlap: bool = False) -> JaxEngine:
    """The production serving combination: pallas kernels (interpret on
    CPU) + int8 KV in int32-PACKED pools + mixed batching + the step
    pipeline. page_size=128 is the pallas+quantized floor (scale-page
    tokens live in lanes), so each sequence is one page."""
    engine = JaxEngine(
        EngineConfig(
            model=CFG,
            dtype="float32",
            mesh=MeshConfig(tp=tp),
            attn_backend="pallas",
            kv_quantization="int8",
            page_size=128,
            num_pages=8,
            max_batch_size=4,
            max_model_len=128,
            prefill_chunk=128,
            mixed_batching=True,
            step_pipeline=True,
            tp_overlap=tp_overlap,
            seed=0,
        )
    )
    _ENGINES.append(engine)
    return engine


def dump_timeout_artifact() -> str | None:
    """rc=124 evidence: trace ring + every engine's phase stats/metrics
    via the shared watchdog artifact writer (utils/artifacts.py)."""
    from dynamo_tpu.utils import artifacts, tracing

    payload = {
        "op": "multichip_smoke.timeout",
        "engines": [
            {
                "mesh_tp": e.config.mesh.tp,
                "phase_stats": e.phase_stats,
                "metrics": _safe_metrics(e),
            }
            for e in _ENGINES
        ],
        "trace": tracing.export(),
    }
    return artifacts.write_crash_artifact("multichip_smoke", payload)


def _safe_metrics(engine) -> dict:
    try:
        return engine.metrics()
    except Exception:  # noqa: BLE001 — artifact beats perfection here
        return {}


async def serve(engine) -> list[list[int]]:
    async def one(prompt):
        pre = PreprocessedRequest(
            token_ids=list(prompt),
            stop_conditions=StopConditions(max_tokens=MAX_TOKENS),
            sampling_options=SamplingOptions(greedy=True),
        )
        frames = [f async for f in await engine.generate(Context(pre.to_dict()))]
        assert frames[-1].get("finish_reason") == "length", frames[-1]
        return [t for f in frames for t in f.get("token_ids") or []]

    return list(await asyncio.gather(*(one(p) for p in PROMPTS)))


async def main() -> None:
    n_dev = jax.device_count()
    assert n_dev == 8, f"expected 8 virtual devices, got {n_dev}"

    ref_engine = make_engine(tp=1)
    want = await serve(ref_engine)
    await ref_engine.close()

    tp8 = make_engine(tp=8)
    got = await serve(tp8)
    # a second wave rides the prefix cache + warm compiled families —
    # the steady-state sharded path, not just the compile path
    got2 = await serve(tp8)
    await tp8.close()

    assert got == want, f"tp=8 diverged from tp=1:\n{got}\nvs\n{want}"
    assert got2 == want, f"tp=8 second wave diverged:\n{got2}\nvs\n{want}"

    # overlap leg: the latency-hiding manual-TP executor (ring
    # reduce-scatter residual stream, parallel/tp_overlap.py) must be
    # byte-identical too — a ring-scheduling regression reads red here
    ov8 = make_engine(tp=8, tp_overlap=True)
    assert ov8._tp_overlap_manual, "tp_overlap engine fell back to GSPMD"
    got_ov = await serve(ov8)
    got_ov2 = await serve(ov8)  # warm wave: steady-state ring path
    stats = ov8.phase_stats
    await ov8.close()
    assert got_ov == want, (
        f"tp=8 tp_overlap diverged from tp=1:\n{got_ov}\nvs\n{want}"
    )
    assert got_ov2 == want, (
        f"tp=8 tp_overlap second wave diverged:\n{got_ov2}\nvs\n{want}"
    )
    moved = sum(
        stats[k] for k in stats if k.endswith("_collective_bytes")
    )
    assert moved > 0, f"overlap engine recorded no collective bytes: {stats}"

    # pallas + packed int8 KV leg: the production backend combination
    # through the SAME overlap executor (the kernels' per-layer
    # shard_maps collapse into its single one) — mixed+pipeline stay on,
    # cold and warm waves, byte-compared against a tp=1 engine of the
    # same pallas+int8 config
    pal1 = make_pallas_engine(tp=1)
    want_pal = await serve(pal1)
    await pal1.close()

    pal8 = make_pallas_engine(tp=8, tp_overlap=True)
    assert pal8._tp_overlap_manual, (
        "pallas tp_overlap engine fell back to GSPMD: "
        f"{pal8.tp_overlap_refusal_reason!r}"
    )
    assert pal8._attn_pallas and pal8._kv_packed, "leg lost the pallas+packed path"
    got_pal = await serve(pal8)
    got_pal2 = await serve(pal8)  # warm wave
    pal_metrics = pal8.metrics()
    await pal8.close()
    assert got_pal == want_pal, (
        f"pallas+int8 tp=8 tp_overlap diverged from tp=1:\n{got_pal}\nvs\n{want_pal}"
    )
    assert got_pal2 == want_pal, (
        f"pallas+int8 tp=8 second wave diverged:\n{got_pal2}\nvs\n{want_pal}"
    )
    # executor attribution: every tp-collective dispatch went through the
    # overlap executor, none fell back to GSPMD
    served = pal_metrics["tp_overlap_dispatches"]
    fell_back = pal_metrics["gspmd_fallback_dispatches"]
    assert served > 0, f"no dispatch attributed to the overlap executor: {pal_metrics}"
    assert fell_back == 0, (
        f"{fell_back} dispatches fell back to GSPMD on the overlap engine"
    )

    print(
        f"multichip smoke ok: {n_dev} devices, tp=8, "
        f"{len(PROMPTS)} streams x {MAX_TOKENS} tokens byte-identical "
        "to tp=1 (mixed+pipeline on; overlap leg byte-identical, "
        f"{moved} exposed collective bytes attributed; pallas+int8 "
        f"packed-KV overlap leg byte-identical, {served} dispatches "
        "served by the executor, 0 GSPMD fallbacks)"
    )


if __name__ == "__main__":
    # arm the span recorder for the whole run: on the happy path it
    # costs a ring buffer; on the timeout path it is the step timeline
    # the crash artifact preserves
    from dynamo_tpu.utils import tracing as _tracing

    _tracing.enable()
    _tracing.set_process("multichip-smoke")
    try:
        asyncio.run(asyncio.wait_for(main(), timeout=840))
    except asyncio.TimeoutError:
        path = dump_timeout_artifact()
        print(
            "multichip smoke TIMED OUT (sharded-path hang); "
            f"crash artifact: {path or 'write failed'}",
            file=sys.stderr,
        )
        sys.exit(124)
