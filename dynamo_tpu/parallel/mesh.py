"""Device mesh construction and model shardings.

Replaces the reference's `--tensor-parallel-size` passthrough + NCCL
(reference: launch/dynamo-run/src/flags.rs:67, lib/engines/sglang/src/lib.rs:64-73)
with native mesh-axis shardings. One mesh carries every axis:

    axes (dp, ep, sp, tp)  —  tp innermost so TP collectives ride the
                                  fastest ICI links; dp outermost so replicas
                                  can span hosts/DCN.

- **tp**: megatron-style column/row parallel linear layers; KV heads sharded
  so the paged-KV path needs no collectives.
- **sp**: sequence (context) parallel — long-prefill activations sharded
  over the token axis (ring/all-gather attention lives in ops/).
- **ep**: expert parallel axis for MoE models (axis exists on every mesh so
  graphs are portable; size 1 for dense models).
- **dp**: engine-internal data parallel over decode slots / prefill batch.

GSPMD does the rest: we annotate params + KV + a few activations and XLA
inserts all-gathers/reduce-scatters/psums over ICI.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dynamo_tpu.models.config import MAMBA, ModelConfig

AXES = ("dp", "ep", "sp", "tp")


@dataclass(frozen=True)
class MeshConfig:
    tp: int = 1
    sp: int = 1
    ep: int = 1
    dp: int = 1

    @property
    def num_devices(self) -> int:
        return self.tp * self.sp * self.ep * self.dp

    @classmethod
    def for_devices(cls, n: int, tp: Optional[int] = None) -> "MeshConfig":
        """Default layout: all-TP up to 8 (one v5e host), dp beyond."""
        if tp is None:
            tp = math.gcd(n, 8)
        if n % tp:
            raise ValueError(f"tp={tp} does not divide {n} devices")
        return cls(tp=tp, dp=n // tp)


def build_mesh(cfg: MeshConfig, devices=None) -> Mesh:
    devices = list(devices if devices is not None else jax.devices())
    if len(devices) < cfg.num_devices:
        raise ValueError(
            f"mesh {cfg} needs {cfg.num_devices} devices, have {len(devices)}"
        )
    arr = np.asarray(devices[: cfg.num_devices]).reshape(
        cfg.dp, cfg.ep, cfg.sp, cfg.tp
    )
    return Mesh(arr, AXES)


def validate_model_mesh(cfg: ModelConfig, mc: MeshConfig) -> None:
    """Fail fast with a clear message instead of an opaque XLA sharding
    error when head counts don't divide the tp axis (e.g. qwen2.5-0.5b has
    2 KV heads — tp=8 can never work)."""
    if cfg.hc_mult > 1 and mc.num_devices > 1:
        raise ValueError(
            f"model '{cfg.name}' carries a residual of {cfg.hc_mult} "
            "streams a token, which is served on one device: no mesh axis "
            f"(tp={mc.tp} sp={mc.sp} ep={mc.ep} dp={mc.dp}) has "
            "a rule for the streams or for the boundary's maps"
        )
    if cfg.num_kv_heads % mc.tp:
        raise ValueError(
            f"model '{cfg.name}' has num_kv_heads={cfg.num_kv_heads}, which "
            f"is not divisible by tp={mc.tp}; choose tp from the divisors "
            f"of {cfg.num_kv_heads}"
        )
    if cfg.num_heads % mc.tp:
        raise ValueError(
            f"model '{cfg.name}' has num_heads={cfg.num_heads}, which is "
            f"not divisible by tp={mc.tp}"
        )
    # the row-parallel projections shard their INPUT dim over tp (wo:
    # [q_size, hidden] -> psum; w_down: [intermediate, hidden]); a
    # non-divisible width would mis-shard them silently under GSPMD
    # (uneven padding shards) and break the manual-TP ring executor's
    # even row blocks outright
    if cfg.hidden_size % mc.tp:
        raise ValueError(
            f"model '{cfg.name}' has hidden_size={cfg.hidden_size}, which "
            f"is not divisible by tp={mc.tp}; choose tp from the divisors "
            f"of {cfg.hidden_size}"
        )
    if cfg.intermediate_size % mc.tp:
        raise ValueError(
            f"model '{cfg.name}' has intermediate_size="
            f"{cfg.intermediate_size}, which is not divisible by "
            f"tp={mc.tp}; choose tp from the divisors of "
            f"{cfg.intermediate_size}"
        )
    if mc.ep > 1 and cfg.num_experts % mc.ep:
        raise ValueError(
            f"model '{cfg.name}' has num_experts={cfg.num_experts}, which "
            f"is not divisible by ep={mc.ep}"
        )


def param_shardings(cfg: ModelConfig, mesh: Mesh) -> dict:
    """NamedSharding pytree matching `llama.init_params` structure.

    Column-parallel (out-dim over tp): wq/wk/wv, w_gate/w_up;
    row-parallel (in-dim over tp): wo, w_down; vocab over tp for
    embed/lm_head; norms replicated.
    """

    def ns(*spec):
        return NamedSharding(mesh, P(*spec))

    def layer(i: int) -> dict:
        if cfg.layer_kind(i) == MAMBA:
            # a Mamba-2 mixer is served on one device (the engine
            # refuses a larger mesh): every leaf whole
            lp = {k: ns() for k in (
                "attn_norm", "w_in", "conv_w", "dt_bias", "A_log", "D",
                "ssm_norm", "w_out", "mlp_norm",
            )}
            if cfg.mamba_conv_bias:
                lp["conv_b"] = ns()
        elif cfg.latent:
            # latent attention is served on one device (the engine
            # refuses a larger mesh): every leaf whole
            lp = {k: ns() for k in (
                "attn_norm",
                *(("w_qa", "q_norm", "w_qb") if cfg.q_lora_rank
                  else ("wq",)),
                "w_kva", "kv_norm", "w_kvb", "wo", "mlp_norm",
            )}
        else:
            lp = {
                "attn_norm": ns(),
                "wq": ns(None, "tp"),
                "wk": ns(None, "tp"),
                "wv": ns(None, "tp"),
                "wo": ns("tp", None),
                "mlp_norm": ns(),
            }
            if cfg.qk_norm:
                lp["q_norm"] = lp["k_norm"] = ns()
        if cfg.is_moe_layer(i):
            # sparse MoE: experts over ep, each expert's FFN column/row
            # parallel over tp (models/moe.py; GSPMD inserts the
            # dispatch/combine collectives over ep)
            lp.update({
                "router": ns(),
                "we_gate": ns("ep", None, "tp"),
                "we_up": ns("ep", None, "tp"),
                "we_down": ns("ep", "tp", None),
            })
            if cfg.router_bias:
                lp["router_bias"] = ns()
            if cfg.num_shared_experts:
                lp.update({
                    "ws_gate": ns(None, "tp"),
                    "ws_up": ns(None, "tp"),
                    "ws_down": ns("tp", None),
                })
        else:
            lp.update({
                "w_gate": ns(None, "tp"),
                "w_up": ns(None, "tp"),
                "w_down": ns("tp", None),
            })
        if cfg.attn_kind(cfg.layer_kind(i)).sink:
            lp["sink"] = ns()
        if cfg.attn_bias:
            lp["bq"] = ns("tp")
            lp["bk"] = ns("tp")
            lp["bv"] = ns("tp")
        if cfg.hc_mult > 1:
            # a residual of several streams is served on one device
            # (`validate_model_mesh`): every leaf of a boundary whole
            for name in ("hc_attn", "hc_mlp"):
                lp[name] = {k: ns() for k in (
                    "w", "phi", "alpha", "b_pre", "b_post", "b_res")}
        return lp

    out = {
        "embed": ns("tp", None),  # vocab-sharded; lookup all-gathers over tp
        "layers": [layer(i) for i in range(cfg.num_layers)],
        "final_norm": ns(),
    }
    if not cfg.tie_word_embeddings:
        out["lm_head"] = ns(None, "tp")
    return out


def kv_cache_sharding(mesh: Mesh) -> NamedSharding:
    """Per-layer KV pools [N_slots, K*Hd]: the folded head dim over tp
    (contiguous Hd-sized blocks per KV head, so tp shards land on whole
    heads) — gathers/scatters stay shard-local, no collectives on the KV
    path."""
    return NamedSharding(mesh, P(None, "tp"))


def token_sharding(mesh: Mesh) -> NamedSharding:
    """Token/position/slot arrays [B, T]: batch over dp, sequence over sp."""
    return NamedSharding(mesh, P("dp", "sp"))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def shard_params(params, cfg: ModelConfig, mesh: Mesh):
    """device_put the param pytree against its shardings.

    Quantized leaves ({"q", "s"} dicts, ops/quant.py) get the weight's
    spec on q and its output-dim (last) axis on the per-channel scale;
    the int8 "lm_head" quantization adds even for tied embeddings is
    vocab-column sharded like an untied head."""
    from dynamo_tpu.ops.quant import is_quantized

    shardings = param_shardings(cfg, mesh)
    if "lm_head" in params and "lm_head" not in shardings:
        shardings["lm_head"] = NamedSharding(mesh, P(None, "tp"))

    def put(arr, s):
        if is_quantized(arr):
            last = s.spec[-1] if len(s.spec) else None
            return {
                "q": jax.device_put(arr["q"], s),
                "s": jax.device_put(arr["s"], NamedSharding(mesh, P(last))),
            }
        return jax.device_put(arr, s)

    return jax.tree.map(
        put, params, shardings,
        is_leaf=lambda x: is_quantized(x) or not isinstance(x, (dict, list)),
    )
