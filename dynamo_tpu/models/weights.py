"""Weight loading: HF safetensors checkpoints -> our param pytree.

Equivalent surface to the reference's model resolution (reference:
lib/llm/src/local_model.rs:37-124 + hub.rs — it downloads HF checkpoints for
vLLM to load; here we load them into JAX directly). Zero-egress friendly:
loads from a local directory only; `transformers` is used solely for
tokenizers elsewhere.

HF stores linear weights [out, in]; we store [in, out] (x @ w). Loading
streams tensor-by-tensor so peak host memory is one tensor, and each tensor
can be device_put against a sharding as it loads.
"""

from __future__ import annotations

import json
import os
from typing import Callable, Optional

import jax.numpy as jnp
import numpy as np

from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.models.llama import Params


def _iter_safetensors(model_dir: str):
    try:
        from safetensors import safe_open  # packaged with transformers deps
    except ImportError as e:  # pragma: no cover
        raise RuntimeError("safetensors not available for weight loading") from e

    files = sorted(
        f for f in os.listdir(model_dir) if f.endswith(".safetensors")
    )
    if not files:
        raise FileNotFoundError(f"no .safetensors files under {model_dir}")
    for fname in files:
        with safe_open(os.path.join(model_dir, fname), framework="np") as f:
            for name in f.keys():
                yield name, f.get_tensor(name)


def load_config(model_dir: str, name: Optional[str] = None) -> ModelConfig:
    with open(os.path.join(model_dir, "config.json")) as f:
        hf = json.load(f)
    return ModelConfig.from_hf_config(hf, name=name or os.path.basename(model_dir))


def load_params(
    model_dir: str,
    cfg: ModelConfig,
    dtype=jnp.bfloat16,
    put: Optional[Callable[[str, np.ndarray], jnp.ndarray]] = None,
) -> Params:
    """Load params from a local HF checkpoint dir.

    `put(path, np_array) -> jax array` lets the caller device_put each
    tensor against its mesh sharding as it streams in; defaults to plain
    jnp.asarray.
    """
    if cfg.hybrid:
        raise ValueError(
            f"checkpoint loading for '{cfg.name}' (window beside full "
            "attention) is not written yet: the published tensor names "
            "(sinks, the router's correction bias, a share of the experts) "
            "are not on this machine; such a model runs on seeded weights"
        )
    if cfg.recurrent:
        raise ValueError(
            f"checkpoint loading for '{cfg.name}' (Mamba-2 layers beside "
            "attention) is not written yet: the published tensor names "
            "are not on this machine; such a model runs on seeded weights"
        )
    if cfg.hc_mult > 1 or cfg.q_lora_rank:
        raise ValueError(
            f"checkpoint loading for '{cfg.name}' (low-rank queries, a "
            "residual of several streams) is not written yet: the published "
            "tensor names are not on this machine; such a model runs on "
            "seeded weights"
        )
    put = put or (lambda _path, arr: jnp.asarray(arr))

    def convert(name: str, t: np.ndarray, transpose: bool) -> jnp.ndarray:
        arr = np.ascontiguousarray(t.T) if transpose else t
        return put(name, arr.astype(dtype))

    layers: list[dict] = [dict() for _ in range(cfg.num_layers)]
    params: Params = {"layers": layers}

    hf_layer_map = {
        "input_layernorm.weight": ("attn_norm", False),
        "self_attn.q_proj.weight": ("wq", True),
        "self_attn.k_proj.weight": ("wk", True),
        "self_attn.v_proj.weight": ("wv", True),
        "self_attn.o_proj.weight": ("wo", True),
        "self_attn.q_proj.bias": ("bq", False),
        "self_attn.k_proj.bias": ("bk", False),
        "self_attn.v_proj.bias": ("bv", False),
        "post_attention_layernorm.weight": ("mlp_norm", False),
        "mlp.gate_proj.weight": ("w_gate", True),
        "mlp.up_proj.weight": ("w_up", True),
        "mlp.down_proj.weight": ("w_down", True),
        # deepseek_v2: latent attention, the router, the shared experts
        # (one FFN of n_shared_experts expert widths in the checkpoint too)
        "self_attn.kv_a_proj_with_mqa.weight": ("w_kva", True),
        "self_attn.kv_a_layernorm.weight": ("kv_norm", False),
        "self_attn.kv_b_proj.weight": ("w_kvb", True),
        # sdar_moe: the norm a head on queries and keys
        "self_attn.q_norm.weight": ("q_norm", False),
        "self_attn.k_norm.weight": ("k_norm", False),
        "mlp.gate.weight": ("router", True),
        "mlp.shared_experts.gate_proj.weight": ("ws_gate", True),
        "mlp.shared_experts.up_proj.weight": ("ws_up", True),
        "mlp.shared_experts.down_proj.weight": ("ws_down", True),
    }

    # mixtral MoE tensors stage per (layer, matrix) and flush to device
    # the moment all E experts arrived — staging stays bounded at one
    # [E, ...] group, keeping the one-tensor(-group) streaming invariant
    moe_stage: dict[tuple[int, str], dict[int, np.ndarray]] = {}
    moe_map = {"w1": "we_gate", "w3": "we_up", "w2": "we_down",
               "gate_proj": "we_gate", "up_proj": "we_up",
               "down_proj": "we_down"}

    def stage_moe(idx: int, ours: str, e_idx: int, tensor: np.ndarray) -> None:
        group = moe_stage.setdefault((idx, ours), {})
        group[e_idx] = np.ascontiguousarray(tensor.T)  # HF stores [out, in]
        if len(group) == cfg.num_experts:
            stacked = np.stack([group[e] for e in sorted(group)])
            layers[idx][ours] = put(
                f"layer{idx}.{ours}", stacked.astype(dtype)
            )
            del moe_stage[(idx, ours)]

    for name, tensor in _iter_safetensors(model_dir):
        if name == "model.embed_tokens.weight":
            params["embed"] = convert(name, tensor, transpose=False)
        elif name == "model.norm.weight":
            params["final_norm"] = convert(name, tensor, transpose=False)
        elif name == "lm_head.weight":
            if not cfg.tie_word_embeddings:
                params["lm_head"] = convert(name, tensor, transpose=True)
        elif name.startswith("model.layers."):
            rest = name[len("model.layers."):]
            idx_s, _, sub = rest.partition(".")
            idx = int(idx_s)
            if sub == "block_sparse_moe.gate.weight":
                layers[idx]["router"] = convert(name, tensor, transpose=True)
                continue
            experts = next(
                (p for p in ("block_sparse_moe.experts.", "mlp.experts.")
                 if sub.startswith(p)), None,
            )
            if experts:
                # mixtral: block_sparse_moe.experts.{e}.{w1|w2|w3}.weight;
                # deepseek_v2: mlp.experts.{e}.{gate|up|down}_proj.weight
                e_s, _, w_name = sub[len(experts):].partition(".")
                ours = moe_map.get(w_name.split(".")[0])
                if ours is not None:
                    stage_moe(idx, ours, int(e_s), tensor)
                continue
            mapped = hf_layer_map.get(sub)
            if mapped is None:
                continue  # rotary inv_freq etc.
            ours, transpose = mapped
            layers[idx][ours] = convert(name, tensor, transpose)

    if moe_stage:
        short = sorted(
            f"layers[{i}].{ours}({len(g)}/{cfg.num_experts} experts)"
            for (i, ours), g in moe_stage.items()
        )
        raise ValueError(
            f"checkpoint {model_dir} has incomplete expert groups: {short[:5]}"
        )
    def required(i: int) -> list[str]:
        need = ["wq"] + (["w_kva", "kv_norm", "w_kvb"] if cfg.latent else [])
        need += ["q_norm", "k_norm"] if cfg.qk_norm else []
        if cfg.is_moe_layer(i):
            need += ["router", "we_gate", "we_up", "we_down"]
            if cfg.num_shared_experts:
                need += ["ws_gate", "ws_up", "ws_down"]
        return need

    missing = [
        k for k in ("embed", "final_norm") if k not in params
    ] + [
        f"layers[{i}].{r}"
        for i, lp in enumerate(layers)
        for r in required(i)
        if r not in lp
    ]
    if missing:
        raise ValueError(f"checkpoint {model_dir} missing tensors: {missing[:5]}")
    return params
