"""Bytes of the weights one decode step of a DeepSeek-V2-family model must
read, from the configuration's shapes (bf16: 2 bytes a parameter): every
matrix outside the routed experts once, and of the routed experts only
those that a token reached. Beside `shapes.py`."""

from __future__ import annotations

BYTES = 2


def attention_params(hf: dict) -> int:
    d, h = hf["hidden_size"], hf["num_attention_heads"]
    nope, rope = hf["qk_nope_head_dim"], hf["qk_rope_head_dim"]
    rank, vd = hf["kv_lora_rank"], hf["v_head_dim"]
    return (d * h * (nope + rope)          # W_q
            + d * (rank + rope) + rank     # W_kva, its norm
            + rank * h * (nope + vd)       # W_kvb
            + h * vd * d                   # W_o
            + 2 * d)                       # the layer's two norms


def expert_params(hf: dict) -> int:
    """One routed expert: a SwiGLU of `moe_intermediate_size`."""
    return 3 * hf["hidden_size"] * hf["moe_intermediate_size"]


def decode_weight_bytes(hf: dict, experts_hit: float) -> float:
    """`experts_hit`: distinct routed experts with a token, mean over the
    expert layers (the engine's `moe_experts_hit` digest column)."""
    d = hf["hidden_size"]
    dense_layers = min(hf.get("first_k_dense_replace", 0),
                       hf["num_hidden_layers"])
    expert_layers = hf["num_hidden_layers"] - dense_layers
    total = hf["num_hidden_layers"] * attention_params(hf)
    total += dense_layers * 3 * d * hf["intermediate_size"]
    total += expert_layers * (
        d * hf["n_routed_experts"]                          # the router
        + hf.get("n_shared_experts", 0) * expert_params(hf)
        + experts_hit * expert_params(hf))
    total += d * hf["vocab_size"] + d                       # head, last norm
    # the embedding is read a row a token: a few hundred KB, left out
    return total * BYTES
