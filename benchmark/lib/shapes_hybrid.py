"""Bytes one decode step of a MiMo-V2-family model must read, from the
configuration's shapes (bf16: 2 bytes a value): per KIND of attention
layer the KV a resident token keeps (full layers: every token of the
context; window layers: at most `sliding_window` of them), and the weights
(every matrix outside the routed experts once, and of the experts the chip
HOLDS only those a token reached). What the algorithm needs, not what a
kernel moves (a kernel reads whole pages). Beside `shapes.py`, whose one
K/V-head count and one head width do not describe such a model."""

from __future__ import annotations

BYTES = 2
FULL, WINDOW = 0, 1


def _kind(hf: dict, kind: int) -> tuple[int, int, int]:
    """(KV heads, key width, value width) of a kind of layer."""
    if kind == WINDOW:
        return (hf["swa_num_key_value_heads"], hf["swa_head_dim"],
                hf["swa_v_head_dim"])
    return hf["num_key_value_heads"], hf["head_dim"], hf["v_head_dim"]


def layers_of(hf: dict, kind: int) -> int:
    return sum(1 for k in hf["hybrid_layer_pattern"] if k == kind)


def kv_bytes_per_token(hf: dict, kind: int) -> int:
    """Bytes one token keeps in ONE layer of `kind`: a key row and a value
    row a KV head (full 4 x (192 + 128) x 2 = 2,560; window 5,120)."""
    heads, kd, vd = _kind(hf, kind)
    return heads * (kd + vd) * BYTES


def resident(art: dict) -> tuple[float, float]:
    """(tokens the full layers must read, tokens a window layer must
    read) in one decode step: over the requests decoding during the traced
    slice, from the benchmark's own request log, the mean of context
    (prompt + tokens streamed so far) and of min(context, sliding_window),
    each summed over the requests. Reckoned at 16 instants of the slice, as
    `shapes_mla.resident_tokens` reckons."""
    lo, hi = art["trace"]["slice"]
    window = art["config"]["sliding_window"]
    marks = [lo + (hi - lo) * (i + 0.5) / 16 for i in range(16)]
    full = win = 0.0
    for r in art["requests"]:
        if "t_first" not in r or r["tokens"] < 2:
            continue
        for m in marks:
            if r["t_first"] <= m <= r["t_last"]:
                done = (m - r["t_first"]) / (r["t_last"] - r["t_first"])
                context = r["prompt_tokens"] + done * r["tokens"]
                full += context / 16
                win += min(context, window) / 16
    return full, win


def decode_kv_bytes(hf: dict, full_tokens: float, win_tokens: float) -> float:
    """HBM bytes one decode step's attention must read, both kinds."""
    return (full_tokens * kv_bytes_per_token(hf, FULL) * layers_of(hf, FULL)
            + win_tokens * kv_bytes_per_token(hf, WINDOW)
            * layers_of(hf, WINDOW))


def attention_params(hf: dict, kind: int) -> int:
    d, h = hf["hidden_size"], hf["num_attention_heads"]
    heads, kd, vd = _kind(hf, kind)
    return (d * h * kd + d * heads * (kd + vd)   # W_q, W_k, W_v
            + h * vd * d                         # W_o
            + (h if kind == WINDOW else 0)       # the sinks
            + 2 * d)                             # the layer's two norms


def expert_params(hf: dict) -> int:
    """One routed expert: a SwiGLU of `moe_intermediate_size`."""
    return 3 * hf["hidden_size"] * hf["moe_intermediate_size"]


def router_width(hf: dict) -> int:
    """Experts the router scores: the published count, whatever share of
    them the file's `n_routed_experts` says this chip holds."""
    return hf.get("router_width", hf["n_routed_experts"])


def decode_weight_bytes(hf: dict, experts_hit: float) -> float:
    """`experts_hit`: distinct HELD experts with a token, mean over the
    expert layers (the engine's `moe_experts_hit` digest column; at most
    the file's `n_routed_experts`, the experts held)."""
    d = hf["hidden_size"]
    expert_layers = sum(1 for f in hf["moe_layer_freq"] if f)
    dense_layers = len(hf["moe_layer_freq"]) - expert_layers
    total = sum(attention_params(hf, k) for k in hf["hybrid_layer_pattern"])
    total += dense_layers * 3 * d * hf["intermediate_size"]
    total += expert_layers * (
        d * router_width(hf) + router_width(hf)   # the router and its bias
        + experts_hit * expert_params(hf))
    total += d * hf["vocab_size"] + d             # head, last norm
    # the embedding is read a row a token: a few hundred KB, left out
    return total * BYTES
