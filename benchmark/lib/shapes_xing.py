"""Parameters and bytes of a Xing4.0-family model from its configuration's
shapes: latent attention with LOW-RANK queries, sigmoid-routed experts
beside shared ones, and a residual of `hc_mult` streams with two mHC
boundaries a layer. What the algorithm needs, not what a kernel or XLA
moves. Beside `shapes.py`, `shapes_moe.py` (full-rank queries, no
boundary) and `shapes_mla.py` (the latent rows, used here as it is)."""

from __future__ import annotations

import shapes_ssm
import trace_host

BYTES = 2


def attention_params(hf: dict) -> int:
    d, h = hf["hidden_size"], hf["num_attention_heads"]
    nope, rope = hf["qk_nope_head_dim"], hf["qk_rope_head_dim"]
    rank, vd, qr = hf["kv_lora_rank"], hf["v_head_dim"], hf["q_lora_rank"]
    return (d * qr + qr + qr * h * (nope + rope)   # W_qa, its norm, W_qb
            + d * (rank + rope) + rank             # W_kva, its norm
            + rank * h * (nope + vd)               # W_kvb
            + h * vd * d                           # W_o
            + 2 * d)                               # the layer's two norms


def boundary_params(hf: dict) -> int:
    """One mHC boundary: w [nC], Phi [nC, 2n + n^2], three alphas, b_pre
    [n], b_post [n], B_res [n, n]."""
    n = hf["hc_mult"]
    width = n * hf["hidden_size"]
    return width * (2 * n + n * n) + width + 3 + 2 * n + n * n


def expert_params(hf: dict) -> int:
    """One routed (or shared) expert: a SwiGLU of `moe_intermediate_size`."""
    return 3 * hf["hidden_size"] * hf["moe_intermediate_size"]


def _layers(hf: dict) -> tuple[int, int]:
    dense = min(hf.get("first_k_dense_replace", 0), hf["num_hidden_layers"])
    return dense, hf["num_hidden_layers"] - dense


def _outside_experts(hf: dict) -> int:
    """Every parameter a decode step reads whatever the router chose."""
    d = hf["hidden_size"]
    dense, expert = _layers(hf)
    total = hf["num_hidden_layers"] * (
        attention_params(hf) + 2 * boundary_params(hf))
    total += dense * 3 * d * hf["intermediate_size"]
    total += expert * (
        d * hf["n_routed_experts"] + hf["n_routed_experts"]  # router, bias
        + hf.get("n_shared_experts", 0) * expert_params(hf))
    return total + d * hf["vocab_size"] + d                  # head, last norm


def param_count(hf: dict) -> int:
    """Every parameter the chip holds (the embedding too)."""
    return (_outside_experts(hf)
            + _layers(hf)[1] * hf["n_routed_experts"] * expert_params(hf)
            + hf["vocab_size"] * hf["hidden_size"])


def decode_weight_bytes(hf: dict, experts_hit: float) -> float:
    """`experts_hit`: distinct routed experts with a token, mean over the
    expert layers (the engine's `moe_experts_hit` digest column). The
    embedding is read a row a token and is left out."""
    return (_outside_experts(hf)
            + _layers(hf)[1] * experts_hit * expert_params(hf)) * BYTES


def mhc_mix_bytes(hf: dict, rows: float) -> float:
    """Bytes the boundaries of one decode step must move, whatever
    implements them: each of 2 x layers boundaries reads a row's n x C
    streams and writes them back, bf16. (The sublayer's input and output,
    C each, and the 24 maps a row are left out: a floor.)"""
    width = hf["hc_mult"] * hf["hidden_size"]
    return rows * 2 * hf["num_hidden_layers"] * 2 * width * BYTES


MHC = ".mhc"   # `attn.mhc`, `mlp.mhc`: a layer's two boundaries


def slice_step(art: dict, suffix: str = ""):
    """(seconds one decode step spends under the scopes that end with
    `suffix` (all of the program without one), the configuration's keys),
    from the traced slice: the decode program's self time there over the
    steps the slice holds, whole or cut (`shapes_ssm.steps_in_slice`: no
    operation's name is read, and an execution of 8 steps that the 0.5 s
    slice cuts counts for what it ran). The boundaries' operations carry
    `attn.mhc` / `mlp.mhc`: the trace reader (`trace_host.SCOPE`) admits
    `attn.*` / `mlp.*` / `norm` / `head` / `sample`, so the program names
    them there and keeps `mhc.maps` / `mhc.pre` / `mhc.post` inside. None
    where there is no trace, no such scope, or the configuration has no
    streams."""
    hf = {k: v for k, v in art["config"].items() if k != "benchmark"}
    if not art.get("trace") or hf.get("hc_mult", 1) < 2:
        return None
    times = (trace_host.scopes(art) or {"times": {}})["times"].get(
        shapes_ssm.PROGRAM, {})
    own = sum(s for scope, s in times.items() if scope.endswith(suffix))
    steps = own and shapes_ssm.steps_in_slice(art)
    return (own / steps, hf) if steps else None


def decode_rows(art: dict) -> float:
    """Mean live rows of the window's decode dispatches (the digests)."""
    rows = [d["rows"] for d in art["digests"] if d["kind"] == "decode"]
    return sum(rows) / len(rows) if rows else 0.0
