"""Bytes of a latent cache (latent attention, DeepSeek-V2), from a
configuration's shapes: what the algorithm needs, not what a kernel moves
(a row is 576 values; it lies in 640 lanes and the kernel copies those).
Beside `shapes.py`, whose K/V-head counts do not apply here."""

from __future__ import annotations


def latent_bytes_per_token(hf: dict) -> int:
    """Bytes one token keeps in the cache over all layers: one row of
    `kv_lora_rank + qk_rope_head_dim` bf16 values a layer."""
    return (hf["num_hidden_layers"]
            * (hf["kv_lora_rank"] + hf["qk_rope_head_dim"]) * 2)


def decode_latent_bytes(hf: dict, resident_tokens: float) -> float:
    """HBM bytes one decode step's latent attention must read: every
    resident token's row ONCE (it is the key and, in its first
    `kv_lora_rank` values, the value)."""
    return resident_tokens * latent_bytes_per_token(hf)


def resident_tokens(art: dict) -> float:
    """Mean resident context tokens of the requests that were decoding
    during the traced slice, from the benchmark's own request log (prompt +
    tokens streamed so far), as `decode_attn_roofline` reckons them."""
    lo, hi = art["trace"]["slice"]
    marks = [lo + (hi - lo) * (i + 0.5) / 16 for i in range(16)]
    resident = 0.0
    for r in art["requests"]:
        if "t_first" not in r or r["tokens"] < 2:
            continue
        for m in marks:
            if r["t_first"] <= m <= r["t_last"]:
                done = (m - r["t_first"]) / (r["t_last"] - r["t_first"])
                resident += (r["prompt_tokens"] + done * r["tokens"]) / 16
    return resident


def step_scope_ms(art: dict, scopes, prefix: str) -> float | None:
    """Device time of one decode step under the scopes that start with
    `prefix`, read as `decode_mlp_ms` reads it: the scopes' share of the
    decode program's recorded self time x its MEDIAN execution /
    `decode_steps`. A share, so an execution that the slice's end cuts
    moves nothing (operation time over a count of executions reads high
    by the cut one: PERF.md section 7, S11a)."""
    times = (scopes or {"times": {}})["times"].get("jit__decode_multi", {})
    own = sum(s for scope, s in times.items() if scope.startswith(prefix))
    prog = ((art.get("trace") or {}).get("programs") or {}).get(
        "jit__decode_multi")
    if not own or not prog:
        return None
    return (own / sum(times.values()) * prog["median_s"]
            / art["engine"]["decode_steps"] * 1e3)
