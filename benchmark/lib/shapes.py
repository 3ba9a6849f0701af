"""Bytes and operations from a configuration's shapes: what the
algorithm needs, not what a kernel happens to move. Kept with the
benchmark so that no change to the program can move a roofline share."""

from __future__ import annotations


def _dims(hf: dict) -> tuple[int, int, int]:
    """(layers, KV heads, head size)."""
    heads = hf["num_attention_heads"]
    hd = hf.get("head_dim") or hf["hidden_size"] // heads
    return hf["num_hidden_layers"], hf.get("num_key_value_heads", heads), hd


def kv_bytes_per_token(hf: dict, kv_quantization: str | None) -> int:
    """Bytes of K and V one token keeps in the cache over all layers:
    bf16 2 bytes a value; int8 1 byte a value plus one float32 scale per
    token per KV head for each of K and V."""
    layers, kvh, hd = _dims(hf)
    if kv_quantization is None:
        return layers * 2 * kvh * hd * 2
    if kv_quantization == "int8":
        return layers * 2 * kvh * (hd + 4)
    raise ValueError(f"no byte count for kv_quantization={kv_quantization!r}")


def decode_attention_bytes(hf: dict, kv_quantization: str | None,
                           resident_tokens: float) -> float:
    """HBM bytes one decode step's attention must read: every resident
    token's K and V once (the queries and the output are thousands of
    times smaller and are left out, so the share this gives is a floor)."""
    return resident_tokens * kv_bytes_per_token(hf, kv_quantization)


def flag(flags: list[str], name: str) -> str | None:
    """The value of `--name value` in an engine flag list."""
    return flags[flags.index(name) + 1] if name in flags else None
