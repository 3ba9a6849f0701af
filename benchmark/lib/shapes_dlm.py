"""Bytes and operations of one PASS of the block step of a model generated
by diffusion over blocks (the SDAR family: grouped-query attention with a
norm a head, softmax-routed experts in every layer), from a
configuration's shapes (bf16: 2 bytes a parameter), and the readers of the
block program's own events in the traced slice. Beside `shapes.py`: what
the algorithm needs, not what a kernel moves.

A pass carries a whole block of `block_length` positions a sequence: it
reads every weight once (of the experts a layer those a token reached),
every resident token's keys and values once a layer, and multiplies `rows x
block_length` token rows through the active parameters."""

from __future__ import annotations

import re

import trace_host

BYTES = 2
PROGRAM = "jit__dlm_multi"
# operations XLA names itself; what is left under a kernel's scope is the
# kernel's custom call, which carries the name of the function that made it
XLA_OP = re.compile(
    r"^%(fusion|copy|broadcast|convert|reshape|slice|bitcast|tile|reduce|"
    r"iota|transpose|pad|select|concatenate|dynamic|gather|scatter|"
    r"all-|while|conditional|tuple|get-tuple|constant|parameter|"
    r"[\w\-]*_fusion|[\w\-]*-done|[\w\-]*-start)")


def config(art: dict) -> dict | None:
    """The configuration's keys where it steps blocks, else None."""
    hf = {k: v for k, v in art["config"].items() if k != "benchmark"}
    return hf if hf.get("block_length") else None


def attention_params(hf: dict) -> int:
    d, hd = hf["hidden_size"], hf["head_dim"]
    h, kh = hf["num_attention_heads"], hf["num_key_value_heads"]
    return (d * h * hd + 2 * d * kh * hd    # W_q, W_k, W_v
            + h * hd * d                    # W_o
            + 2 * hd + 2 * d)               # the two head norms, two norms


def expert_params(hf: dict) -> int:
    return 3 * hf["hidden_size"] * hf["moe_intermediate_size"]


def param_count(hf: dict) -> int:
    """Every parameter the configuration's file holds: its layers, the
    embedding, the head (untied) and the last norm."""
    d = hf["hidden_size"]
    layer = (attention_params(hf) + d * hf["num_experts"]
             + hf["num_experts"] * expert_params(hf))
    return hf["num_hidden_layers"] * layer + 2 * d * hf["vocab_size"] + d


def kv_bytes_per_token(hf: dict) -> int:
    """Keys and values one token keeps over all layers."""
    return (hf["num_hidden_layers"] * 2 * hf["num_key_value_heads"]
            * hf["head_dim"] * BYTES)


def pass_weight_bytes(hf: dict, experts_hit: float) -> float:
    """`experts_hit`: distinct experts with a token, mean over the layers
    and the passes (the engine's `moe_experts_hit` digest column). The
    embedding is read a row a token and is left out."""
    d = hf["hidden_size"]
    layer = (attention_params(hf) + d * hf["num_experts"]
             + experts_hit * expert_params(hf))
    return (hf["num_hidden_layers"] * layer
            + d * hf["vocab_size"] + d) * BYTES


def block_attn_bytes(hf: dict, resident_tokens: float, rows: float) -> float:
    """What the block attention of one pass must move over all layers:
    every resident token's keys and values once (the block's own rows
    among them), and each row's block of queries in and outputs out."""
    q = hf["num_attention_heads"] * hf["head_dim"] * BYTES
    return (resident_tokens * kv_bytes_per_token(hf)
            + hf["num_hidden_layers"] * rows * hf["block_length"] * 2 * q)


def pass_ops(hf: dict, rows: float, resident_tokens: float) -> float:
    """Multiply-adds x 2 of one pass: `rows x block_length` token rows
    through the parameters a token uses (attention, router, its
    `num_experts_per_tok` experts, the head) and the attention's scores
    and weighted values over the resident tokens."""
    d = hf["hidden_size"]
    token_rows = rows * hf["block_length"]
    active = hf["num_hidden_layers"] * (
        attention_params(hf) + d * hf["num_experts"]
        + hf["num_experts_per_tok"] * expert_params(hf)
    ) + d * hf["vocab_size"]
    scores = (hf["num_hidden_layers"] * hf["block_length"] * resident_tokens
              * hf["num_attention_heads"] * hf["head_dim"] * 2)
    return 2.0 * (token_rows * active + scores)


def rows_mean(art: dict) -> float:
    """Mean live rows of the window's block dispatches (the digests)."""
    rows = [d["rows"] for d in art["digests"] if d["kind"] == "dlm"]
    return sum(rows) / len(rows) if rows else 0.0


def experts_hit(art: dict) -> float:
    hits = [d["moe_experts_hit"] for d in art["digests"]
            if d.get("moe_experts_hit")]
    return sum(hits) / len(hits) if hits else 0.0


def _block_kernel_events(art: dict, scope: str = "attn.block"):
    """(events, seconds) of the KERNEL's own events under `scope` in the
    traced slice: its custom call, named by the function that made it, not
    the waits and copies XLA schedules beside it under the same scope."""
    path = trace_host.find(art) if config(art) and art.get("trace") else None
    count = total = 0
    for plane in (trace_host.load(path)["planes"] if path else ()):
        if not plane["name"].startswith("/device:"):
            continue
        for line in plane["lines"]:
            if line["name"] != trace_host.OPS_LINE:
                continue
            for name, _, dur, meta in line["events"]:
                if meta.get("scope") == scope and not XLA_OP.match(name):
                    count += 1
                    total += dur
    return count, total / 1e9


def passes_in_slice(art: dict) -> float:
    """Passes of the block step the traced slice holds, whole executions
    and cut ones alike: the block attention kernel runs once a layer a
    pass. (An execution of 9 passes lasts ~0.3 s and the slice 0.5 s: two
    of the three executions a slice meets are cut, and a MEDIAN execution
    is a cut one: PERF.md section 7, S11a.) 0 without such a program."""
    hf = config(art)
    return _block_kernel_events(art)[0] / hf["num_hidden_layers"] if hf else 0


def scope_pass_seconds(art: dict, prefixes: tuple = ("",)) -> float | None:
    """Device time of one pass under the scopes that start with one of
    `prefixes` (all of the program without one): the block program's self
    time there in the slice over the passes the slice holds."""
    passes = passes_in_slice(art)
    times = (trace_host.scopes(art) or {"times": {}})["times"].get(
        PROGRAM, {}) if passes else {}
    own = sum(s for scope, s in times.items() if scope.startswith(prefixes))
    return own / passes if own else None


def pass_seconds(art: dict) -> float | None:
    """Device time of one pass of the block step."""
    return scope_pass_seconds(art)


def kernel_pass_seconds(art: dict) -> float | None:
    """Device time of one pass in the block attention kernel's own
    events: their summed duration over the passes they ran in."""
    count, seconds = _block_kernel_events(art)
    hf = config(art)
    return seconds / (count / hf["num_hidden_layers"]) if count else None
