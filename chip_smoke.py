#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the serving path starts on the chip.

    python chip_smoke.py            # one TPU chip (what the driver runs)
    python chip_smoke.py --chips 4  # the tensor-parallel phase only, four chips

One process owns the chip(s): the OpenAI HTTP server (built by the same
functions `python -m dynamo_tpu.run in=http out=jax` calls), the HTTP
client and the oracle engine all live here, one engine at a time.

One chip: Llama-3.2-1B at its published width and depth, random weights
from a seed, a synthetic full-vocabulary tokenizer from the same seed,
served in two engine configurations —
  bf16_page64   bf16 weights + bf16 KV, page 64 (examples/llm/configs/agg.yaml)
  int8_page128  --quantization int8 --kv-quantization int8 --page-size 128
                (the int32-packed pallas path)
Each answers: a non-streamed chat completion, a streamed one (SSE framing,
[DONE]), one with logprobs (finite?), a concurrent batch of prompts of
different lengths (several pages, more than one prefill chunk) for 64
output tokens, a repeated prompt (prefix cache) and a /metrics scrape.
The same requests are then served by an engine built with
`--attn-backend gather` (plain XLA attention) from the same seed, and the
greedy token ids and their logprobs are compared under COMPARE_RULE
below. Last, the first configuration starts once more after
`jax.clear_caches()` and must read programs back from the persistent
compile cache.

Four chips (`--chips 4`): Llama-3.1-8B widths, depth cut to
TP_LAYERS layers on BOTH sides so the tp=1 reference fits one 16 GB
chip, bf16, `--tp 4` (as the CLI gives it, then with the ring executor)
against `--tp 1`, same comparison, plus a check that weights and KV
pools are spread evenly over the four devices.

Every line of stdout is one JSON object; the LAST line is
    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}
and is printed only when every phase passed. No accelerator, a failed
phase or a failed comparison exits non-zero without that line. There is
no CPU mode: tests import the phase functions and call them directly.
"""

from __future__ import annotations

import argparse
import asyncio
import gc
import json
import math
import os
import random
import shutil
import sys
import tempfile
import time

SEED = 0

# Published config.json values (meta-llama/Llama-3.2-1B and
# meta-llama/Llama-3.1-8B); they must agree with the presets in
# dynamo_tpu/models/config.py — `check_against_preset` checks them.
LLAMA_32_1B = {
    "architectures": ["LlamaForCausalLM"],
    "model_type": "llama",
    "vocab_size": 128256,
    "hidden_size": 2048,
    "intermediate_size": 8192,
    "num_hidden_layers": 16,
    "num_attention_heads": 32,
    "num_key_value_heads": 8,
    "head_dim": 64,
    "rope_theta": 500000.0,
    "rope_scaling": {
        "rope_type": "llama3", "factor": 32.0, "low_freq_factor": 1.0,
        "high_freq_factor": 4.0, "original_max_position_embeddings": 8192,
    },
    "rms_norm_eps": 1e-5,
    "max_position_embeddings": 131072,
    "tie_word_embeddings": True,
    "torch_dtype": "bfloat16",
}
LLAMA_31_8B = {
    **LLAMA_32_1B,
    "hidden_size": 4096,
    "intermediate_size": 14336,
    "num_hidden_layers": 32,
    "head_dim": 128,
    "rope_scaling": {**LLAMA_32_1B["rope_scaling"], "factor": 8.0},
    "tie_word_embeddings": False,
}

# Depth cut of the four-chip phase, the same on both sides: 8 of 32
# layers at 8B widths is 1.75 B parameters in layers + 1.05 B of untied
# embedding and head, 5.6 GB in bf16 — the tp=1 reference fits one 16 GB
# chip with room for its KV pool, and three cold engine builds (every
# step program unrolls the layers) fit one four-chip call.
TP_LAYERS = 8

# (label, engine flags, the COMPARE_RULES entry its comparison is held to)
ONE_CHIP_CONFIGS = [
    ("bf16_page64", ["--page-size", "64"], "bf16"),
    ("int8_page128", ["--quantization", "int8", "--kv-quantization", "int8",
                      "--page-size", "128"], "int8"),
]
COMMON_FLAGS = ["--max-batch-size", "16", "--max-model-len", "2048",
                "--prefill-chunk", "512", "--decode-steps", "8"]

# content lengths (tokens) of the concurrent batch: from under one page
# to past one 512-token prefill chunk; 10 requests, so the decode batch
# is not a multiple of 8 and prefill groups have odd row counts. With
# the template's 8 tokens, 120 and 376 fill their pages exactly (the
# first decode step allocates a new page) and 55, 183 and 247 end one
# token short of a page (the second does), at page 64 or at both page
# sizes — `compare_ids` counts the crossings it verified.
BATCH_PROMPT_TOKENS = [5, 23, 55, 70, 120, 183, 247, 376, 530, 700]
BATCH_MAX_TOKENS = 64
SINGLE_MAX_TOKENS = 16

# The rule the pallas-vs-gather (and tp=4-vs-tp=1) comparison is held to.
# Both sides are greedy from the same seed. Random weights give top-1
# margins of the order of bf16 rounding, so arithmetic in a different
# order flips a near-tie every few dozen tokens, and after a flip two
# greedy streams have different contexts and cannot be compared. Up to
# and including its first mismatch a request's context is identical on
# both sides, so there two things can be judged:
# - the mismatch itself: the reference side is asked for top_logprobs,
#   and the served token must be one the reference rates about as good
#   as its own choice. A wrong page, mask or scale gives a token the
#   reference does not list at all;
# - a continuous quantity that ties cannot defeat: the served side
#   reports its chosen tokens' logprobs, and |logprob served - logprob
#   reference| of the same token in the same context is bounded, in the
#   mean and at the worst position.
# The numbers are set per KV/weight format from what the v5e showed in
# PR 21 (PERF.md section 6, chip diagnostic 4), about 3-4x the readings:
# bf16 pallas vs gather — mean gap 0.010, worst 0.043, deficits up to
# 0.026, share 0.56-0.77; int8 — mean gap 0.043, worst 0.17, deficits up
# to 0.21, share 0.16-0.29. int8 differs about four times more, and not
# because of the pallas kernels: at int8 ANY two arithmetic variants of
# attention end up that far apart (the gather path against itself with
# f32 attention: mean gap 0.041, against 0.0008 for the same pair at
# bf16), and op by op the int8 kernels sit within one bf16 rounding of
# an exact attention over the same pools, like the gather path. The
# mechanism this points to: W8A8 and int8 KV re-round every matmul input
# to 8 bits, so a rounding-sized difference upstream becomes an 8-bit
# step downstream.
COMPARE_WINDOW = 32          # leading tokens of each request compared
COMPARE_TOP = 8              # reference alternatives per position (the
#                              most the engine reports, TOP_LOGPROBS_MAX)
COMPARE_RULES = {
    # tie_nats: served token's deficit under the reference at a mismatch
    # unexplained: requests whose mismatch may miss that
    # share: floor on mean (common prefix)/window — decode steps verified
    # gap_mean, gap_max: |logprob served - reference|, nats
    "bf16": {"tie_nats": 0.1, "unexplained": 0, "share": 0.3,
             "gap_mean": 0.04, "gap_max": 0.2},
    "int8": {"tie_nats": 0.5, "unexplained": 1, "share": 0.08,
             "gap_mean": 0.12, "gap_max": 0.5},
}
COMPARE_RULE = (
    f"over the first {COMPARE_WINDOW} greedy tokens of every request: ids "
    "identical up to the first mismatch, where the served token is among "
    f"the reference's top {COMPARE_TOP} within tie_nats of its choice (at "
    "most `unexplained` requests may miss that); mean over requests of "
    "(common prefix)/window >= share; |logprob served - logprob "
    "reference| of the same token, over every position up to and "
    "including the first mismatch, <= gap_mean in the mean and <= gap_max "
    "at the worst; and at least one decode step that crossed into a new "
    "KV page lies inside a verified prefix"
)

# Llama-3 keeps its 256 reserved ids at the end of the vocabulary
# (128000..128255); the four the chat template uses, as offsets there.
_SPECIAL_OFFSETS = {
    "<|begin_of_text|>": 0,
    "<|start_header_id|>": 6,
    "<|end_header_id|>": 7,
    "<|eot_id|>": 9,
}
_CHAT_TEMPLATE = (
    "{{ bos_token }}{% for m in messages %}<|start_header_id|>{{ m['role'] }}"
    "<|end_header_id|>\n\n{{ m['content'] }}<|eot_id|>{% endfor %}"
    "{% if add_generation_prompt %}<|start_header_id|>assistant"
    "<|end_header_id|>\n\n{% endif %}"
)
# bos + 3 header tokens + eot + 3 generation-prompt tokens
_TEMPLATE_TOKENS = 8


class SmokeFailure(RuntimeError):
    """A phase produced a wrong result (as opposed to crashing)."""


def emit(obj: dict) -> None:
    print(json.dumps(obj), flush=True)


# ---------------------------------------------------------------- model dir


def make_vocab(vocab_size: int, seed: int) -> list[str]:
    """One distinct whitespace-free word per token id, from the seed.
    Random weights sample ids across the whole vocabulary, so the
    tokenizer must know every id (an id the tokenizer does not know
    decodes to the empty string — measured on tests/data's 68-word
    tokenizer — which would make every completion empty)."""
    rng = random.Random(seed)
    syl = [c + v for c in "bdfghjklmnprstvwz" for v in "aeiou"]
    words = [
        syl[n // 7225] + syl[n // 85 % 85] + syl[n % 85]
        for n in rng.sample(range(85 ** 3), vocab_size)
    ]
    for i, role in enumerate(("system", "user", "assistant")):
        words[i] = role
    for name, off in _SPECIAL_OFFSETS.items():
        words[vocab_size - 256 + off] = name
    return words


def write_model_dir(path: str, hf_config: dict, seed: int) -> list[str]:
    """config.json + tokenizer.json + tokenizer_config.json and NO
    safetensors: LocalModel.prepare then random-inits from
    EngineConfig.seed. Returns the vocabulary (id -> word)."""
    from tokenizers import AddedToken, Tokenizer
    from tokenizers.models import WordLevel
    from tokenizers.pre_tokenizers import WhitespaceSplit

    os.makedirs(path, exist_ok=True)
    with open(os.path.join(path, "config.json"), "w") as f:
        json.dump(hf_config, f, indent=1)
    words = make_vocab(hf_config["vocab_size"], seed)
    tok = Tokenizer(WordLevel({w: i for i, w in enumerate(words)},
                              unk_token=words[3]))
    tok.pre_tokenizer = WhitespaceSplit()
    # template tokens are matched before whitespace splitting; not
    # "special", so decode keeps them and every generated id maps back
    tok.add_tokens([
        AddedToken(name, special=False, normalized=False)
        for name in _SPECIAL_OFFSETS
    ])
    tok.save(os.path.join(path, "tokenizer.json"))
    with open(os.path.join(path, "tokenizer_config.json"), "w") as f:
        json.dump({
            "tokenizer_class": "PreTrainedTokenizerFast",
            "bos_token": "<|begin_of_text|>",
            "eos_token": "<|eot_id|>",
            "chat_template": _CHAT_TEMPLATE,
        }, f, indent=1)
    return words


def check_against_preset(hf_config: dict, preset: str) -> None:
    """The smoke's published values and the repo's preset must agree,
    depth included unless the caller cut it on purpose."""
    from dynamo_tpu.models.config import PRESETS, ModelConfig

    got = ModelConfig.from_hf_config(hf_config, name=preset)
    want = PRESETS[preset].with_(num_layers=got.num_layers)
    if got != want:
        raise SmokeFailure(f"{preset}: config.json {got} != preset {want}")


def build_prompts(words: list[str], seed: int, lengths: list[int]) -> dict:
    rng = random.Random(seed + 1)
    usable = [w for w in words[4:] if w not in _SPECIAL_OFFSETS]

    def content(n: int) -> str:
        return " ".join(rng.choice(usable) for _ in range(n))

    return {
        "single": content(12),
        "stream": content(20),
        "logprobs": content(9),
        "batch": [content(n) for n in lengths],
    }


# ------------------------------------------------------------------- client


def _ids_from_text(text: str, vocab: dict) -> list[int]:
    ids = []
    for w in text.split():
        if w not in vocab:
            raise SmokeFailure(f"completion holds a non-vocabulary word {w!r}")
        ids.append(vocab[w])
    return ids


async def _chat(session, base: str, model: str, content: str,
                max_tokens: int, vocab: dict, *, stream: bool = False,
                logprobs: bool = False, top_logprobs: int = 0,
                sampling: dict | None = None) -> dict:
    body = {
        "model": model,
        "messages": [{"role": "user", "content": content}],
        "max_tokens": max_tokens,
        "temperature": 0.0,
        "stream": stream,
        # exact output length: random weights may sample <|eot_id|>
        "nvext": {"ignore_eos": True},
        **(sampling or {}),
    }
    if stream:
        body["stream_options"] = {"include_usage": True}
    logprobs = logprobs or bool(top_logprobs)
    if logprobs:
        body["logprobs"] = True
    if top_logprobs:
        body["top_logprobs"] = top_logprobs
    async with session.post(f"{base}/v1/chat/completions", json=body) as r:
        raw = await r.text()
        if r.status != 200:
            raise SmokeFailure(f"HTTP {r.status}: {raw[:500]}")
        ctype = r.headers.get("content-type", "")
    entries: list[dict] = []  # per-token logprobs content
    if stream:
        if "text/event-stream" not in ctype:
            raise SmokeFailure(f"streamed reply has content-type {ctype!r}")
        datas = []
        for event in raw.split("\n\n"):
            for line in event.splitlines():
                if line.startswith("data:"):
                    datas.append(line[5:].strip())
                elif line and not line.startswith((":", "event:", "id:")):
                    raise SmokeFailure(f"bad SSE line {line[:80]!r}")
        if not datas or datas[-1] != "[DONE]":
            raise SmokeFailure("SSE stream did not end with data: [DONE]")
        chunks = [json.loads(d) for d in datas[:-1]]
        text, finish, usage = "", None, None
        for c in chunks:
            usage = c.get("usage") or usage
            for ch in c.get("choices") or []:
                text += (ch.get("delta") or {}).get("content") or ""
                finish = ch.get("finish_reason") or finish
                entries += (ch.get("logprobs") or {}).get("content") or []
    else:
        obj = json.loads(raw)
        choice = obj["choices"][0]
        text = choice["message"]["content"] or ""
        finish, usage = choice.get("finish_reason"), obj.get("usage")
        entries = (choice.get("logprobs") or {}).get("content") or []
    ids = _ids_from_text(text, vocab)
    if len(ids) != max_tokens:
        raise SmokeFailure(
            f"asked for {max_tokens} tokens, completion holds {len(ids)}")
    if usage is None or usage.get("completion_tokens") != max_tokens:
        raise SmokeFailure(f"usage {usage} does not report {max_tokens}")
    want_prompt = len(content.split()) + _TEMPLATE_TOKENS
    if usage.get("prompt_tokens") != want_prompt:
        raise SmokeFailure(
            f"prompt_tokens {usage.get('prompt_tokens')} != {want_prompt}")
    if finish != "length":
        raise SmokeFailure(f"finish_reason {finish!r}, expected 'length'")
    out = {"ids": ids, "prompt_tokens": usage["prompt_tokens"],
           "stream": stream}
    if logprobs:
        lps = [e["logprob"] for e in entries]
        if len(lps) != max_tokens or not all(
            isinstance(x, float) and math.isfinite(x) and x <= 1e-3
            for x in lps
        ):
            raise SmokeFailure(f"logprobs not finite/non-positive: {lps}")
        out["lps"] = lps
    if top_logprobs:
        # per position: {token id: logprob} of the reference's best
        out["tops"] = [
            {vocab[a["token"].strip()]: a["logprob"]
             for a in e["top_logprobs"]}
            for e in entries
        ]
    return out


def _metric(text: str, name: str) -> float:
    """Sum of a series over its label sets in a Prometheus exposition."""
    total, seen = 0.0, False
    for line in text.splitlines():
        if line.startswith("#"):
            continue
        head, _, val = line.rpartition(" ")
        if head == name or head.startswith(name + "{"):
            total, seen = total + float(val), True
    if not seen:
        raise SmokeFailure(f"/metrics has no series {name}")
    return total


async def run_requests(base: str, model: str, prompts: dict,
                       vocab: dict, top_logprobs: int = 0,
                       sampled: bool = False,
                       first_only: bool = False) -> dict:
    """The smoke's traffic against a listening server. Returns the
    greedy ids of every request, in a fixed order, and the scrape.
    `top_logprobs` (the reference side of a comparison) asks every
    request for that many alternatives per position; the served side
    asks the batch and the repeat for their chosen tokens' logprobs and
    leaves the first two requests plain; `sampled` (the served side)
    adds one temperature/top-k/top-p request at the end; `first_only`
    stops after the first request (a start-time reading)."""
    top = {"top_logprobs": top_logprobs}
    lp = {**top, "logprobs": True}
    import aiohttp

    out: dict = {"walls": {}}
    timeout = aiohttp.ClientTimeout(total=900)
    async with aiohttp.ClientSession(timeout=timeout) as s:
        async with s.get(f"{base}/v1/models") as r:
            listed = [m["id"] for m in (await r.json())["data"]]
        if model not in listed:
            raise SmokeFailure(f"/v1/models lists {listed}, not {model}")

        t = time.perf_counter()
        first = await _chat(s, base, model, prompts["single"],
                            SINGLE_MAX_TOKENS, vocab, **top)
        out["walls"]["first_request_s"] = time.perf_counter() - t
        if first_only:
            out["ids"], out["metrics"] = [first["ids"]], {}
            return out

        t = time.perf_counter()
        streamed = await _chat(s, base, model, prompts["stream"],
                               SINGLE_MAX_TOKENS, vocab, stream=True, **top)
        with_lps = await _chat(s, base, model, prompts["logprobs"],
                               SINGLE_MAX_TOKENS, vocab, logprobs=True, **top)
        out["walls"]["stream_and_logprobs_s"] = time.perf_counter() - t

        t = time.perf_counter()
        batch = await asyncio.gather(*[
            _chat(s, base, model, p, BATCH_MAX_TOKENS, vocab,
                  stream=bool(i % 2), **lp)
            for i, p in enumerate(prompts["batch"])
        ])
        out["walls"]["batch_s"] = time.perf_counter() - t

        # the longest prompt again: its full pages are in the prefix cache
        t = time.perf_counter()
        repeat = await _chat(s, base, model, prompts["batch"][-1],
                             BATCH_MAX_TOKENS, vocab, **lp)
        out["walls"]["repeat_s"] = time.perf_counter() - t

        if sampled:
            # not greedy, so not compared: runs the top-k/top-p shortlist
            # (approx_max_k over the 128,256-wide vocabulary on TPU)
            # inside the decode scan; count and ids are still checked
            t = time.perf_counter()
            await _chat(s, base, model, prompts["logprobs"],
                        SINGLE_MAX_TOKENS, vocab, sampling={
                            "temperature": 0.8, "top_p": 0.9,
                            "nvext": {"ignore_eos": True, "top_k": 40}})
            out["walls"]["sampled_s"] = time.perf_counter() - t

        async with s.get(f"{base}/metrics") as r:
            if r.status != 200:
                raise SmokeFailure(f"/metrics HTTP {r.status}")
            scrape = await r.text()

    results = [first, streamed, with_lps, *batch, repeat]
    out["ids"] = [r["ids"] for r in results]
    out["tops"] = [r.get("tops") for r in results]
    out["lps"] = [r.get("lps") for r in results]
    out["prompt_tokens"] = [r["prompt_tokens"] for r in results]
    n_req = len(results) + int(sampled)
    served = _metric(scrape, "dynamo_tpu_http_service_requests_total")
    if served < n_req:
        raise SmokeFailure(f"/metrics counts {served} requests, sent {n_req}")
    out["metrics"] = {"http_requests_total": served, **{
        k: _metric(scrape, f"dynamo_tpu_engine_{k}") for k in (
            "prefix_reused_tokens", "kv_pages_peak_used",
            "tp_overlap_dispatches", "gspmd_fallback_dispatches",
            "preemptions_total",
        )
    }}
    if out["metrics"]["prefix_reused_tokens"] <= 0:
        raise SmokeFailure("repeated prompt reused no prefix-cache tokens")
    return out


# ------------------------------------------------------------------- server


def _device_memory(devices) -> list[dict]:
    """bytes_in_use / bytes_limit per device, straight from the backend.
    The smoke's source of truth: a device without stats is an error."""
    out = []
    for d in devices:
        stats = d.memory_stats()
        if not stats or "bytes_limit" not in stats:
            raise SmokeFailure(f"{d} reports no memory_stats()")
        out.append({"id": d.id, "bytes_in_use": int(stats["bytes_in_use"]),
                    "bytes_limit": int(stats["bytes_limit"])})
    return out


async def serve_once(model_dir: str, model_name: str, flags: list[str],
                     prompts: dict, vocab: dict, *, label: str,
                     require_compiled_pallas: bool,
                     report_memory: bool = True,
                     top_logprobs: int = 0,
                     first_only: bool = False) -> dict:
    """Build the `in=http out=jax` service exactly as dynamo_tpu.run does,
    listen on a free localhost port, run the smoke's traffic over real
    HTTP, stop, and free the device. Returns ids + the engine's report."""
    import jax

    from dynamo_tpu.engine import telemetry
    from dynamo_tpu.run import build_http_service, build_parser

    argv = ["in=http", "out=jax", "--model-path", model_dir,
            "--model-name", model_name, "--http-host", "127.0.0.1", *flags]
    args = build_parser().parse_args(argv)
    c0 = telemetry.compile_stats()
    t0 = time.perf_counter()
    svc, engine = await build_http_service(args, "jax")
    await svc.start(args.http_host, 0)
    build_s = time.perf_counter() - t0
    try:
        backend = engine.attention_backend
        devices = list(engine.mesh.devices.flat)
        report = {
            "phase": "engine", "label": label, "argv": argv[2:],
            "attention": backend, "num_pages": engine.num_pages,
            "page_size": engine.page_size, "build_s": round(build_s, 3),
            "param_count": engine.param_count,
            "mesh_devices": len(devices),
        }
        if report_memory:
            report["memory_after_load"] = _device_memory(devices)
        emit(report)
        if require_compiled_pallas and (
            backend["kind"] != "pallas" or backend["interpret"]
        ):
            raise SmokeFailure(
                f"{label}: attn_backend=auto chose {backend}, expected "
                "compiled pallas kernels")
        res = await run_requests(
            f"http://127.0.0.1:{svc.port}", model_name, prompts, vocab,
            top_logprobs=top_logprobs, sampled=not top_logprobs,
            first_only=first_only)
        c1 = telemetry.compile_stats()
        res["report"] = report
        # the counters' growth (`phase_s` is a table by phase, `at_s` the
        # snapshot's stamp: neither is a compile count)
        res["compiles"] = {k: round(c1[k] - c0[k], 3) for k in c1
                           if k not in ("phase_s", "at_s")}
        res["start_s"] = build_s + res["walls"]["first_request_s"]
        served = {
            "phase": "served", "label": label,
            "requests": len(res["ids"]) + int(
                not top_logprobs and not first_only),
            "tokens": sum(len(x) for x in res["ids"]),
            "start_s": round(res["start_s"], 3),
            "walls": {k: round(v, 3) for k, v in res["walls"].items()},
            "compiles": res["compiles"],
            "metrics": res["metrics"],
        }
        if report_memory:
            served["memory_after_traffic"] = _device_memory(devices)
        emit(served)
        return res
    finally:
        await svc.stop()
        await engine.close()
        # one engine at a time on the chip: free its arrays now, not
        # whenever the last reference happens to die
        for leaf in jax.tree.leaves((engine.params, engine.kv)):
            leaf.delete()
        del engine, svc
        gc.collect()


def compare_ids(label: str, main: dict, ref: dict, rule: str,
                page_size: int) -> dict:
    """COMPARE_RULE, with the numbers of COMPARE_RULES[rule], between
    what the served side and the reference answered (two `run_requests`
    results; the reference's carries per-position alternatives)."""
    r = COMPARE_RULES[rule]
    got, want = main["ids"], ref["ids"]
    if not (len(got) == len(want) == len(ref["tops"])):
        raise SmokeFailure(f"{label}: {len(got)} vs {len(want)} requests")
    shares, bad, rows, gaps, crossed = [], [], [], [], []
    for i, (a, b, tops, lps, n_prompt) in enumerate(zip(
            got, want, ref["tops"], main["lps"], main["prompt_tokens"])):
        if len(a) != len(b) or not a:
            raise SmokeFailure(f"{label}: request {i} lengths {len(a)}/{len(b)}")
        w = min(COMPARE_WINDOW, len(a))
        pre = next((j for j in range(w) if a[j] != b[j]), w)
        shares.append(pre / w)
        # up to AND including the first mismatch both sides saw the same
        # context: the served token's logprob is comparable wherever the
        # reference lists that token
        judged = [j for j in range(min(pre + 1, w))
                  if lps is not None and a[j] in tops[j]]
        gaps += [abs(lps[j] - tops[j][a[j]]) for j in judged]
        # generated token j sits at position n_prompt + j; where that is
        # a page's first slot, token j+1 is the first computed over a row
        # of a newly allocated page
        if any((n_prompt + j - 1) % page_size == 0 for j in judged if j):
            crossed.append(i)
        if pre == w:
            continue
        deficit = (tops[pre][b[pre]] - tops[pre][a[pre]]
                   if a[pre] in tops[pre] else None)
        near_tie = deficit is not None and deficit <= r["tie_nats"]
        rows.append({"request": i, "first_mismatch_at": pre,
                     "got": a[pre], "ref": b[pre],
                     "ref_logprob_deficit": (
                         None if deficit is None else round(deficit, 4)),
                     "near_tie": near_tie})
        if not near_tie:
            bad.append(i)
    mean_share = sum(shares) / len(shares)
    gap_mean = sum(gaps) / len(gaps) if gaps else math.inf
    gap_max = max(gaps, default=math.inf)
    ok = (len(bad) <= r["unexplained"] and mean_share >= r["share"]
          and gap_mean <= r["gap_mean"] and gap_max <= r["gap_max"]
          and bool(crossed))
    verdict = {
        "phase": "compare", "label": label, "ok": ok, "rule": COMPARE_RULE,
        "numbers": r, "requests": len(got), "not_near_tie": bad,
        "mean_prefix_share": round(mean_share, 4),
        "tokens_identical_before_first_mismatch": sum(
            round(x * min(COMPARE_WINDOW, len(a))) for x, a in zip(shares, got)),
        "identical_requests": sum(a == b for a, b in zip(got, want)),
        "logprob_gap": {"positions": len(gaps),
                        "mean": round(gap_mean, 5), "max": round(gap_max, 5)},
        "page_crossings_verified_in_requests": crossed,
        "mismatches": rows,
    }
    emit(verdict)
    if not ok:
        raise SmokeFailure(f"{label}: token comparison failed")
    return verdict


def prepare_model(workdir: str, preset: str, hf_config: dict,
                  prompt_tokens: list[int] = BATCH_PROMPT_TOKENS) -> dict:
    """Model directory, prompts and vocabulary from the seed: the
    leading arguments of `serve_once`, as keywords."""
    model_dir = os.path.join(workdir, preset)
    words = write_model_dir(model_dir, hf_config, SEED)
    return {"model_dir": model_dir, "model_name": preset,
            "prompts": build_prompts(words, SEED, prompt_tokens),
            "vocab": {w: i for i, w in enumerate(words)}}


async def serve_and_compare(workdir: str, preset: str, hf_config: dict,
                            flags: list[str], label: str, *,
                            main_flags: list[str], ref_flags: list[str],
                            rule: str, require_compiled_pallas: bool,
                            prompt_tokens: list[int] = BATCH_PROMPT_TOKENS,
                            report_memory: bool = True) -> dict:
    """One configuration: serve with `main_flags`, serve again with
    `ref_flags` from the same seed, compare greedy ids."""
    common = dict(prepare_model(workdir, preset, hf_config, prompt_tokens),
                  report_memory=report_memory)
    main = await serve_once(
        flags=flags + main_flags, label=f"{label}/main",
        require_compiled_pallas=require_compiled_pallas, **common)
    ref = await serve_once(
        flags=flags + ref_flags, label=f"{label}/ref",
        require_compiled_pallas=False, top_logprobs=COMPARE_TOP, **common)
    verdict = compare_ids(label, main, ref, rule,
                          main["report"]["page_size"])
    return {"main": main, "ref": ref, "verdict": verdict}


# -------------------------------------------------------------------- phases


async def one_chip(workdir: str) -> None:
    check_against_preset(LLAMA_32_1B, "llama-3.2-1b")
    cold = None
    for label, flags, rule in ONE_CHIP_CONFIGS:
        res = await serve_and_compare(
            workdir, "llama-3.2-1b", LLAMA_32_1B, COMMON_FLAGS + flags,
            label, main_flags=["--attn-backend", "auto"],
            ref_flags=["--attn-backend", "gather"], rule=rule,
            require_compiled_pallas=True)
        cold = cold if cold is not None else res["main"]
    # second start: the first configuration again after
    # `jax.clear_caches()`, so nothing this process compiled is still at
    # hand in memory: a program is read back from the persistent cache
    # directory or compiled again. First request only.
    import jax

    from dynamo_tpu.utils import compile_cache

    jax.clear_caches()
    label, flags, _ = ONE_CHIP_CONFIGS[0]
    warm = await serve_once(
        flags=COMMON_FLAGS + flags + ["--attn-backend", "auto"],
        label=f"{label}/warm", require_compiled_pallas=True, first_only=True,
        **prepare_model(workdir, "llama-3.2-1b", LLAMA_32_1B))
    emit({"phase": "start", "label": label,
          "cache_dir": compile_cache.resolve_dir(),
          "first_start_s": round(cold["start_s"], 3),
          "first_engine_compiles": cold["compiles"],
          "second_start_s": round(warm["start_s"], 3),
          "second_start_compiles": warm["compiles"]})
    if warm["compiles"]["persistent_cache_hits"] <= 0:
        raise SmokeFailure("second start read no program back from the "
                           f"compile cache at {compile_cache.resolve_dir()}")
    if warm["ids"][0] != cold["ids"][0]:
        # one request alone in the engine: same program, same inputs
        raise SmokeFailure("second start: the first request's greedy ids "
                           "differ from the first start's")


def check_spread(label: str, before: list[dict], after: list[dict]) -> None:
    """Weights + KV pools of a tp engine must land about evenly on its
    devices: the largest per-device growth within 1.25x of the smallest."""
    grew = [a["bytes_in_use"] - b["bytes_in_use"] for a, b in zip(after, before)]
    emit({"phase": "spread", "label": label, "bytes_grown_per_device": grew})
    if min(grew) <= 0 or max(grew) > 1.25 * min(grew):
        raise SmokeFailure(f"{label}: uneven spread over devices: {grew}")


async def four_chips(workdir: str) -> None:
    """tp=4 against tp=1, Llama-3.1-8B widths: `--tp 4` as the CLI gives
    it (GSPMD + shard_map'd kernels), then the manual ring executor
    (`tp_overlap`, parallel/tp_overlap.py), each compared with one tp=1
    engine on the first chip."""
    import jax

    hf = {**LLAMA_31_8B, "num_hidden_layers": TP_LAYERS}
    check_against_preset(hf, "llama-3.1-8b")
    emit({"phase": "depth_cut", "model": "llama-3.1-8b",
          "layers": TP_LAYERS, "of": LLAMA_31_8B["num_hidden_layers"],
          "why": "the tp=1 reference must fit one 16 GB chip; widths uncut"})
    common = prepare_model(workdir, "llama-3.1-8b", hf)
    flags = COMMON_FLAGS + ["--page-size", "64", "--attn-backend", "auto"]
    ring_args = os.path.join(workdir, "ring.json")
    with open(ring_args, "w") as f:
        json.dump({"tp_overlap": True}, f)

    before = _device_memory(jax.devices())
    gspmd = await serve_once(
        flags=flags + ["--tp", "4"], label="tp4/gspmd",
        require_compiled_pallas=True, **common)
    check_spread("tp4/gspmd", before, gspmd["report"]["memory_after_load"])
    ring = await serve_once(
        flags=flags + ["--tp", "4", "--extra-engine-args", ring_args],
        label="tp4/ring", require_compiled_pallas=True, **common)
    check_spread("tp4/ring", before, ring["report"]["memory_after_load"])
    if ring["metrics"]["tp_overlap_dispatches"] <= 0:
        raise SmokeFailure("tp4/ring: the ring executor served no dispatch")
    ref = await serve_once(
        flags=flags + ["--tp", "1"], label="tp1/ref",
        require_compiled_pallas=True, top_logprobs=COMPARE_TOP, **common)
    compare_ids("tp4_gspmd_vs_tp1", gspmd, ref, "bf16", 64)
    compare_ids("tp4_ring_vs_tp1", ring, ref, "bf16", 64)


# ---------------------------------------------------------------------- main


def require_tpu(chips: int) -> dict:
    """Refuse to run anywhere but on the accelerator: no result line."""
    import jax

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if device["platform"] != "tpu":
        raise SystemExit(
            f"chip_smoke: JAX found no TPU (devices: {device}); this script "
            "has no CPU mode")
    if device["count"] != chips:
        raise SystemExit(
            f"chip_smoke: --chips {chips} but JAX sees {device['count']} "
            "devices")
    return device


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=[1, 4], default=1,
                    help="4 runs ONLY the tensor-parallel phase (tp=4 "
                         "against tp=1); the driver never passes it")
    opts = ap.parse_args(argv)
    t0 = time.perf_counter()
    device = require_tpu(opts.chips)
    emit({"phase": "device", **device})
    workdir = tempfile.mkdtemp(prefix="chip_smoke_")
    try:
        phase = one_chip if opts.chips == 1 else four_chips
        asyncio.run(phase(workdir))
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    emit({"phase": "done", "wall_s": round(time.perf_counter() - t0, 1)})
    emit({"ok": True, "device": device})
    return 0


if __name__ == "__main__":
    sys.exit(main())
