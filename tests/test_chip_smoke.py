"""chip_smoke.py cannot run here (no accelerator, and it has no CPU
mode), so its phases are imported and driven as functions on a tiny model
— the smoke cannot rot between chip runs — and its refusals are checked:
no TPU, a failed phase and a failed comparison all exit non-zero without
the `"ok": true` line. Plus the two bring-up repairs that sit beside it:
the supervisor's chip allocator stays off jax, and the compile-cache
helper honours `JAX_COMPILATION_CACHE_DIR`.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

import chip_smoke as cs

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# Llama-3.2-1B's config.json cut to a toy: KH*Hd = 128 keeps the folded KV
# width lane-aligned, so the pallas kernels (interpret mode here) serve it
TINY_HF = {
    **cs.LLAMA_32_1B,
    "vocab_size": 512, "hidden_size": 128, "intermediate_size": 256,
    "num_hidden_layers": 2, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 64,
    "max_position_embeddings": 2048,
}


@pytest.mark.parametrize("label,flags,rule", cs.ONE_CHIP_CONFIGS,
                         ids=[c[0] for c in cs.ONE_CHIP_CONFIGS])
async def test_serve_and_compare_phase_on_tiny_model(tmp_path, label, flags,
                                                     rule):
    """The one-chip phase end to end — model dir + tokenizer from the
    seed, `in=http out=jax` service, real HTTP traffic (plain, SSE,
    logprobs, concurrent batch over several pages and two prefill
    chunks, repeated prompt, /metrics), pallas against gather, the
    comparison rule — at a size the CPU finishes in under a minute."""
    res = await cs.serve_and_compare(
        str(tmp_path), "tiny-llama", TINY_HF,
        cs.COMMON_FLAGS + flags + ["--dtype", "float32"], label,
        main_flags=["--attn-backend", "pallas"],
        ref_flags=["--attn-backend", "gather"], rule=rule,
        require_compiled_pallas=False, report_memory=False,
        # with the template's 8 tokens: 63 ends one short of a page of
        # 64, 128 fills pages of 64 and of 128 exactly
        prompt_tokens=[5, 55, 120, 530],
    )
    v = res["verdict"]
    assert v["ok"]
    # the continuous check ran on the batch and the repeat, and a decode
    # step into a newly allocated page lay inside a verified prefix
    assert v["logprob_gap"]["positions"] >= 5 * cs.COMPARE_WINDOW // 2
    # f32 on both sides here; with int8 even f32-sized differences of
    # arithmetic order flip 8-bit roundings downstream
    assert v["logprob_gap"]["max"] < (1e-3 if rule == "bf16" else 5e-2)
    assert v["page_crossings_verified_in_requests"]
    assert res["main"]["report"]["attention"]["kind"] == "pallas"
    assert res["main"]["report"]["attention"]["interpret"]  # CPU
    assert res["ref"]["report"]["attention"]["kind"] == "gather"
    n_req = 3 + 4 + 1
    assert len(res["main"]["ids"]) == len(res["ref"]["ids"]) == n_req
    assert res["main"]["metrics"]["prefix_reused_tokens"] > 0
    if "int8" in label:
        assert res["main"]["report"]["attention"]["kv_packed"]


async def test_auto_backend_on_cpu_fails_the_pallas_requirement(tmp_path):
    """`attn_backend=auto` resolves to gather off-TPU; the smoke's
    assertion on what the engine reports must catch exactly that."""
    with pytest.raises(cs.SmokeFailure, match="expected compiled pallas"):
        await cs.serve_once(
            flags=cs.COMMON_FLAGS + ["--attn-backend", "auto"],
            label="auto-on-cpu", require_compiled_pallas=True,
            report_memory=False,
            **cs.prepare_model(str(tmp_path), "m", TINY_HF, [5]),
        )


def test_published_configs_match_presets():
    cs.check_against_preset(cs.LLAMA_32_1B, "llama-3.2-1b")
    cs.check_against_preset(cs.LLAMA_31_8B, "llama-3.1-8b")
    cs.check_against_preset(
        {**cs.LLAMA_31_8B, "num_hidden_layers": cs.TP_LAYERS}, "llama-3.1-8b")
    with pytest.raises(cs.SmokeFailure):
        cs.check_against_preset(
            {**cs.LLAMA_32_1B, "hidden_size": 1024}, "llama-3.2-1b")


def test_full_size_tokenizer_round_trips_every_id(tmp_path):
    """Random weights sample ids across all 128,256: each must decode to
    a distinct word that maps back (an id the tokenizer does not know
    decodes to nothing — that is what tests/data's 68-word tokenizer
    does with large ids)."""
    from dynamo_tpu.llm.tokenizer import HuggingFaceTokenizer

    words = cs.write_model_dir(str(tmp_path), cs.LLAMA_32_1B, cs.SEED)
    assert len(set(words)) == len(words) == 128256
    tok = HuggingFaceTokenizer.from_file(str(tmp_path))
    ids = [0, 3, 4, 77777, 128000, 128009, 128255]
    assert tok.decode(ids).split() == [words[i] for i in ids]
    assert tok.eos_token_ids() == [128009]
    small = HuggingFaceTokenizer.from_file(
        os.path.join(REPO, "tests/data/tiny-trained-llama"))
    assert small.decode([77777]) == ""
    # same seed, same vocabulary
    assert cs.make_vocab(128256, cs.SEED) == words


def _answers(ids, lp=-0.1, n_prompt=63):
    """A `run_requests` result as `compare_ids` reads it: every chosen
    token at logprob `lp`, token t + 100 listed 0.05 nats below it."""
    return {"ids": ids, "prompt_tokens": [n_prompt] * len(ids),
            "lps": [[lp] * len(row) for row in ids],
            "tops": [[{t: lp, t + 100: lp - 0.05} for t in row]
                     for row in ids]}


@pytest.mark.parametrize("case,got,kw,ok", [
    ("same", [[1, 2, 3, 4], [5, 6, 7, 8]], {}, True),
    # 103 is listed 0.05 nats down: a near-tie
    ("near", [[1, 2, 103, 9], [5, 6, 7, 8]], {}, True),
    # 400 is not listed at all; the int8 numbers let ONE request miss
    # (a flat top on random weights), the bf16 numbers none
    ("one-off-int8", [[1, 2, 400, 4], [5, 6, 7, 8]], {"rule": "int8"}, True),
    ("one-off-bf16", [[1, 2, 400, 4], [5, 6, 7, 8]], {}, False),
    ("garbage", [[1, 2, 400, 4], [5, 401, 7, 8]], {"rule": "int8"}, False),
    # near-ties at token 0: nothing was compared
    ("early", [[101, 2, 3, 4], [105, 6, 7, 8]], {}, False),
    # same ids, but the served logprobs sit 0.3 nats off the reference's:
    # a scale error that flips no token
    ("gap", [[1, 2, 3, 4], [5, 6, 7, 8]], {"lp": -0.4}, False),
    # no decode step into a new page inside a verified prefix
    ("no-crossing", [[1, 2, 3, 4], [5, 6, 7, 8]], {"n_prompt": 70}, False),
])
def test_compare_rule(capsys, case, got, kw, ok):
    ref = _answers([[1, 2, 3, 4], [5, 6, 7, 8]])
    main = _answers(got, lp=kw.get("lp", -0.1),
                    n_prompt=kw.get("n_prompt", 63))
    rule = kw.get("rule", "bf16")
    if ok:
        v = cs.compare_ids(case, main, ref, rule, 64)
        assert v["ok"] and v["page_crossings_verified_in_requests"]
    else:
        with pytest.raises(cs.SmokeFailure):
            cs.compare_ids(case, main, ref, rule, 64)
    line = json.loads(capsys.readouterr().out.splitlines()[-1])
    assert line["ok"] is ok and line["numbers"] == cs.COMPARE_RULES[rule]


def test_compare_judges_the_mismatch_by_the_reference(capsys):
    """A listed token too far down fails; the gap is taken at the first
    mismatch too, where the reference lists the served token."""
    ref = _answers([[1, 2, 3, 4], [5, 6, 7, 8]])
    for row in ref["tops"]:
        for alts in row:
            for t in alts:
                if t >= 100:
                    alts[t] = -2.0
    with pytest.raises(cs.SmokeFailure):
        cs.compare_ids("far", _answers([[1, 2, 103, 9], [5, 6, 7, 8]]),
                       ref, "bf16", 64)
    v = json.loads(capsys.readouterr().out.splitlines()[-1])
    assert v["mismatches"][0]["ref_logprob_deficit"] == 1.9
    # positions 0..2 of request 0 (the mismatch included) + 4 of request 1
    assert v["logprob_gap"]["positions"] == 7
    assert v["logprob_gap"]["max"] == 1.9


def test_main_exits_nonzero_without_an_accelerator():
    """As the driver runs it in the sandbox: no TPU, no result line."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        capture_output=True, text=True, timeout=120, cwd=REPO,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "no TPU" in proc.stderr


@pytest.mark.parametrize("chips", [1, 4])
def test_main_last_line_is_the_contract_line(monkeypatch, capsys, chips):
    """Every phase passing, the LAST stdout line is exactly the contract's
    object with the device as jax reported it, and `--chips 4` runs the
    four-chip phase and no other."""
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": chips}
    monkeypatch.setattr(cs, "require_tpu", lambda n: device)
    ran = []

    def phase(name):
        async def run(workdir):
            assert os.path.isdir(workdir)
            ran.append(name)
        return run

    monkeypatch.setattr(cs, "one_chip", phase("one_chip"))
    monkeypatch.setattr(cs, "four_chips", phase("four_chips"))
    assert cs.main(["--chips", str(chips)] if chips == 4 else []) == 0
    assert ran == ["four_chips" if chips == 4 else "one_chip"]
    last = capsys.readouterr().out.splitlines()[-1]
    assert json.loads(last) == {"ok": True, "device": device}


def test_broken_phase_makes_main_fail(monkeypatch, capsys):
    """No phase's failure is swallowed: with the device check stubbed
    out and the phase raising, main() raises (a non-zero exit) and the
    `"ok": true` line is never printed."""
    monkeypatch.setattr(cs, "require_tpu", lambda chips: {
        "platform": "tpu", "kind": "stub", "count": chips})

    async def broken(workdir):
        raise cs.SmokeFailure("deliberately broken phase")

    monkeypatch.setattr(cs, "one_chip", broken)
    with pytest.raises(cs.SmokeFailure, match="deliberately broken"):
        cs.main([])
    assert '"ok"' not in capsys.readouterr().out


@pytest.mark.parametrize("hits,second_ids,fails", [
    (40, [1, 2], None),
    (0, [1, 2], "read no program back"),
    (40, [1, 3], "differ"),
], ids=["ok", "no-cache-reads", "different-ids"])
async def test_one_chip_phase_tail(monkeypatch, tmp_path, capsys,
                                   hits, second_ids, fails):
    """The part of the one-chip phase after the two comparisons — the
    second start — with the serving stubbed out: jax's in-memory caches
    are dropped first, both start times are reported, and it fails when
    the second start read nothing from the persistent cache or answers
    the first request differently."""
    import jax

    calls = []

    async def fake_compare(workdir, preset, hf, flags, label, **kw):
        calls.append(label)
        return {"main": {"start_s": 40.0, "ids": [[1, 2], [7]],
                         "compiles": {"persistent_cache_hits": 0}}}

    async def fake_serve_once(**kw):
        assert calls[-1] == "caches cleared"
        assert kw["first_only"] and kw["require_compiled_pallas"]
        return {"start_s": 5.0, "ids": [second_ids],
                "compiles": {"persistent_cache_hits": hits}}

    monkeypatch.setattr(cs, "serve_and_compare", fake_compare)
    monkeypatch.setattr(cs, "serve_once", fake_serve_once)
    monkeypatch.setattr(cs, "prepare_model", lambda *a, **kw: {})
    monkeypatch.setattr(jax, "clear_caches",
                        lambda: calls.append("caches cleared"))
    if fails:
        with pytest.raises(cs.SmokeFailure, match=fails):
            await cs.one_chip(str(tmp_path))
    else:
        await cs.one_chip(str(tmp_path))
    assert calls == [c[0] for c in cs.ONE_CHIP_CONFIGS] + ["caches cleared"]
    start = json.loads(capsys.readouterr().out.splitlines()[-1])
    assert start["phase"] == "start"
    assert start["first_start_s"] == 40.0 and start["second_start_s"] == 5.0
    assert start["second_start_compiles"]["persistent_cache_hits"] == hits


def test_supervisor_allocator_never_initialises_jax():
    """A chip belongs to one process: the supervisor (parent of every
    worker) must count chips without importing jax. Runs the real
    detection path in a fresh interpreter."""
    code = (
        "import sys\n"
        "from dynamo_tpu.sdk.supervisor import Supervisor\n"
        "from dynamo_tpu.sdk.allocator import TpuAllocator, detect_num_chips\n"
        "a = TpuAllocator()\n"
        "assert a.total_chips is None  # lazy: no probe until a chip is asked for\n"
        "assert a.assign(0) == []\n"
        "print('chips', detect_num_chips(), a.assign(1))\n"
        "assert 'jax' not in sys.modules and 'jaxlib' not in sys.modules, "
        "sorted(m for m in sys.modules if m.startswith('jax'))\n"
    )
    env = {k: v for k, v in os.environ.items()
           if k not in ("DYN_TPU_NUM_CHIPS", "JAX_PLATFORMS")}
    # unpinned: the probe takes the child-process route, and the child
    # reports the CPU = 0 chips
    proc = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=300, cwd=REPO, env={**env, "PYTHONPATH": REPO},
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert proc.stdout.split() == ["chips", "0", "None"]


def test_allocator_probe_failure_is_loud(monkeypatch):
    """"The chip is busy" must not read as "this host has no chips"."""
    from dynamo_tpu.sdk import allocator

    monkeypatch.delenv("DYN_TPU_NUM_CHIPS", raising=False)
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    monkeypatch.setattr(allocator, "_PROBE", "raise SystemExit('chip busy')")
    with pytest.raises(RuntimeError, match="chip busy"):
        allocator.detect_num_chips()
    monkeypatch.setenv("DYN_TPU_NUM_CHIPS", "4")
    assert allocator.detect_num_chips() == 4


def test_compile_cache_dir_resolution(monkeypatch, tmp_path):
    import jax

    from dynamo_tpu.utils import compile_cache

    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    fixed = os.path.join(REPO, ".jax_cache")
    assert compile_cache.resolve_dir() == fixed
    assert compile_cache.resolve_dir() == fixed  # no pid/time/temp part
    before = jax.config.jax_compilation_cache_dir
    # on the CPU backend (tests) the fixed path is resolved, not applied
    assert compile_cache.configure() == fixed
    assert jax.config.jax_compilation_cache_dir == before
    # on an accelerator it is applied
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    try:
        assert compile_cache.configure() == fixed
        assert jax.config.jax_compilation_cache_dir == fixed
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    # placed from outside: the code sets nothing
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    assert compile_cache.resolve_dir() == str(tmp_path)
    assert compile_cache.configure() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_env_var_is_what_jax_uses(tmp_path):
    """With the variable set, jax itself picks the directory up at
    import and engine construction leaves it alone."""
    code = (
        "import jax\n"
        "from dynamo_tpu.utils import compile_cache\n"
        "compile_cache.configure()\n"
        "print(jax.config.jax_compilation_cache_dir)\n"
    )
    proc = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=120, cwd=REPO,
        env={**os.environ, "PYTHONPATH": REPO, "JAX_PLATFORMS": "cpu",
             "JAX_COMPILATION_CACHE_DIR": str(tmp_path)},
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert proc.stdout.strip() == str(tmp_path)
