"""dynamo-run CLI equivalent (`python -m dynamo_tpu.run`): the in=/out=
matrix surface (reference: launch/dynamo-run — main.rs in/out enums,
input/batch.rs batch driver)."""

from __future__ import annotations

import json
import subprocess
import sys

from dynamo_tpu.run import build_engine_config_kwargs, build_parser, parse_io


def test_parse_io_matrix():
    assert parse_io(["in=http", "out=jax"]) == ("http", "jax")
    assert parse_io(["out=dyn://ns.c.e", "in=text"]) == ("text", "dyn://ns.c.e")
    assert parse_io([]) == ("http", "echo_full")  # defaults
    try:
        parse_io(["bogus"])
        raise AssertionError("expected SystemExit")
    except SystemExit:
        pass


def test_engine_kwargs_from_flags():
    args = build_parser().parse_args(
        ["in=http", "out=jax", "--tp", "2", "--page-size", "64",
         "--max-batch-size", "128", "--attn-backend", "pallas",
         "--host-kv-pages", "32"]
    )
    kw = build_engine_config_kwargs(args)
    assert kw["mesh"].tp == 2
    assert kw["page_size"] == 64
    assert kw["max_batch_size"] == 128
    assert kw["attn_backend"] == "pallas"
    assert kw["host_kv_pages"] == 32


def test_batch_mode_end_to_end(tmp_path):
    """in=batch:file out=echo_full as a real subprocess: prompts in,
    outputs + latency summary out (reference input/batch.rs)."""
    prompts = tmp_path / "prompts.jsonl"
    with open(prompts, "w") as f:
        for text in ("alpha bravo", "charlie"):
            f.write(json.dumps({"text": text}) + "\n")
    proc = subprocess.run(
        [sys.executable, "-m", "dynamo_tpu.run",
         f"in=batch:{prompts}", "out=echo_full", "--max-tokens", "8"],
        capture_output=True, text=True, timeout=120,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin",
             "PYTHONPATH": "/root/repo", "HOME": "/root"},
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "batch done: n=2" in proc.stdout
    out_lines = [
        json.loads(line)
        for line in open(str(prompts) + ".out.jsonl")
    ]
    assert [o["input"] for o in out_lines] == ["alpha bravo", "charlie"]
    assert all(o["output"] for o in out_lines)


