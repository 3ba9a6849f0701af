"""One run of one cell: build the server through the program's own entry
point, drive it over localhost HTTP from a child process, judge from the
client's clock, print one JSON line.

    set-up    model dir + tokenizer from the seed -> run.build_http_service
              -> probes (every program shape the cell's traffic can reach,
              see `probe_steps`) -> `correct` (four prompts against the
              float32 reference) -> the traffic's own warm-up stretch
    window    `--seconds` of the cell's traffic at the cell's fixed load
    after     request log + engine finish summaries + flight-recorder
              digests (+ a profiler trace of a slice of the window with
              `--trace 1`) -> metrics -> the final line

Everything particular to a configuration, a traffic mix, a cell or a
per-layer metric is a file found by its name in BENCHMARK.json:
configs/<config>.json, traffic/<mix>.json, cells/<cell>.json,
layer_metrics/<metric>.py. This file has no `if` on any of those names.
"""

from __future__ import annotations

import argparse
import asyncio
import gc
import importlib.util
import json
import logging
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, HERE)

import modeldir  # noqa: E402

# keys of a configuration file that are the benchmark's, not config.json's
BENCH_KEY = "benchmark"
# the four prompts `correct` is judged on (whole prompt tokens): two under
# one page (so they share their programs), one just past a 512-token
# prefill chunk, one past two chunks
CHECK_PROMPTS = (40, 120, 520, 1100)
CHECK_TOKENS = 16
# --rehearse: the same run at a size the CPU finishes in a minute
REHEARSAL_MODEL = {
    "vocab_size": 512, "hidden_size": 128, "intermediate_size": 256,
    "num_hidden_layers": 2, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 64,
}
REHEARSAL_LENGTH_SCALE = 0.125


class Refusal(RuntimeError):
    """The run cannot be made as asked (no chip, unknown device, unknown
    name). Exit non-zero, print no result line."""


_T0 = time.monotonic()


def log(msg: str) -> None:
    print(f"[bench {time.monotonic() - _T0:8.2f}] {msg}", file=sys.stderr,
          flush=True)


def load_json(*parts: str) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def find(entries: list[dict], name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise Refusal(f"BENCHMARK.json has no {what} named {name!r}")


# ------------------------------------------------------------------ set-up


def scale_lengths(mix: dict, k: float) -> dict:
    """The mix with every token length multiplied by k (rehearsals)."""
    def scaled(spec):
        if isinstance(spec, int):
            return max(int(spec * k), 2)
        return {key: (max(int(v * k), 2)
                      if key in ("min", "max", "median", "value") else v)
                for key, v in spec.items()}

    out = dict(mix)
    for key in ("prompt_tokens", "output_tokens"):
        out[key] = scaled(mix[key])
    out["prompt_tokens"]["min"] = max(
        out["prompt_tokens"].get("min", 0), modeldir.TEMPLATE_TOKENS + 4)
    if (mix.get("sharing") or {}).get("prefix_groups"):
        out["sharing"] = {**mix["sharing"], "prefix_tokens":
                          scaled(mix["sharing"]["prefix_tokens"])}
    return out


def _longest(spec) -> int:
    if isinstance(spec, int):
        return spec
    return int(spec.get("max", spec.get("value", 0)))


def probe_steps(mix: dict, eng: dict, rows_max: int,
                width_max: int = 0) -> list[list[dict]]:
    """Requests that make the engine compile, during set-up, every step
    program the mix can reach — the engine has no call that compiles a
    list of shapes, so each shape is reached by traffic built for it.

    Prefill programs are keyed by (rows padded to a power of two, chunk
    bucket, attended-page bucket); decode programs by the batch width
    (power of two from 8). `eng` carries page_size, prefill_chunk,
    prefill_group_tokens, max_batch and max_model_len as the engine
    runs them; `rows_max` is the most slots the cell's load holds when
    nothing disturbs it; `width_max` (>= rows_max) is the widest decode
    program loaded at all. The width is the power of two over the highest
    occupied SLOT, and waiting requests hold slots: in an open loop a
    host stall of a few seconds pushes it past `rows_max`, and a decode
    program first met then stalls the engine for as long as it takes to
    load (7 s) or compile (22 s), which fills the next width in turn.
    - one row: a prompt of 512*m + b tokens ends in a chunk of bucket b
      attending pow2(ceil(L/page)) pages, and passes every full-chunk
      program on the way;
    - n rows: n such prompts sent together advance in lockstep (one
      chunk per tick under the group budget) and end in one (n, b)
      dispatch; single-chunk prompts are held back behind a long
      "blocker" prompt so that all n are queued when their tick comes;
    - decode width w: w/2 + 1 short prompts at once; the engine holds
      decode until the whole wave has prefilled.
    - rows joining a running batch: see the last loop below.
    """
    ps, chunk = eng["page_size"], eng["prefill_chunk"]
    budget = eng["prefill_group_tokens"]
    share = mix.get("sharing") or {}
    longest = min(_longest(mix["prompt_tokens"])
                  + (_longest(share["prefix_tokens"])
                     if share.get("prefix_groups") else 0),
                  eng["max_model_len"] - _longest(mix["output_tokens"]))
    shortest = modeldir.TEMPLATE_TOKENS + 1
    buckets, b = [], max(ps, 16)
    while b < chunk:
        buckets.append(b)
        b *= 2
    buckets.append(chunk)

    def pow2(n):
        return 1 << max(n - 1, 0).bit_length()

    # one prompt length per (final-chunk bucket, page bucket)
    lengths: dict[tuple[int, int], int] = {}
    for b in buckets:
        m = 0
        while chunk * m + shortest <= longest:
            for r in (b, b // 2 + 1):
                length = chunk * m + r
                if shortest <= length <= longest:
                    lengths.setdefault((b, pow2(-(-length // ps))), length)
            m += 1
    one = {"output_tokens": 2}
    steps = [[{**one, "prompt_tokens": length}]
             for _, length in sorted(lengths.items())]
    n = 2
    while n * buckets[0] <= budget:
        for (b, _), length in sorted(lengths.items()):
            if n * b > budget:
                continue
            group = [{**one, "prompt_tokens": length, "delay_s": 0.06}
                     for _ in range(n)]
            if length <= chunk:
                group.insert(0, {**one, "prompt_tokens": longest})
            steps.append(group)
        n *= 2
    # waves of 1, 2, 4, 8 rows (width 8), then width/2 + 1 rows for each
    # wider program: besides the decode programs this reaches the
    # power-of-two padded state-flush programs (eleven small ones per
    # count of changed slots) that a wave's first decode dispatch runs
    short = {"prompt_tokens": shortest + 8,
             "output_tokens": 2 + eng["decode_steps"]}
    steps += [[short] * rows for rows in (1, 2, 4, 8)]
    width = 16
    while width // 2 < min(max(rows_max, width_max), eng["max_batch"]):
        rows = width // 2 + 1
        steps.append([short] * rows)
        # the same rows again, decoding for some twenty dispatches while 1,
        # 2, 4, ... short prompts join them, as many as one tick's budget
        # prefills together: the carry-override scatter is a program per
        # (decode width x rows that became ready in one tick, padded to a
        # power of two), and paced arrivals meet it inside a window. Past
        # the load's own rows the decode program alone is loaded
        joiners, k = [], 1
        while k * buckets[0] <= budget and len(joiners) + k < width // 2:
            joiners += [{**one, "prompt_tokens": shortest + 8,
                         "delay_s": 0.8 + 0.4 * len(joiners).bit_length()}] * k
            k *= 2
        if width // 2 < rows_max:
            steps.append([{**short, "output_tokens": 20 * eng["decode_steps"]}]
                         * rows + joiners)
        width *= 2
    return steps


class Child:
    """The load generator's process and its line protocol."""

    def __init__(self, proc):
        self.proc = proc

    @classmethod
    async def start(cls, plan_path: str) -> "Child":
        env = {k: v for k, v in os.environ.items()
               if not k.startswith(("JAX_", "XLA_", "TPU_"))}
        proc = await asyncio.create_subprocess_exec(
            sys.executable, os.path.join(HERE, "loadgen.py"), plan_path,
            stdin=asyncio.subprocess.PIPE, stdout=asyncio.subprocess.PIPE,
            env=env, limit=1 << 22)
        child = cls(proc)
        await child._answer(lambda ev: None)
        return child

    async def _answer(self, on_event) -> dict:
        while True:
            line = await self.proc.stdout.readline()
            if not line:
                raise RuntimeError("the load generator ended early")
            msg = json.loads(line)
            if msg.get("done"):
                if "error" in msg:
                    raise RuntimeError(f"load generator: {msg['error']}")
                return msg
            on_event(msg)

    async def call(self, cmd: dict, on_event=lambda ev: None) -> dict:
        self.proc.stdin.write((json.dumps(cmd) + "\n").encode())
        await self.proc.stdin.drain()
        msg = await self._answer(on_event)
        return load_json(msg["out"]) if "out" in msg else msg

    async def stop(self) -> None:
        if self.proc.returncode is None:
            try:
                self.proc.stdin.write(b'{"cmd": "quit"}\n')
                await self.proc.stdin.drain()
                await asyncio.wait_for(self.proc.wait(), 10)
            except (asyncio.TimeoutError, ConnectionError, OSError):
                self.proc.kill()
                await self.proc.wait()


class CompileNames(logging.Handler):
    """jax logs every program it compiles or reads back when
    `jax_log_compiles` is on; inside the window there should be none, and
    if there is one its name says which shape the probes missed."""

    def __init__(self):
        super().__init__(logging.DEBUG)
        self.names: list[str] = []

    def emit(self, record):
        msg = record.getMessage()
        if msg.startswith(("Finished XLA compilation", "Compiling",
                           "Persistent compilation cache hit")):
            self.names.append(msg[:160])


def watch_compiles(jax, handler: CompileNames, on: bool) -> None:
    jax.config.update("jax_log_compiles", on)
    for name in ("jax._src.dispatch", "jax._src.compiler",
                 "jax._src.interpreters.pxla"):
        lg = logging.getLogger(name)
        (lg.addHandler if on else lg.removeHandler)(handler)


class PreemptionCounter(logging.Handler):
    """The engine has no preemption counter; it logs each one."""

    def __init__(self):
        super().__init__(logging.INFO)
        self.stamps: list[float] = []

    def emit(self, record):
        if "preempting seq" in record.getMessage():
            self.stamps.append(time.monotonic())


def check_preset(hf: dict, preset: str) -> None:
    """The configuration's published values and the repo's preset must
    agree, depth apart (a configuration may cut it, and says so)."""
    from dynamo_tpu.models.config import PRESETS, ModelConfig

    got = ModelConfig.from_hf_config(hf, name=preset)
    want = PRESETS[preset].with_(num_layers=got.num_layers)
    if got != want:
        raise Refusal(f"{preset}: configuration {got} != preset {want}")


# --------------------------------------------------------------------- run


async def run(args, t_proc0: float) -> int:
    bench = load_json(ROOT, "BENCHMARK.json")
    cell = find(bench["workloads"], args.workload, "workload")
    config_entry = find(bench["configs"], cell["config"], "configuration")
    config = load_json(ROOT, config_entry["file"])
    mix = load_json(BENCH, "traffic", cell["traffic"] + ".json")
    cell_params = load_json(BENCH, "cells", cell["name"] + ".json")
    cb = config[BENCH_KEY]
    hf = {k: v for k, v in config.items() if k != BENCH_KEY}
    if args.rehearse:
        hf.update(REHEARSAL_MODEL)
        mix = scale_lengths(mix, REHEARSAL_LENGTH_SCALE)
        check_prompts = tuple(max(int(p * REHEARSAL_LENGTH_SCALE), 12)
                              for p in CHECK_PROMPTS)
    else:
        check_preset(hf, cb["preset"])
        check_prompts = CHECK_PROMPTS

    import jax

    if not args.rehearse:
        # keep every program in the persistent cache, not only those that
        # took a second to compile: a run after the first then compiles
        # nothing (the cache's place is the program's: compile_cache.py)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    devices = jax.devices()
    platform, kind = devices[0].platform, devices[0].device_kind
    if not args.rehearse and (platform != "tpu" or len(devices) < cell["chips"]):
        raise Refusal(f"cell needs {cell['chips']} TPU chip(s); jax sees "
                      f"{len(devices)} x {platform}")
    peaks = load_json(HERE, "peaks.json")
    if kind not in peaks and not args.rehearse:
        raise Refusal(f"device kind {kind!r} is not in peaks.json")

    work = os.path.join(ROOT, ".bench_work", cell["name"])
    shutil.rmtree(work, ignore_errors=True)
    os.makedirs(work)
    seed = int(args.seed)
    model_dir = os.path.join(work, "model")
    words = modeldir.write_model_dir(model_dir, hf, seed)
    words_file = os.path.join(work, "words.json")
    with open(words_file, "w") as f:
        json.dump(modeldir.usable_words(words), f)
    engine_args = {**cb["engine_args"], "seed": seed % (2 ** 31 - 1)}
    extra = os.path.join(work, "engine_args.json")
    with open(extra, "w") as f:
        json.dump(engine_args, f)

    # the flight recorder's ring must hold a whole window's digests
    os.environ.setdefault("DYN_FLIGHT_BUFFER", "400000")
    from dynamo_tpu.engine import telemetry
    from dynamo_tpu.run import build_http_service, build_parser

    argv = ["in=http", "out=jax", "--model-path", model_dir, "--model-name",
            cell["config"], "--http-host", "127.0.0.1",
            "--extra-engine-args", extra, *cb["engine_flags"]]
    log(f"building {' '.join(argv[2:])}")
    svc, engine = await build_http_service(build_parser().parse_args(argv), "jax")
    await svc.start("127.0.0.1", 0)
    child = None
    preempt = PreemptionCounter()
    logging.getLogger("dynamo_tpu.engine").addHandler(preempt)
    try:
        summaries: dict[str, dict] = {}
        engine.subscribe_requests(
            lambda s: summaries.__setitem__(s["request_id"], s))
        ecfg = engine.config
        eng = {
            "page_size": engine.page_size, "num_pages": engine.num_pages,
            "prefill_chunk": ecfg.prefill_chunk,
            "prefill_group_tokens": ecfg.prefill_group_tokens,
            "max_batch": ecfg.max_batch_size,
            "max_model_len": ecfg.max_model_len,
            "decode_steps": ecfg.decode_steps,
            "attention": engine.attention_backend,
            "param_count": engine.param_count,
        }
        log(f"engine up: {eng}")
        if not args.rehearse and (eng["attention"]["kind"] != "pallas"
                                  or eng["attention"]["interpret"]):
            raise Refusal(f"engine chose {eng['attention']}, not the "
                          "compiled pallas kernels")
        plan = os.path.join(work, "plan.json")
        with open(plan, "w") as f:
            json.dump({"base_url": f"http://127.0.0.1:{svc.port}",
                       "model": cell["config"], "mix": mix,
                       "words_file": words_file,
                       "template_tokens": modeldir.TEMPLATE_TOKENS}, f)
        child = await Child.start(plan)
        served_for_judge = asyncio.Event()

        # 1. `correct`, first half: four greedy continuations with their
        # log-probabilities; the reference then runs beside the probes
        c_build = telemetry.compile_stats()
        judging = asyncio.create_task(judge(
            child, engine, hf, words, check_prompts, cb["tolerance"], seed,
            work, served_for_judge))
        await served_for_judge.wait()

        # 2. every program shape the mix can reach, compiled or read back
        steps = probe_steps(
            mix, eng,
            int(cell_params.get("decode_rows_max", cell_params.get(
                "clients", eng["max_batch"]))),
            int(cell_params.get("decode_width_max", 0)))
        res = await child.call({"cmd": "batch", "steps": steps, "seed": seed,
                                "tag": "probe",
                                "out": os.path.join(work, "probe.json")})
        bad = [r for r in res["requests"] if r["status"] != "ok"]
        if bad:
            raise RuntimeError(f"probe request failed: {bad[0]}")
        c_probe = telemetry.compile_stats()
        log(f"probes: {len(steps)} steps, compile stats {c_probe}")
        try:
            verdict = await judging
        except Exception as e:  # noqa: BLE001 — a run that cannot be
            # judged still reports its numbers, with correct = false
            verdict = {"ok": False, "why": f"{type(e).__name__}: {e}"[:500]}
        log(f"correct: {verdict}")

        if args.sweep:
            return await sweep(args, child, engine, summaries, cell, work,
                               platform, kind)

        # 3. the window
        marks: dict[str, float] = {}
        stats: dict[str, dict] = {}
        tracer = None
        compiled = CompileNames()

        def on_event(ev):
            marks[ev["event"]] = ev["t"]
            stats[ev["event"]] = telemetry.compile_stats()
            log(f"{ev['event']}")
            watch_compiles(jax, compiled, ev["event"] == "window_open")
            nonlocal tracer
            if ev["event"] == "window_open" and args.trace:
                tracer = asyncio.create_task(trace_slice(
                    work, ev["t"], float(args.seconds), mix))

        load = {"cmd": "load", "seconds": args.seconds, "seed": seed,
                "out": os.path.join(work, "load.json"), **{
                    k: cell_params[k] for k in ("rate_rps", "clients")
                    if k in cell_params}}
        result = await child.call(load, on_event)
        trace = await tracer if tracer is not None else None
        mem = devices[0].memory_stats() or {}
        art = {
            "cell": cell, "cell_params": cell_params, "config": config,
            "mix": mix, "engine": eng, "peaks": peaks.get(kind),
            "seconds": float(args.seconds),
            "window": result["window"], "cutoff_s": result["cutoff_s"],
            "requests": result["requests"], "summaries": summaries,
            "digests": window_digests(engine, result["window"]),
            "compile": {"before": stats["window_open"],
                        "after": stats["window_close"],
                        "build": c_build, "probe": c_probe,
                        "in_window": compiled.names},
            "preemptions": sum(result["window"][0] <= t < result["window"][1]
                               for t in preempt.stamps),
            "trace": trace, "setup_s": marks["window_open"] - t_proc0,
        }
        line = final_line(bench, art, args, verdict, {
            "platform": platform, "kind": kind, "count": cell["chips"],
            "memory_peak_bytes": int(mem.get("peak_bytes_in_use", 0)),
        })
        if args.keep:
            with open(os.path.join(work, "artefacts.json"), "w") as f:
                json.dump(art, f)
        await shutdown(child, svc, engine, jax)
        child = None
        print(json.dumps(line), flush=True)
        return 0
    finally:
        logging.getLogger("dynamo_tpu.engine").removeHandler(preempt)
        if child is not None:
            await shutdown(child, svc, engine, jax)


async def shutdown(child, svc, engine, jax) -> None:
    await child.stop()
    await svc.stop()
    await engine.close()
    for leaf in jax.tree.leaves((engine.params, engine.kv)):
        leaf.delete()
    gc.collect()


async def judge(child, engine, hf, words, prompts, tolerance, seed, work,
                served: asyncio.Event):
    """`correct`: the served log-probabilities of four greedy
    continuations against the float32 reference, teacher-forced. Sets
    `served` once the engine has answered (the child takes one command at
    a time); the reference's forward passes need no request."""
    import reference

    try:
        res = await child.call({
            "cmd": "batch", "seed": seed, "tag": "check",
            "out": os.path.join(work, "check.json"),
            "steps": [[{"prompt_tokens": p, "output_tokens": CHECK_TOKENS,
                        "logprobs": True}] for p in prompts]})
    finally:
        served.set()
    vocab = {w: i for i, w in enumerate(words)}
    lps, ref = [], []
    pad = -(-(max(prompts) + CHECK_TOKENS) // 128) * 128
    for r in res["requests"]:
        out_ids = [vocab[w] for w in r["text"].split()]
        if (r["status"] != "ok" or len(out_ids) != CHECK_TOKENS
                or len(r["logprobs"]) != CHECK_TOKENS):
            return {"ok": False, "why": f"bad check request: {r}"}
        ids = modeldir.prompt_ids(words, r["content"]) + out_ids
        if len(ids) != r["prompt_tokens"] + CHECK_TOKENS or (
                r.get("usage", {}).get("prompt_tokens") != r["prompt_tokens"]):
            return {"ok": False, "why": "prompt length differs from the "
                    f"engine's count: {r.get('usage')} vs {len(ids)}"}
        lps.append(r["logprobs"])
        ref.append(await asyncio.to_thread(
            reference.token_logprobs, engine.params, hf, ids,
            CHECK_TOKENS, pad))
    return reference.compare(lps, ref, tolerance)


async def trace_slice(work: str, t_open: float, seconds: float, mix: dict):
    """Profile a slice in the middle of the window; returns the reduced
    trace (None if the profiler gave nothing)."""
    import glob

    import jax

    import trace_reduce

    length = min(float(mix.get("trace_slice_s", 3.0)), seconds / 2)
    await asyncio.sleep(max(t_open + (seconds - length) / 2
                            - time.monotonic(), 0))
    out = os.path.join(work, "trace")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    await asyncio.to_thread(jax.profiler.start_trace, out,
                            profiler_options=opts)
    began = time.monotonic()
    await asyncio.sleep(length)
    ended = time.monotonic()
    await asyncio.to_thread(jax.profiler.stop_trace)
    files = glob.glob(os.path.join(out, "plugins/profile/*/*.xplane.pb"))
    if not files:
        return None
    log(f"trace: {files[0]} {os.path.getsize(files[0])} bytes")
    table = await asyncio.to_thread(trace_reduce.load, files[0])
    try:
        return {**trace_reduce.reduce(table), "slice": [began, ended]}
    except ValueError as e:  # no device plane: the CPU backend
        log(f"trace: {e}")
        return None


def window_digests(engine, window) -> list[dict]:
    """The flight recorder's per-step digests stamped inside the window,
    as dicts. Digests carry time.time(); the window is monotonic."""
    from dynamo_tpu.engine import flight_recorder as fr

    if engine.flight is None:
        return []
    shift = time.time() - time.monotonic()
    lo, hi = window[0] + shift, window[1] + shift
    rows = engine.flight.snapshot_rows()
    out = []
    for r in rows:
        d = dict(zip(fr.FIELDS, r))
        if lo <= d["ts_unix"] < hi:
            d["kind"] = fr.KINDS[int(d["kind"])] if d["kind"] >= 0 else "?"
            d["t"] = d["ts_unix"] - shift  # on the window's clock
            out.append(d)
    return out


# ----------------------------------------------------------------- metrics


def read_metric(folder: str, name: str, art: dict):
    """A metric is the file <folder>/<name>.py with one function
    `read(art)`; a reader that finds nothing to read returns None."""
    path = os.path.join(BENCH, folder, name + ".py")
    spec = importlib.util.spec_from_file_location(
        "layer_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(art)


def final_line(bench, art, args, verdict, device) -> dict:
    import e2e

    cell = art["cell"]
    rehearse = args.rehearse

    def wanted(m):
        return "workloads" not in m or cell["name"] in m["workloads"]

    counts = e2e.counts(art)
    groups = {}
    for group, folder in (("end_to_end", "e2e_metrics"),
                          ("per_layer", "layer_metrics")):
        groups[group] = {}
        for m in filter(wanted, bench[group]):
            v = read_metric(folder, m["name"], art)
            if v is None:
                continue
            if rehearse and m["unit"] not in ("count", "rows"):
                v = None  # a CPU run gives counts, never a time, rate or share
            groups[group][m["name"]] = {"value": v, "unit": m["unit"]}
    # the driver reads `metrics`: the group `--trace` asks for. The other
    # group rides along under `also` (without a trace its trace readers
    # have nothing to read), so that a builder's run tells all it can
    metrics = groups["per_layer" if args.trace else "end_to_end"]
    line = {
        "correct": bool(verdict["ok"]) and counts["attempted"] > 0,
        "attempted": counts["attempted"], "failed": counts["failed"],
        "metrics": metrics, "device": device,
        "also": groups["end_to_end" if args.trace else "per_layer"],
        "check": verdict, "counts": counts,
        "compiled_in_window": art["compile"]["in_window"],
    }
    trace = art["trace"]
    if args.trace and trace is not None and not rehearse:
        import trace_reduce

        device["busy_s"] = trace["busy_s"]
        device["window_s"] = trace["window_s"]
        line["breakdown"] = trace_reduce.breakdown(trace)
    return line


# ------------------------------------------------------------------- sweep


async def sweep(args, child, engine, summaries, cell, work, platform, kind):
    """Find the knee once: one warm engine, one window per rate. Prints
    one JSON line per rate; the last line is the list. Not a cell run."""
    import e2e

    rows = []
    for i, rate in enumerate(float(x) for x in args.sweep.split(",")):
        summaries.clear()
        res = await child.call({
            "cmd": "load", "seconds": args.seconds, "seed": int(args.seed) + i,
            "rate_rps": rate, "clients": int(rate),
            "out": os.path.join(work, f"sweep{i}.json")})
        art = {"requests": res["requests"], "window": res["window"],
               "cutoff_s": res["cutoff_s"], "seconds": float(args.seconds),
               "summaries": summaries,
               "digests": window_digests(engine, res["window"])}
        half = (res["window"][0] + res["window"][1]) / 2
        q = [[d["queue_depth"] for d in art["digests"]
              if (d["t"] < half) == first] for first in (True, False)]
        row = {"load": rate, **e2e.counts(art), **e2e.metrics(art),
               "queue_depth_mean_halves": [
                   sum(x) / len(x) if x else None for x in q]}
        rows.append(row)
        print(json.dumps(row), flush=True)
    print(json.dumps({"sweep": rows, "device": {
        "platform": platform, "kind": kind}}), flush=True)
    return 0


def main(t_proc0: float) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--seconds", type=float, default=None)
    p.add_argument("--trace", type=int, default=0, choices=(0, 1))
    p.add_argument("--rehearse", action="store_true",
                   help="tiny model on the CPU: counts only, times null")
    p.add_argument("--sweep", default=None,
                   help="comma-separated loads (req/s, or clients): one "
                        "window each on one warm engine, to find the knee")
    p.add_argument("--keep", action="store_true",
                   help="leave the run's artefacts under .bench_work/")
    args = p.parse_args()
    if args.seconds is None:
        args.seconds = load_json(ROOT, "BENCHMARK.json")["run_seconds"]
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path.insert(0, ROOT)
    try:
        return asyncio.run(run(args, t_proc0))
    except Refusal as e:
        print(f"refused: {e}", file=sys.stderr)
        return 3
