"""Concurrency-sweep serving benchmark — the reference's protocol
(reference: examples/llm/benchmarks/README.md:27-34 — genai-perf sweep,
concurrency 1..256) against the local chip. Reuses bench.py's engine
setup per point; writes SWEEP.json at the repo root and prints a table.

Run: python scripts/sweep.py [conc ...]   (default 1 4 16 64 128)
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_point(conc: int) -> dict:
    # prepend (not replace) PYTHONPATH so the caller's entries survive
    pp = os.environ.get("PYTHONPATH", "")
    env = dict(
        os.environ,
        BENCH_CONCURRENCY=str(conc),
        BENCH_FAST="1",  # headline + prefix probe per point
        PYTHONPATH=f"{REPO}:{pp}" if pp else REPO,
    )
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py")],
        env=env, capture_output=True, text=True, timeout=2400,
    )
    lines = [ln for ln in out.stdout.strip().splitlines() if ln.startswith("{")]
    if not lines:
        raise RuntimeError(
            f"bench conc={conc} produced no JSON (rc={out.returncode}):\n"
            f"{out.stdout[-2000:]}\n{out.stderr[-2000:]}"
        )
    return json.loads(lines[-1])


def main() -> None:
    concs = [int(a) for a in sys.argv[1:]] or [1, 4, 16, 64, 128]
    points = []
    print(f"{'conc':>5} {'decode tok/s':>13} {'total tok/s':>12} "
          f"{'p50 TTFT s':>11} {'p50 ITL ms':>11}")
    for conc in concs:
        r = run_point(conc)
        e = r["extra"]
        points.append({
            "concurrency": conc,
            "decode_toks_per_s_chip": r["value"],
            "total_toks_per_s_chip": e["total_toks_per_sec_chip"],
            "p50_ttft_s": e["p50_ttft_s"],
            "p50_itl_s": e["p50_itl_s"],
            "vs_baseline": r["vs_baseline"],
        })
        print(f"{conc:>5} {r['value']:>13.1f} "
              f"{e['total_toks_per_sec_chip']:>12.1f} "
              f"{e['p50_ttft_s']:>11.3f} {e['p50_itl_s'] * 1e3:>11.2f}")
    extra = {}
    sweep_path = os.path.join(REPO, "SWEEP.json")
    if os.path.exists(sweep_path):
        try:
            prev = json.load(open(sweep_path))
            extra = {
                k: v for k, v in prev.items()
                if k not in ("metric", "protocol", "points")
            }
        except Exception:
            pass
    record = {
        **extra,
        "metric": points and points[-1] or {},
        "protocol": {
            "isl": int(os.environ.get("BENCH_ISL", "512")),
            "osl": int(os.environ.get("BENCH_OSL", "64")),
            "quant": os.environ.get("BENCH_QUANT", "int8"),
        },
        "points": points,
    }
    with open(os.path.join(REPO, "SWEEP.json"), "w") as f:
        json.dump(record, f, indent=1)
    print("wrote SWEEP.json")


if __name__ == "__main__":
    main()
