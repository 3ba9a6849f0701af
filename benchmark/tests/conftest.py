"""The benchmark's own tests: `python -m pytest benchmark/tests -q` from
the root of the repo. They run on the CPU (`--rehearse`); nothing here
produces a device number."""
import json
import os
import subprocess
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, os.path.join(BENCH, "lib"))


def run_cell(root: str, *argv: str, timeout: int = 900):
    """Run `<root>/benchmark/run_cell.py` as the driver would; returns
    (returncode, last stdout line parsed or None, stderr)."""
    env = {**os.environ, "PYTHONPATH": ROOT}
    p = subprocess.run(
        [sys.executable, os.path.join(root, "benchmark", "run_cell.py"), *argv],
        cwd=root, env=env, capture_output=True, text=True, timeout=timeout)
    lines = p.stdout.strip().splitlines()
    last = json.loads(lines[-1]) if lines and lines[-1].startswith("{") else None
    return p.returncode, last, p.stderr


@pytest.fixture(scope="session")
def bench_json():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)
