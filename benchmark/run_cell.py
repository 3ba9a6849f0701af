#!/usr/bin/env python3
"""One run of one benchmark cell; see benchmark/README.md.

    python benchmark/run_cell.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

The last line of stdout is the result. Without a TPU (or with fewer
chips than the cell asks for) it exits non-zero and prints no result,
unless `--rehearse` (tiny model on the CPU, counts only).
"""
import time

T_PROC0 = time.monotonic()  # set-up is counted from here

import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "lib"))

if __name__ == "__main__":
    import harness

    sys.exit(harness.main(T_PROC0))
