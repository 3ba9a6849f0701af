"""End-to-end metric `tpot_p95_ms`: the arithmetic is in lib/e2e.py."""
import e2e


def read(art):
    return e2e.metrics(art)["tpot_p95_ms"]
