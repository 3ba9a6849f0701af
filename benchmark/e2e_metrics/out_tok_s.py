"""End-to-end metric `out_tok_s`: the arithmetic is in lib/e2e.py."""
import e2e


def read(art):
    return e2e.metrics(art)["out_tok_s"]
