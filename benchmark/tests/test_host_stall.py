"""`host_stall_max_ms` on hand-made digest lists: the largest `emit_s` of
a `sync` / `overlap` row, in ms; nothing (and nothing raised) where no
such row or no such column exists, as in a program from before PR 24."""
import pytest

import harness


def _row(kind, emit_s=None, **more):
    d = {"kind": kind, "rows": 128, "wall_s": 0.14, **more}
    if emit_s is not None:
        d["emit_s"] = emit_s
    return d


@pytest.mark.parametrize("digests,want", [
    # one stalled landing among ordinary ones: its size, not a median
    ([_row("decode", 0.0), _row("overlap", 0.012), _row("prefill", 0.0),
      _row("overlap", 0.38), _row("sync", 0.011)], 380.0),
    # a dispatch row's column is not a landing's
    ([_row("decode", 9.0), _row("sync", 0.004)], 4.0),
    # the columns PR 35 adds beside it change nothing
    ([_row("overlap", 0.02, frames=128, tokens=1024, gc_s=0.0)], 20.0),
    ([_row("decode", 0.0), _row("prefill", 0.0)], None),
    ([_row("decode"), _row("sync"), _row("overlap")], None),
    ([], None),
])
def test_host_stall_max_ms(digests, want):
    got = harness.read_metric(
        "layer_metrics", "host_stall_max_ms", {"digests": digests})
    assert got == (want if want is None else pytest.approx(want, rel=1e-12))


def test_host_stall_max_ms_is_declared(bench_json):
    assert bench_json["per_layer"][-1] == {
        "name": "host_stall_max_ms", "unit": "ms", "better": "lower",
        "source": "program_span", "layer": "engine loop, scheduler",
        "moves": "out_tok_s",
        "workloads": [w["name"] for w in bench_json["workloads"]]}
