"""`run_cell.py --rehearse` end to end on the tiny model: an open-loop and
a closed-loop cell, end-to-end and per-layer lines, keys and null times;
and the refusal without `--rehearse` where there is no TPU."""
import pytest

from conftest import ROOT, run_cell

KEYS = {"correct", "attempted", "failed", "metrics", "device"}


@pytest.mark.parametrize("cell,trace", [
    ("mistral-7b-int8.chat-steady", 0),
    ("mistral-7b-int8.decode-saturate", 1),
])
def test_rehearsal_prints_one_whole_line(bench_json, cell, trace):
    rc, line, err = run_cell(ROOT, "--workload", cell, "--seed", "2147483659",
                             "--seconds", "5", "--trace", str(trace),
                             "--rehearse")
    assert rc == 0, err[-2000:]
    assert KEYS <= set(line)
    assert line["correct"] is True, line["check"]
    assert line["attempted"] > 0
    # the CPU serves 64 closed-loop callers too slowly for every request
    # started in a 5 s window to show a token before the cut-off
    assert line["failed"] == 0 or "decode-saturate" in cell
    assert line["device"]["platform"] == "cpu"
    group = bench_json["per_layer" if trace else "end_to_end"]
    want = {m["name"] for m in group
            if "workloads" not in m or cell in m["workloads"]}
    got = line["metrics"]
    # a reader with nothing to read (no device trace on the CPU) is left out
    assert set(got) <= want and (trace or set(got) == want)
    for m in group:
        if m["name"] in got:
            v = got[m["name"]]["value"]
            assert got[m["name"]]["unit"] == m["unit"]
            if m["unit"] in ("count", "rows"):
                assert v is not None
            else:  # a CPU run prints no time, rate or share
                assert v is None
    # the probes reach every STEP program before the window; what may
    # still compile inside it is one of the engine's power-of-two padded
    # index scatters, a program of a few operations
    assert not [n for n in line["compiled_in_window"]
                if "_model_step" in n or "_decode_multi" in n]
    if trace:
        assert got["compiles_in_window"]["value"] <= 2
        assert "busy_s" not in line["device"] and "breakdown" not in line


def test_no_tpu_no_result():
    rc, line, err = run_cell(ROOT, "--workload", "mistral-7b-int8.chat-steady",
                             "--seed", "1", "--seconds", "5", "--trace", "0")
    assert rc != 0 and line is None
    assert "refused" in err
