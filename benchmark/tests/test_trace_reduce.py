"""`trace_reduce.reduce` on a small recording of a real device trace:
150 ms cut from the first traced chip run of `mistral-7b-int8.chat-steady`
(TPU v5e, PR 23): one execution of the decode program (8 steps at width
32) and two prefill chunks. The numbers are properties of that recording;
the reduction must give them every time."""
import os

import pytest

import trace_reduce as tr
from conftest import BENCH

DATA = os.path.join(BENCH, "tests", "data", "v5e_int8_chat_150ms.json.gz")


@pytest.fixture(scope="module")
def reduced():
    return tr.reduce(tr.load(DATA))


def test_busy_share_and_programs(reduced):
    assert reduced["devices"] == ["/device:TPU:0"]
    assert reduced["window_s"] == pytest.approx(0.165772171, rel=1e-9)
    assert reduced["busy_s"] == pytest.approx(0.149867349, rel=1e-9)
    dec = reduced["programs"]["jit__decode_multi"]
    assert dec["count"] == 1 and dec["median_s"] == pytest.approx(0.094278773)
    pre = reduced["programs"]["jit__model_step"]
    assert pre["count"] == 2 and pre["total_s"] == pytest.approx(0.059489751)
    assert reduced["host_steps"] == {"prefill": 1, "decode": 1, "mixed": 0,
                                     "spec_verify": 0}


def test_kernel_times_are_self_times(reduced):
    fam = tr.family_times(reduced["ops"])
    assert fam["fused_paged_decode_attention"] == pytest.approx(0.010817366)
    assert fam["flash_prefill_attention"] == pytest.approx(0.005334138)
    # the decode scan is a `while` that contains its body: counted by
    # self time it is next to nothing, by total time it is the program
    assert fam["while"] == pytest.approx(0.00017798)
    whiles = [v for k, v in reduced["ops"].items() if k.startswith("%while")]
    assert sum(v["total_s"] for v in whiles) > 0.09
    # self times partition the busy time (operations do not overlap)
    assert sum(fam.values()) == pytest.approx(reduced["busy_s"], rel=1e-3)
    inside = reduced["ops_in_program"]["jit__decode_multi"]
    k = sum(v["self_s"] for n, v in inside.items()
            if tr.op_family(n) == "fused_paged_decode_attention")
    assert k == pytest.approx(0.0105199)


def test_same_answer_every_time(reduced):
    assert tr.reduce(tr.load(DATA)) == reduced
    top = tr.breakdown(reduced)
    assert [n for n, _ in top["device_ops"][:3]] == [
        "fusion", "slice-done", "copy-done"]
    assert len(top["device_ops"]) == 10 and len(top["idle_gaps"]) == 10
    assert top["idle_gaps"][0] == ["decode", pytest.approx(1.4582e-05)]


def test_union_and_self_time_on_a_hand_made_line():
    # a 10 us loop holding two 3 us ops, then a gap, then a 4 us op
    ev = [["%while.1", 0, 10_000, {}], ["%fusion.1", 1_000, 3_000, {}],
          ["%fusion.2", 5_000, 3_000, {}], ["%copy.1", 20_000, 4_000, {}]]
    table = {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": ev},
            {"name": "XLA Modules", "events": [["jit_f(1)", 0, 10_000, {}],
                                               ["jit_g(2)", 20_000, 4_000, {}]]}]},
        {"name": "/host:CPU", "lines": [
            {"name": "python", "events": [["decode", 9_000, 2_000, {}]]}]}]}
    r = tr.reduce(table)
    assert r["busy_s"] == pytest.approx(14e-6)
    assert r["window_s"] == pytest.approx(24e-6)
    assert r["ops"]["%while.1"]["self_s"] == pytest.approx(4e-6)
    assert r["ops_in_program"]["jit_g"] == {"%copy.1": {"count": 1,
                                                       "self_s": 4e-6}}
    assert r["idle_gaps"][0] == ["decode", pytest.approx(10e-6)]
