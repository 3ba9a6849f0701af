"""`moe_row_blocks_mean` on hand-made digest lists: the mean of the
landing rows' `moe_row_blocks`; nothing (and nothing raised) where no row
carries the column, as in a program from before PR 39."""
import pytest

import harness


@pytest.mark.parametrize("digests,want", [
    # dispatch rows read 0 there: only the landings count
    ([{"kind": "decode", "moe_row_blocks": 0.0},
      {"kind": "overlap", "moe_row_blocks": 1.0, "moe_pairs_held": 92.0},
      {"kind": "sync", "moe_row_blocks": 1.25, "moe_pairs_held": 131.5}],
     1.125),
    # a program that returns its expert load without the blocks
    ([{"kind": "overlap", "moe_experts_hit": 15.7, "moe_load_max": 12.0}],
     None),
    ([], None),
])
def test_moe_row_blocks_mean(digests, want):
    got = harness.read_metric(
        "layer_metrics", "moe_row_blocks_mean", {"digests": digests})
    assert got == want


def test_moe_row_blocks_mean_is_declared(bench_json):
    m = harness.find(
        bench_json["per_layer"], "moe_row_blocks_mean", "per-layer metric")
    assert m == {
        "name": "moe_row_blocks_mean", "unit": "blocks", "better": "lower",
        "source": "program_counter", "layer": "model step",
        "moves": "tpot_p95_ms",
        "workloads": ["mimo-v2-flash-l7.reason-wide"]}
