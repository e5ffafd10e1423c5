"""Every cell runs through the whole harness on the CPU at a small size and
comes out correct, every compared number at zero (the sound runs' reading)."""

import pytest

import bench_testutil as bt


@pytest.mark.parametrize("workload", sorted(bt.TINY))
def test_cell_is_correct(workload):
    out = bt.run(workload)
    assert out["correct"], out["compared"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert all(c["value"] == 0 and c["limit"] == 0 for c in out["compared"].values())
    assert list(out)[-1] == "compared"  # the numbers compared come last
    names = {m["name"] for m in bt.spec_for(workload)[0]["end_to_end"]}
    assert set(out["metrics"]) == names
    assert all(m["value"] > 0 for m in out["metrics"].values())
