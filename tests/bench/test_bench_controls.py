"""The controls (``bench/control.py``): the reference in the program's place
with one stated guarantee broken must make the check come out not correct."""

import pytest

import bench_testutil as bt
import control

CASES = [
    ("kv.zipf.closed", "outside_replica_set"),
    ("kv.zipf.open", "outside_replica_set"),
    ("rebal.add-remove", "rows_off_changed_node"),
]


@pytest.mark.parametrize("workload,number", CASES)
def test_control_is_not_correct(workload, number):
    entry = bt.spec_for(workload)[0]["traffic"]["entry"]
    out = bt.run(workload, system=control.CONTROLS[entry])
    assert not out["correct"]
    c = out["compared"][number]
    assert c["value"] > 3 * c["limit"], out["compared"]
