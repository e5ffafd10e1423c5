"""The benchmark's plain reference agrees with the program, bit for bit, on
placement and on minimal movement, and its control does not."""

import numpy as np

import bench_testutil as bt
import harness
from repro.core import make_cluster
from repro.core.asura import align_replica_sets, place_replicas_batch

spec, _ = bt.spec_for("rebal.add-remove")
ref = harness.reference_module(spec)


def _program_sets(cluster, ids):
    segs = place_replicas_batch(ids, cluster.seg_lengths(), cluster.seg_to_node(), 3)
    return cluster.seg_to_node()[segs]


def test_reference_matches_program_through_churn():
    rng = np.random.default_rng(3)
    caps = rng.uniform(0.5, 2.0, 200)
    cluster, table = make_cluster(caps), ref.SegmentTable(caps)
    ids = rng.integers(0, 2**32, 5000, dtype=np.uint64).astype(np.uint32)
    before = ref.place_replicas(ids, table, 3)
    assert np.array_equal(before, _program_sets(cluster, ids))
    for step, (add, remove) in enumerate([((200, 1.7), None), (None, 200), (None, 7),
                                          ((201, 0.6), None)]):
        if add:
            cluster.add_node(*add)
            table.add(*add)
        else:
            cluster.remove_node(remove)
            table.remove(remove)
        after = ref.place_replicas(ids, table, 3)
        assert np.array_equal(after, _program_sets(cluster, ids)), step
        moved, src, _ = align_replica_sets(before, after)
        m2, s2 = ref.align(before, after)
        assert np.array_equal(moved, m2) and np.array_equal(src, s2)
        before = after


def test_control_breaks_capacity_weighting():
    caps = np.linspace(0.5, 2.0, 100)
    table = ref.SegmentTable(caps)
    ids = np.arange(20000, dtype=np.uint32) * np.uint32(2654435761)
    good = ref.place_replicas(ids, table, 3)
    bad = ref.place_replicas(ids, table, 3, weighted=False)
    assert (good != bad).any(axis=1).mean() > 0.2
