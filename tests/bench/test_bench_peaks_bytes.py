"""The peak table and the bytes functions the roofline shares rest on."""

import pytest

import bench_testutil  # noqa: F401  (puts the benchmark on the path)
import bytes_model
import harness


def test_v5e_peaks():
    p = harness.load_peaks("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["hbm_bytes"] == 16e9
    assert p["bf16_flops_per_s"] == 197e12
    assert p["int8_ops_per_s"] == 393e12


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError):
        harness.load_peaks("TPU v9 imaginary")


def test_route_bytes_at_the_cell_shape():
    # 65,536 keys: 4 B in + 4 B out each = 524,288
    # 1,024 nodes: counters r+w 8 B, queue r+w 8 B, service 4 B, history row 4 B = 24,576
    # tables: 1,536 lane-padded entries x (len32 + node) 8 B = 12,288
    assert bytes_model.route_bytes(65536, 1024, 1536) == 524288 + 24576 + 12288


def test_diff_bytes_at_the_cell_shape():
    # 2^20 ids: 4 B in, and per slot 1 B moved + 3 x 4 B (src, dst, src_slot)
    # out, R = 3: 43 B an id = 45,088,768
    # tables: two versions x 1,536 entries x 8 B = 24,576
    assert bytes_model.diff_bytes(1 << 20, 3, 1536) == 45088768 + 24576
