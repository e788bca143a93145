"""The benchmark's operation and byte counts against counts by hand."""
from __future__ import annotations

from bench import roofline


def test_dpm_merge_bytes_by_hand():
    # one instance on a 2x2 fabric, 24 candidates: mask 4 B + membership
    # 16 B + source 4 B + 24 x (1 + 4 + 4 + 1) B out; tables 4 x 16 x 4 B
    # + labels and order 2 x 4 x 4 B
    assert roofline.dpm_merge_bytes(1, 4) == 4 + 16 + 4 + 240 + 256 + 32
    # a 512-chunk at 32x32 moves ~16.8 MB, most of it the four tables
    b = roofline.dpm_merge_bytes(512, 1024)
    assert 4 * 1024 * 1024 * 4 < b < 4 * 1024 * 1024 * 4 + 4_000_000


def test_xsim_cycle_bytes_by_hand():
    # 2 links, 4 VCs, depth 4: FIFOs 2 x 4 x (4 + 2) = 48 words, lanes
    # 2 x 3 nodes = 6, counters 2: 56 words, read and written, int32
    assert roofline.xsim_cycle_bytes(1, 2, 4, 4, 3) == 2 * 56 * 4
    assert roofline.xsim_cycle_bytes(5, 2, 4, 4, 3) == 5 * 2 * 56 * 4


def test_peaks_are_keyed_by_device_kind():
    from bench import harness

    v5e = harness.peaks("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9
    try:
        harness.peaks("cpu")
    except KeyError:
        pass
    else:
        raise AssertionError("an unknown device kind must be an error")
