"""The work counts behind the roofline and MFU shares."""

import pytest

from chipbench.yardstick import peaks, work


def test_flops_of_a_16384x120_panel():
    # 2 operations x 32 edges x 16384 neurons x 120 layers x 512 inputs
    assert work.stack_flops(16384, 120, 512) == 64_424_509_440


def test_bytes_count_panels_and_each_phase_once():
    # 1024 neurons, 3 phases, 512 inputs: two f32 panels and 3 x 32768
    # edges of an f32 value and an int32 index
    assert work.stack_bytes(1024, 3, 512) == 2 * 1024 * 512 * 4 + 3 * 1024 * 32 * 8


def test_least_time_names_its_bound():
    v5e = peaks.peaks("TPU v5 lite")
    t, bound = work.least_seconds(work.stack_flops(16384, 120, 512),
                                  work.stack_bytes(16384, 3, 512), v5e)
    assert bound == "compute"
    assert t == pytest.approx(64_424_509_440 / 197e12)
    t, bound = work.least_seconds(1.0, 819e9, v5e)
    assert (t, bound) == (1.0, "memory")


def test_unknown_chip_is_an_error():
    with pytest.raises(ValueError, match="no published peaks"):
        peaks.peaks("TPU v9 imaginary")
