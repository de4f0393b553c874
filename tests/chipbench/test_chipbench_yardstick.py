"""Bytes and operations of the two paged kernels against hand sums,
and the table of peaks."""

import pytest

from chipbench import yardstick


def test_unknown_device_is_an_error():
    assert yardstick.peaks("TPU v5 lite") == (197e12, 819e9)
    with pytest.raises(KeyError):
        yardstick.peaks("cpu")


def test_paged_decode_bytes_hand_sum():
    # 2 rows attending 100 + 28 positions, 4 heads of 64, bf16:
    # K and V: 2 * 128 * 4 * 64 * 2 = 131072; q in + o out:
    # 2 * 2 * 4 * 64 * 2 = 2048.
    assert yardstick.paged_decode_bytes(128, 2, 4, 4, 64, 2) == 133120


def test_paged_chunk_cost_hand_sum():
    # 4 query rows at positions 8..11 see 9 + 10 + 11 + 12 = 42 keys;
    # 2 heads of 16: flops = 4 * 42 * 16 * 2 = 5376.
    flops, nbytes = yardstick.paged_chunk_cost(8, 4, 2, 2, 16, 2)
    assert flops == 5376
    # K, V of 12 positions: 2 * 12 * 2 * 16 * 2 = 1536; q, o of 4 rows:
    # 2 * 4 * 2 * 16 * 2 = 512.
    assert nbytes == 2048


def test_floor_is_the_larger_bound():
    assert yardstick.floor_seconds(197e12, 0, "TPU v5e") == pytest.approx(1.0)
    assert yardstick.floor_seconds(197e12, 2 * 819e9, "TPU v5e") == pytest.approx(2.0)
