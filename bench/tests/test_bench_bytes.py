"""The LC byte count against a hand count, for both layouts."""
import pytest

import _paths  # noqa: F401
import roofline


def test_row_launch_bytes_by_hand():
    # B=16 lanes of P=30 shards of 112 x 10,240 f32: A once per launch
    a = 16 * 30 * 112 * 10240 * 4
    z = a + 16 * 4 * (10240 + 3 * 30 * 112)
    f = a + 16 * 4 * (30 * 112 + 10240 + 30 * 10240)
    assert roofline.row_launch_bytes("z", 16, 30, 112, 10240) == z
    assert roofline.row_launch_bytes("f", 16, 30, 112, 10240) == f
    assert a == 2_202_009_600          # the 2.20 GB stack


def test_col_launch_bytes_by_hand():
    # B=16 lanes of P=4 column slices of 3,072 x 3,008 f32
    a = 16 * 4 * 3072 * 3008 * 4
    r = a + 16 * 4 * (4 * 3008 + 4 * 3072)
    inner = a + 16 * 4 * (3 * 4 * 3008 + 4 * 3072 + 3072 + 3008)
    assert roofline.col_launch_bytes("r", 16, 4, 3072, 3008) == r
    assert roofline.col_launch_bytes("inner", 16, 4, 3072, 3008) == inner
    assert a == 2_365_587_456          # the 2.37 GB stack


def test_bf16_halves_the_matrix_term():
    f32 = roofline.row_launch_bytes("z", 1, 2, 8, 128, a_bytes=4)
    bf16 = roofline.row_launch_bytes("z", 1, 2, 8, 128, a_bytes=2)
    assert f32 - bf16 == 2 * 8 * 128 * 2


def test_peaks_table():
    assert roofline.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        roofline.peaks("cpu")
