"""chipbench.counts against shapes worked out by hand."""

import pytest

from chipbench import counts, peaks


def test_kernel_ops_per_entry():
    # 2 x 3 entries, each 2*9 (distance) + 2*9 (contraction) flops
    assert counts.kernel_ops(2, 3, 9, 9) == 2 * 3 * 36


def test_kernel_bytes_reads_inputs_once():
    # X rows 2x9, X cols 3x9, V 3x4 read; out 2x4 written; float32
    assert counts.kernel_bytes(2, 3, 9, 4) == 4 * (18 + 27 + 12 + 8)


@pytest.mark.parametrize("mode,expect", [("cold", 21), ("warm", 22),
                                         ("refresh", 22)])
def test_train_traversals(mode, expect):
    assert counts.train_traversals(mode, 20) == expect


def test_train_traversals_rejects_unknown_mode():
    with pytest.raises(ValueError):
        counts.train_traversals("lukewarm", 20)


def test_train_step_ops_houseelectric():
    n, d, probes = 65536, 9, 8
    per_entry = 2 * 9 + 2 * 9
    assert counts.train_step_ops(n, d, probes, "cold", 20) == \
        21 * n * n * per_entry


def test_train_step_ops_ctslice():
    n, d = 34240, 385
    assert counts.kernel_ops(n, n, d, 9) == n * n * (770 + 18)


def test_predict_ops_two_passes():
    rows, n, d, r = 16, 1000, 9, 128
    assert counts.predict_ops(rows, n, d, r) == \
        rows * n * (2 * d + 2) + rows * n * (2 * d + 2 * r)


def test_roofline_picks_the_binding_bound():
    t, bound = counts.roofline_seconds(1e12, 1e6, 1e12, 1e9)
    assert (t, bound) == (1.0, "mxu")
    t, bound = counts.roofline_seconds(1e6, 1e9, 1e12, 1e9)
    assert (t, bound) == (1.0, "hbm")


def test_fp32_peak_is_six_bf16_passes():
    pk = peaks.peaks_for("TPU v5 lite")
    assert peaks.mxu_flops(pk, "float32") == pytest.approx(197e12 / 6)
    assert peaks.mxu_flops(pk, "bfloat16") == 197e12


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        peaks.peaks_for("TPU v9 imaginary")


@pytest.mark.parametrize("mesh,mode,expect", [
    ((1, 1), "2d", (65536, 65536)), ((2, 2), "2d", (32768, 32768)),
    ((4, 1), "1d", (16384, 65536)), ((2, 2), "1d", (16384, 65536))])
def test_train_tile(mesh, mode, expect):
    assert counts.train_tile(65536, mesh, mode) == expect
