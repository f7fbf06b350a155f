"""`train_mfu` charges each step the CG iterations its solve needed, driven
with hand-made window records."""

import importlib.util
import os
import types

import pytest

from chipbench import counts, peaks
from chipbench.common import Context
from chipbench.tests import cells

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
V5E = types.SimpleNamespace(device_kind="TPU v5 lite")
PEAK = peaks.mxu_flops(peaks.peaks_for("TPU v5 lite"), "float32")
CT_N, CT_ENTRY = 34240, 2 * 385 + 2 * 9


def reader():
    path = os.path.join(HERE, "layer_metrics", "train_mfu.py")
    spec = importlib.util.spec_from_file_location("train_mfu", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def ct_ctx():
    w, cfg, tr, lim = cells.load("ct-train-1chip")
    return Context(w, cfg, tr, lim, 7, 10.0, True, 0.0, [V5E], "")


def window(modes, iters, step_s):
    return {"kind": "train", "steps": len(modes), "modes": modes,
            "cg_iters": iters, "window_host_s": step_s * len(modes),
            "step_s": step_s}


def charged_flops(mfu, lc):
    return mfu / 100.0 * lc["window_host_s"] * PEAK


def test_warm_step_of_one_iteration_is_three_traversals():
    lc = window(["warm"], [1], 1.0)
    got = charged_flops(reader()(None, ct_ctx(), lc), lc)
    assert got == pytest.approx(3 * CT_N**2 * CT_ENTRY, rel=1e-12)


@pytest.mark.parametrize("mode", ["cold", "warm", "refresh"])
def test_iterations_past_the_trip_count_are_capped(mode):
    read, ctx = reader(), ct_ctx()
    lc = window([mode], [35], 1.0)
    over = read(None, ctx, lc)
    assert over == read(None, ctx, window([mode], [20], 1.0))
    assert charged_flops(over, lc) == pytest.approx(
        counts.train_step_ops(CT_N, 385, 8, mode, 20), rel=1e-12)


@pytest.mark.parametrize("iters", [[1, None, 1], None, []])
def test_a_step_without_its_count_reads_nothing(iters):
    lc = window(["warm", "refresh", "warm"], iters, 1.0)
    assert reader()(None, ct_ctx(), lc) is None


def test_an_early_exit_window_reads_below_peak():
    lc = window(["warm"] * 28, [1] * 28, 0.35)
    mfu = reader()(None, ct_ctx(), lc)
    assert mfu == pytest.approx(24.1, abs=0.05)
    # the loop's trip count charged for the same window would read 177%
    trip = 100.0 * counts.train_step_ops(CT_N, 385, 8, "warm", 20) / (
        0.35 * PEAK)
    assert trip == pytest.approx(176.9, abs=0.1)
    assert mfu / trip == pytest.approx(3 / 22, rel=1e-12)


def test_each_step_is_charged_its_own_count():
    read, ctx = reader(), ct_ctx()
    lc = window(["refresh", "warm", "warm", "warm"], [3, 1, 1, 1], 1.0)
    per_step = [read(None, ctx, window([m], [i], 1.0))
                for m, i in zip(lc["modes"], lc["cg_iters"])]
    assert read(None, ctx, lc) == pytest.approx(sum(per_step) / 4,
                                                rel=1e-12)
    # (3 + 2) + 3 x (1 + 2) traversals against 4 x 22 at the trip count
    assert charged_flops(read(None, ctx, lc), lc) == pytest.approx(
        14 * CT_N**2 * CT_ENTRY, rel=1e-12)
