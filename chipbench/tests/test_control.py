"""The control, the reference put in the program's place one precision step
down, comes out not correct through the harness's own run of a training
cell; the reference at the configuration's precision comes out correct.

On the chip `chipbench/control.py` reads it at the cells' own sizes (see
PERF.md); here it runs on the CPU at a size a test run holds, with the
three-pass product written out in bf16 halves (`reference/matern32.py`).
"""

import jax
import pytest

from chipbench import control
from chipbench.common import CompileClock, is_correct
from chipbench.kinds import train
from chipbench.tests import cells


def _ctx(cell, n, seed):
    return cells.context(cell, seed, config=dict(n=n))


def _run(ctx, name):
    out = train.run(ctx, CompileClock(),
                    trainer_cls=control.train_trainer(name))
    return is_correct(out), out["checks"].as_dict()


# (cell, n): the smallest size at which the CPU shows the control failing
SIZES = [("ct-train-1chip", 2048)]


@pytest.mark.parametrize("cell,n", SIZES)
@pytest.mark.parametrize("seed", [11, 2**32 + 12])
def test_control_fails_and_sound_reference_passes(cell, n, seed):
    ctx = _ctx(cell, n, seed)
    ok, checks = _run(ctx, "control")
    assert not ok, checks
    assert checks["resid_gap"]["value"] > checks["resid_gap"]["limit"]
    ok, checks = _run(ctx, "reference")
    assert ok, checks


@pytest.fixture(scope="module")
def chip():
    if jax.devices()[0].platform != "tpu":
        pytest.skip("the cells' own sizes need a TPU")


@pytest.mark.parametrize("cell", ["he-train-1chip", "ct-train-1chip"])
def test_control_fails_at_the_cells_own_size(chip, cell):
    """On the chip: the reference at HIGH in the program's place, at the
    cell's n, through the cell's own run."""
    ctx = _ctx(cell, cells.load(cell)[1]["n"], 11)
    ok, checks = _run(ctx, "control")
    assert not ok, checks
