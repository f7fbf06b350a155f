"""A run with the timed path broken underneath comes out not correct.

Each test drives the rest of a run (a cell's kind: set-up, window, the
check against the reference, with the cell's own limits) on the CPU at a
small size, with one fault planted in the program's path, and sees
`correct` come out false; a sound run at the same size comes out true.
"""

import copy
import os
import subprocess
import sys

import pytest

from chipbench import common, control
from chipbench.common import CompileClock, is_correct
from chipbench.kinds import serve_open_loop, train
from chipbench.tests import cells

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module", autouse=True)
def runtime():
    from repro.launch.runtime import setup_runtime

    setup_runtime()


def train_ctx(n=512):
    return cells.context("he-train-1chip", 2**33 + 7, 0.5,
                         config=dict(n=n, row_block=256))


def serve_ctx(n=2048):
    return cells.context("he-serve-1chip", 2**33 + 9, 0.5, config=dict(n=n),
                         traffic=dict(query_pool=512, calibration_rows=256,
                                      rate_per_s=40))


def test_train_sound_run_is_correct():
    out = train.run(train_ctx(), CompileClock())
    assert is_correct(out), out["checks"].as_dict()
    # the set-up's three steps and the window's last ones were checked
    assert set(out["checks"].as_dict()) == {"resid_gap", "grad_gap",
                                            "adam_gap"}
    # each window step left the iterations its solve needed for train_mfu
    lc = out["layer_ctx"]
    assert len(lc["cg_iters"]) == lc["steps"]
    assert all(1 <= i <= 20 for i in lc["cg_iters"]), lc["cg_iters"]


@pytest.mark.parametrize("fault", ["unchanged", "half_rows", "altered",
                                   "no_trace", "skipped"])
def test_train_fault_is_not_correct(fault):
    """The faults of `chipbench/control.py`, planted in the program's path
    (`no_trace`: the Eq. 2 backward without its trace term; `skipped`: a
    cold solve that returns zero and the residual of zero)."""
    out = train.run(train_ctx(), CompileClock(),
                    trainer_cls=control.train_trainer(fault))
    assert not is_correct(out), out["checks"].as_dict()


def test_serve_sound_run_is_correct():
    out = serve_open_loop.run(serve_ctx(), CompileClock())
    assert is_correct(out), out["checks"].as_dict()


@pytest.mark.parametrize("wrap", [control.AlteredEngine,
                                  control.HalfRowsEngine])
def test_serve_fault_is_not_correct(wrap):
    def factory(*a):
        return wrap(serve_open_loop.build_engine(*a))

    out = serve_open_loop.run(serve_ctx(), CompileClock(),
                              engine_factory=factory)
    assert not is_correct(out), out["checks"].as_dict()


MESH_SCRIPT = r"""
import sys, time
sys.path[:0] = [{root!r}, {src!r}]
import jax
from chipbench.common import CompileClock, is_correct
from chipbench.kinds import train
from chipbench.tests import cells
from repro.launch.runtime import setup_runtime
setup_runtime()
if {fault!r}:
    # the exchange between chips left out: the column groups' partial
    # rows are no longer summed, each chip keeps its own
    def local(x, axes, scatter_dimension=0, tiled=True):
        i = jax.lax.axis_index(axes)
        k = x.shape[scatter_dimension] // jax.lax.psum(1, axes)
        return jax.lax.dynamic_slice_in_dim(x, i * k, k, scatter_dimension)
    jax.lax.psum_scatter = local
ctx = cells.context("he-train-1chip", 2**35 + 1, 0.5,
                    config=dict(n=1024, row_block=256, mesh=[2, 2]), chips=4)
out = train.run(ctx, CompileClock())
print("CORRECT", is_correct(out), out["checks"].as_dict())
"""


@pytest.mark.parametrize("fault", [False, True])
def test_mesh_exchange_left_out_is_not_correct(fault):
    """On four virtual CPU devices as a 2x2 mesh (a child process, so that
    this one keeps its single device)."""
    root = os.path.dirname(HERE)
    code = MESH_SCRIPT.format(root=root, src=os.path.join(root, "src"),
                              fault=fault)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    res = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=900)
    line = [ln for ln in res.stdout.splitlines() if ln.startswith("CORRECT")]
    assert line, res.stdout[-2000:] + res.stderr[-4000:]
    assert line[0].startswith(f"CORRECT {not fault}"), line[0]


def test_checks_print_each_number_beside_its_limit(capsys):
    checks = common.Checks({"a": {"limit": 1.0}})
    checks.record("a", 0.5)
    checks.print_stderr()
    assert "check a = 0.5 limit 1.0 ok" in capsys.readouterr().err
    assert copy.deepcopy(checks.as_dict()) == {"a": {"value": 0.5,
                                                     "limit": 1.0}}
