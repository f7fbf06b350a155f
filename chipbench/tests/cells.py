"""The cells' files by name, for the tests."""

import os
import time

import jax

from chipbench.common import Context, load_json

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CELLS = {
    "he-train-1chip": ("houseelectric-m32-n65536", "train_steps", 1),
    "ct-train-1chip": ("ctslice-m32", "train_steps", 1),
    "he-serve-1chip": ("houseelectric-m32", "open_loop_16rows", 1),
}


def load(cell):
    """(workload entry, config, traffic, limits) of a cell."""
    config, traffic, chips = CELLS[cell]
    w = {"name": cell, "config": config, "traffic": traffic, "chips": chips}
    cfg = load_json(os.path.join(HERE, "configs", config + ".json"))
    tr = load_json(os.path.join(HERE, "traffic", traffic + ".json"))
    lim = load_json(os.path.join(HERE, "limits", cell + ".json"))
    return w, cfg, tr, lim


def context(cell, seed, seconds=1.0, config=None, traffic=None, chips=1):
    """A run's context for a cell, its config and traffic overridden."""
    w, cfg, tr, lim = load(cell)
    return Context(w, dict(cfg, **(config or {})), dict(tr, **(traffic or {})),
                   lim, seed, seconds, False, time.perf_counter(),
                   jax.devices()[:chips], "")
