"""BENCHMARK.json against the files the harness finds by name, and the
harness's refusal to run without a chip."""

import json
import os
import re
import shutil
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_names(bench):
    names = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    names += [w["name"] for w in bench["workloads"]]
    names += [c["name"] for c in bench["configs"]]
    names += [w["traffic"] for w in bench["workloads"]]
    for name in names:
        assert NAME.match(name), name
    for sec in ("end_to_end", "per_layer", "workloads", "configs"):
        seen = [x["name"] for x in bench[sec]]
        assert len(seen) == len(set(seen)), sec


def test_every_cell_finds_its_files(bench):
    configs = {c["name"]: c for c in bench["configs"]}
    used = set()
    for w in bench["workloads"]:
        used.add(w["config"])
        cfg_path = os.path.join(ROOT, configs[w["config"]]["file"])
        with open(cfg_path) as f:
            cfg = json.load(f)
        assert set(configs[w["config"]]["reduced"]) <= set(cfg), w
        assert os.path.isfile(os.path.join(
            HERE, "reference", cfg["reference"] + ".py"))
        with open(os.path.join(HERE, "traffic", w["traffic"] + ".json")) as f:
            kind = json.load(f)["kind"]
        assert os.path.isfile(os.path.join(HERE, "kinds", kind + ".py"))
        assert os.path.isfile(os.path.join(HERE, "limits",
                                           w["name"] + ".json"))
        assert w["chips"] in (1, 4)
    assert used == set(configs)


def test_metrics_are_reported_where_they_move(bench):
    cells = {w["name"] for w in bench["workloads"]}
    e2e = {m["name"]: set(m.get("workloads", cells))
           for m in bench["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"] == cells
    for m in bench["per_layer"]:
        assert os.path.isfile(os.path.join(HERE, "layer_metrics",
                                           m["name"] + ".py")), m["name"]
        assert set(m["workloads"]) <= e2e[m["moves"]], m["name"]
    for cell in cells:
        reported = [n for n, ws in e2e.items() if cell in ws]
        assert len(reported) >= 2, cell
        assert any(cell in m["workloads"] for m in bench["per_layer"]), cell


def _run(cwd, extra_env=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(extra_env or {}))
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "he-train-1chip",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_chip_no_result():
    res = _run(ROOT)
    assert res.returncode == 2, res.stderr[-2000:]
    assert not [ln for ln in res.stdout.splitlines() if ln.startswith("{")]


def test_benchmark_files_alone_give_no_result(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(HERE, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    res = _run(str(tmp_path))
    assert res.returncode != 0
    assert not [ln for ln in res.stdout.splitlines() if ln.startswith("{")]
