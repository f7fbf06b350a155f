"""Autotuner cache: disk round-trip, determinism, key invalidation."""

import json
import os

import pytest

from repro.kernels.autotune import (
    DEFAULT_CANDIDATES,
    autotune_tiles,
    cache_key,
    clear_memo,
    key_hash,
    prewarm,
    shape_bucket,
    tiles_for_spec,
)

COMPONENTS = (("rbf", "matern32"),)


@pytest.fixture(autouse=True)
def _fresh_memo():
    clear_memo()
    yield
    clear_memo()


def _fixed_measure(table):
    """Deterministic injectable measure; records the sweep order."""
    calls = []

    def measure(bm, bn):
        calls.append((bm, bn))
        return table.get((bm, bn), 1.0)

    measure.calls = calls
    return measure


def test_sweep_picks_minimum_and_persists(tmp_path):
    cdir = str(tmp_path)
    measure = _fixed_measure({(256, 256): 0.1, (128, 128): 0.5})
    choice = autotune_tiles(COMPONENTS, 1000, 1000, 8, 9,
                            compute_dtype="float32", interpret=True,
                            candidates=DEFAULT_CANDIDATES,
                            measure=measure, cache_dir=cdir)
    assert choice == (256, 256)
    assert measure.calls == list(DEFAULT_CANDIDATES)
    # one entry on disk, named by the content hash, carrying the timings
    files = os.listdir(cdir)
    assert len(files) == 1
    key = cache_key(COMPONENTS, 1000, 1000, 8, 9,
                    compute_dtype="float32", interpret=True)
    assert files[0] == key_hash(key) + ".json"
    with open(os.path.join(cdir, files[0])) as f:
        entry = json.load(f)
    assert (entry["bm"], entry["bn"]) == (256, 256)
    assert entry["key"] == key
    assert entry["timings"]["256x256"] == pytest.approx(0.1)


def test_disk_roundtrip_skips_measurement(tmp_path):
    cdir = str(tmp_path)
    m1 = _fixed_measure({(512, 512): 0.01})
    first = autotune_tiles(COMPONENTS, 500, 500, 4, 3,
                           compute_dtype="float32", interpret=True,
                           measure=m1, cache_dir=cdir)
    assert first == (512, 512)
    # a fresh process (memo cleared) must hit the disk entry, not re-sweep
    clear_memo()
    m2 = _fixed_measure({(128, 128): 0.0})  # would pick differently
    second = autotune_tiles(COMPONENTS, 500, 500, 4, 3,
                            compute_dtype="float32", interpret=True,
                            measure=m2, cache_dir=cdir)
    assert second == first
    assert m2.calls == []


def test_memo_skips_disk(tmp_path):
    cdir = str(tmp_path)
    measure = _fixed_measure({})
    first = autotune_tiles(COMPONENTS, 64, 64, 2, 1,
                           compute_dtype="float32", interpret=True,
                           measure=measure, cache_dir=cdir)
    os.unlink(os.path.join(cdir, os.listdir(cdir)[0]))
    second = autotune_tiles(COMPONENTS, 64, 64, 2, 1,
                            compute_dtype="float32", interpret=True,
                            measure=measure, cache_dir=cdir)
    assert second == first
    assert len(measure.calls) == len(DEFAULT_CANDIDATES)  # swept only once


def test_tie_breaks_toward_earliest_candidate(tmp_path):
    # every candidate times identically -> the FIRST in the sweep wins
    measure = _fixed_measure({c: 0.25 for c in DEFAULT_CANDIDATES})
    choice = autotune_tiles(COMPONENTS, 256, 256, 4, 2,
                            compute_dtype="float32", interpret=True,
                            measure=measure, cache_dir=str(tmp_path))
    assert choice == DEFAULT_CANDIDATES[0]


def test_deterministic_under_fixed_measure(tmp_path):
    table = {(128, 256): 0.3, (256, 512): 0.2, (512, 512): 0.7}
    picks = []
    for i in range(3):
        clear_memo()
        cdir = str(tmp_path / f"run{i}")
        picks.append(autotune_tiles(
            COMPONENTS, 2048, 2048, 16, 9,
            compute_dtype="float32", interpret=True,
            measure=_fixed_measure(table), cache_dir=cdir))
    assert picks == [(256, 512)] * 3


def test_shape_bucket_is_next_pow2():
    assert [shape_bucket(x) for x in (1, 2, 3, 64, 65, 1000, 1024)] == \
        [1, 2, 4, 64, 128, 1024, 1024]


def test_key_invalidates_on_dtype_backend_and_shape_bucket():
    base = dict(compute_dtype="float32", interpret=True, platform="cpu")
    k0 = cache_key(COMPONENTS, 1000, 1000, 8, 9, **base)
    # same bucket (513..1024 -> 1024): same key, cache hit
    same = cache_key(COMPONENTS, 700, 513, 8, 9, **base)
    assert key_hash(same) == key_hash(k0)
    # dtype change invalidates
    kd = cache_key(COMPONENTS, 1000, 1000, 8, 9,
                   **{**base, "compute_dtype": "bfloat16"})
    # backend (platform / interpret) change invalidates
    kp = cache_key(COMPONENTS, 1000, 1000, 8, 9,
                   **{**base, "platform": "tpu"})
    ki = cache_key(COMPONENTS, 1000, 1000, 8, 9,
                   **{**base, "interpret": False})
    # shape-bucket change invalidates
    ks = cache_key(COMPONENTS, 1000, 1025, 8, 9, **base)
    # component structure change invalidates
    kc = cache_key((("rbf",),), 1000, 1000, 8, 9, **base)
    hashes = {key_hash(k) for k in (k0, kd, kp, ki, ks, kc)}
    assert len(hashes) == 6


def test_cache_hit_across_shapes_in_same_bucket(tmp_path):
    cdir = str(tmp_path)
    m1 = _fixed_measure({(128, 256): 0.0})
    a = autotune_tiles(COMPONENTS, 900, 900, 5, 3,
                       compute_dtype="float32", interpret=True,
                       measure=m1, cache_dir=cdir)
    m2 = _fixed_measure({(512, 512): 0.0})
    clear_memo()
    b = autotune_tiles(COMPONENTS, 1024, 600, 7, 4,  # same pow2 buckets? no:
                       compute_dtype="float32", interpret=True,
                       measure=m2, cache_dir=cdir)
    # different buckets (n: 1024 vs 1024? m 900->1024, 1024->1024; n 900->1024,
    # 600->1024; d 5->8, 7->8; t 3->4, 4->4) — identical buckets: disk hit
    assert b == a
    assert m2.calls == []
    assert len(os.listdir(cdir)) == 1


def test_cache_miss_under_trace_falls_back_without_memoizing(tmp_path):
    """A miss while tracing returns the static defaults (a timed launch
    would return tracers) and persists nothing, so a later eager call
    still runs the real sweep."""
    import jax
    import jax.numpy as jnp
    from repro.kernels.kmvm import DEFAULT_BM, DEFAULT_BN

    cdir = str(tmp_path)
    seen = {}

    def f(x):
        seen["tiles"] = autotune_tiles(
            COMPONENTS, 64, 64, 2, 1, compute_dtype="float32",
            interpret=True, measure=_fixed_measure({}), cache_dir=cdir)
        return x + 1

    jax.jit(f)(jnp.zeros(1))
    assert seen["tiles"] == (DEFAULT_BM, DEFAULT_BN)
    assert os.listdir(cdir) == []
    eager = autotune_tiles(COMPONENTS, 64, 64, 2, 1,
                           compute_dtype="float32", interpret=True,
                           measure=_fixed_measure({(256, 256): 0.0}),
                           cache_dir=cdir)
    assert eager == (256, 256)
    assert len(os.listdir(cdir)) == 1


def test_tiles_for_spec_and_prewarm_route_through_cache(tmp_path, rng):
    import jax.numpy as jnp
    from repro.core import init_params

    cdir = str(tmp_path)
    X = jnp.asarray(rng.normal(size=(64, 3)), jnp.float32)
    params = init_params(dtype=jnp.float32)
    measure_tbl = {(128, 128): 0.9, (256, 256): 0.1}
    # seed the cache entry via the low-level API at prewarm's key
    from repro.kernels.ops import mvm_plan
    plan = mvm_plan("matern32", params)
    autotune_tiles(plan.passes[0].components, 64, 64, 3, 9,
                   compute_dtype="float32", interpret=True,
                   measure=_fixed_measure(measure_tbl), cache_dir=cdir)
    got = prewarm("matern32", params, 64, 3, num_probes=8,
                  compute_dtype="float32", interpret=True, cache_dir=cdir)
    assert got == (256, 256)
    got2 = tiles_for_spec("matern32", params, 64, 64, 3, 9,
                          compute_dtype="float32", interpret=True,
                          cache_dir=cdir)
    assert got2 == (256, 256)


def test_default_cache_dir_is_in_the_checkout(monkeypatch, tmp_path):
    from repro.kernels.autotune import default_cache_dir
    from repro.launch.runtime import CHECKOUT

    monkeypatch.delenv("REPRO_AUTOTUNE_CACHE", raising=False)
    assert default_cache_dir() == os.path.join(CHECKOUT, ".autotune_cache")
    assert os.path.isdir(os.path.join(CHECKOUT, "src", "repro"))
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(tmp_path))
    assert default_cache_dir() == str(tmp_path)
