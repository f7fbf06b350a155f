"""chipbench.datagen: shapes, split sizes, whitening, determinism."""

import numpy as np
import pytest

from chipbench import datagen


def test_split_sizes_match_the_paper():
    # HouseElectric: 2,950,963 points -> 1,311,539 train (Table 2)
    assert datagen.split_sizes(2_950_963) == (1_311_539, 655_770, 983_654)
    # CTslice: 77,040 -> 34,240
    assert datagen.split_sizes(77_040)[0] == 34_240


@pytest.mark.parametrize("n", [65536, 131072, 34240, 1_311_539])
def test_total_for_train_gives_n(n):
    total = datagen.total_for_train(n)
    assert datagen.split_sizes(total)[0] == n


def test_shapes_and_whitening():
    s = datagen.make(7, total=9000, d=5)
    n_train, _, n_test = datagen.split_sizes(9000)
    assert s.X_train.shape == (n_train, 5)
    assert s.y_train.shape == (n_train,)
    assert s.X_test.shape == (n_test, 5)
    X = np.asarray(s.X_train, np.float64)
    np.testing.assert_allclose(X.mean(0), 0.0, atol=1e-4)
    np.testing.assert_allclose(X.std(0), 1.0, atol=1e-3)
    y = np.asarray(s.y_train, np.float64)
    assert abs(y.mean()) < 1e-4 and abs(y.std() - 1.0) < 1e-3


def test_cut_training_split():
    s = datagen.make(7, total=9000, d=5, n_train=1000)
    assert s.X_train.shape == (1000, 5)
    assert s.X_test.shape[0] == datagen.split_sizes(9000)[2]
    with pytest.raises(ValueError):
        datagen.make(7, total=9000, d=5, n_train=5000)


@pytest.mark.parametrize("seed", [0, 2**31 + 5, 2**40 + 3])
def test_same_seed_same_data(seed):
    a = datagen.make(seed, total=900, d=3)
    b = datagen.make(seed, total=900, d=3)
    for x, z in zip(a, b):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(z))


def test_seeds_differ_also_above_32_bits():
    a = datagen.make(5, total=900, d=3)
    b = datagen.make(5 + 2**32, total=900, d=3)
    assert not np.array_equal(np.asarray(a.X_train), np.asarray(b.X_train))


def test_without_targets_keeps_inputs():
    a = datagen.make(3, total=900, d=3)
    b = datagen.make(3, total=900, d=3, with_targets=False)
    np.testing.assert_array_equal(np.asarray(a.X_train),
                                  np.asarray(b.X_train))
    np.testing.assert_array_equal(np.asarray(a.X_test), np.asarray(b.X_test))
