"""The benchmark's data generators."""

import numpy as np

from bench.data import arbo, msd


def test_msd_copy_matches_the_repository_simulator():
    from repro.data.msd import make_dataset

    ours = msd.make_dataset(200, 100, 16, 11)
    theirs = make_dataset(200, 100, seed=11)
    for a, b in zip(ours, theirs):
        assert a.shape == b.shape and a.dtype == b.dtype
        np.testing.assert_allclose(a, b, rtol=0, atol=1e-6)


def test_msd_seed_changes_values_not_shapes():
    a = msd.make_dataset(64, 32, 16, 1)
    b = msd.make_dataset(64, 32, 16, 2**31 + 5)
    assert [x.shape for x in a] == [x.shape for x in b]
    assert not np.allclose(a[1], b[1])


CFG = {"problems": 16, "size_min": 8, "size_max": 64, "size_seed": 3, "s_max": 64,
       "input_scales": [10.0, 1.0, 10.0], "candidates": 5}


def test_arbo_sizes_come_from_the_configuration_alone():
    s = arbo.sizes(CFG)
    assert s.shape == (16,) and s.min() >= 8 and s.max() <= 64
    xa, ya = arbo.make_fleet(CFG, 1)
    xb, yb = arbo.make_fleet(CFG, 99)
    assert [x.shape for x in xa] == [x.shape for x in xb] == [(n, 3) for n in s]
    assert not np.allclose(ya[0], yb[0])
    for y in ya:
        assert abs(float(y.mean())) < 1e-5 and abs(float(y.std()) - 1.0) < 1e-4


def test_arbo_candidates_share_input_scale_and_load():
    c = arbo.candidates(CFG, np.random.default_rng(0))
    assert len(c) == 16 and all(x.shape == (5, 3) for x in c)
    np.testing.assert_allclose(c[0][:, 0] * 10.0, np.linspace(1.0, 64.0, 5), rtol=1e-6)
    assert np.ptp(c[0][:, 1]) == 0 and np.ptp(c[0][:, 2]) == 0
