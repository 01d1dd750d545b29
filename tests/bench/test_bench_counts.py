"""Operation counts, the peak table and the readers."""

import pytest

from bench import counts, peaks, readers
from bench.harness import ReaderInput
from bench.trace import TraceSummary


def test_posterior_counts_by_hand():
    # n = 2, nt = 1, d = 1: 2*4*1 + 8/3 + 2*4 + 2*2*1 + 2*2 + 4*1 + 2*2*1
    f, b = counts.posterior(2, 1, 1, full_cov=False)
    assert f == pytest.approx(8 + 8 / 3 + 8 + 4 + 4 + 4 + 4)
    assert b == 4 * ((2 + 1) * 1 + 2 + 2) + 4 * 4
    # full covariance: the prior block 2*1*1*1 and V^T V 2*2*1*1 instead of the diagonal
    f, b = counts.posterior(2, 1, 1, full_cov=True)
    assert f == pytest.approx(8 + 8 / 3 + 8 + 4 + 4 + 4 + 2 + 4)
    assert b == 4 * ((2 + 1) * 1 + 2 + 2) + 4 * 4


def test_posterior_counts_at_paper_scale():
    f, _ = counts.posterior(16384, 16384, 16, full_cov=False)
    assert f == pytest.approx(16384**3 / 3 + 16384**3, rel=5e-3)


def test_peak_table():
    p = peaks.peak("TPU v5 lite")
    assert (p.flops_per_s, p.bytes_per_s, p.memory_bytes) == (197e12, 819e9, 16e9)
    with pytest.raises(KeyError):
        peaks.peak("TPU v9 imaginary")


def _summary(busy, window, n_ops=10, spans=None):
    return TraceSummary(window, busy, 1, n_ops, [], [], spans or {})


def test_readers():
    p = peaks.Peak(100.0, 10.0, 1.0)
    r = ReaderInput(_summary(2.0, 4.0, 30, {"front_end_call": [0.01, 0.03]}), 3, 20.0, 1.0, p)
    assert readers.dispatch_ms(r) == pytest.approx(20.0)
    assert readers.device_ops(r) == 10
    assert readers.device_idle(r) == pytest.approx(50.0)
    # least time max(20/100, 1/10) = 0.2 s over 2/3 s busy per iteration
    assert readers.roofline(r) == pytest.approx(30.0)


def test_readers_find_nothing_to_read():
    r = ReaderInput(_summary(0.0, 4.0, 0), 3, 20.0, 1.0, peaks.Peak(1.0, 1.0, 1.0))
    assert readers.dispatch_ms(r) is None
    assert readers.device_ops(r) is None
    assert readers.device_idle(r) is None
    assert readers.roofline(r) is None
