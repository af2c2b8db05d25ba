import math

import pytest

from benchmark import stats


def test_percentile_interpolates_between_order_statistics():
    xs = [10.0, 20.0, 30.0, 40.0, 50.0]
    assert stats.percentile(xs, 0) == 10.0
    assert stats.percentile(xs, 100) == 50.0
    assert stats.percentile(xs, 50) == 30.0
    # position (5-1)*0.95 = 3.8 -> 40 + 0.8*(50-40)
    assert stats.percentile(xs, 95) == pytest.approx(48.0)
    # unsorted input, position 0.25*3 = 0.75
    assert stats.percentile([4.0, 1.0, 3.0, 2.0], 25) == pytest.approx(1.75)


def test_percentile_matches_numpy_linear():
    np = pytest.importorskip("numpy")
    rs = np.random.RandomState(3)
    xs = rs.lognormal(size=401).tolist()
    for q in (1, 50, 90, 95, 99):
        assert stats.percentile(xs, q) == pytest.approx(
            float(np.percentile(xs, q)), rel=1e-12)


def test_percentile_of_nothing_is_an_error():
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_samples_beyond():
    assert stats.samples_beyond(400, 95) == 20
    assert stats.samples_beyond(199, 95) == 9


def test_whole_step_rate_is_over_whole_steps_and_their_own_time():
    # 7 steps of 16,384 tokens between syncs 2.5 s apart
    assert stats.whole_step_rate(16384, 7, 10.0, 12.5) == pytest.approx(
        7 * 16384 / 2.5)
    with pytest.raises(ValueError):
        stats.whole_step_rate(16384, 0, 10.0, 12.5)
    with pytest.raises(ValueError):
        stats.whole_step_rate(16384, 3, 12.5, 12.5)


def test_spread_is_the_drivers():
    import statistics
    xs = [100.0, 101.0, 99.0, 100.5, 102.0, 98.0]
    q1, _, q3 = statistics.quantiles(xs, n=4)
    assert stats.spread(xs) == pytest.approx(
        (q3 - q1) / statistics.median(xs))
    assert not math.isnan(stats.spread(xs))
