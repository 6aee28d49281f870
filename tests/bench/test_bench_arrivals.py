"""The benchmark's copied Poisson generator and request draws."""
import bench_cells  # noqa: F401  (puts the checkout on sys.path)
import numpy as np
import pytest

from bench import arrivals


@pytest.mark.parametrize("seed", [0, 2**31 + 5, 2**40 + 3])
def test_poisson_is_a_function_of_the_seed(seed):
    a = arrivals.poisson(np.random.default_rng([seed, 1]), 500.0, 4.0)
    b = arrivals.poisson(np.random.default_rng([seed, 1]), 500.0, 4.0)
    np.testing.assert_array_equal(a, b)
    assert np.all(np.diff(a) > 0) and a[0] >= 0 and a[-1] < 4.0
    c = arrivals.poisson(np.random.default_rng([seed + 1, 1]), 500.0, 4.0)
    assert a.shape != c.shape or not np.array_equal(a, c)


def test_poisson_rate():
    t = arrivals.poisson(np.random.default_rng(3), 2000.0, 20.0)
    assert abs(t.size / 20.0 - 2000.0) < 5 * np.sqrt(2000.0 * 20.0) / 20.0


def test_requests_stay_inside_the_window():
    r = arrivals.requests(np.random.default_rng(4), 5000, 200, (1, 16), 128)
    assert r["rows"].min() == 1 and r["rows"].max() == 16
    assert r["sensor"].min() >= 0 and r["sensor"].max() < 200
    assert np.all(r["start"] >= 0) and np.all(r["start"] + r["rows"] <= 128)
    again = arrivals.requests(np.random.default_rng(4), 5000, 200, (1, 16), 128)
    for k in r:
        np.testing.assert_array_equal(r[k], again[k])


def test_poisson_refuses_a_rate_of_zero():
    with pytest.raises(ValueError):
        arrivals.poisson(np.random.default_rng(0), 0.0, 1.0)
