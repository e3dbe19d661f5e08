import numpy as np
import pytest

import traffic

POISSON = {"process": "poisson"}


@pytest.mark.parametrize("seed", [0, 2**31 + 5])
def test_count_is_fixed_and_times_lie_in_the_window(seed):
    t = traffic.arrivals(POISSON, 37.3, 20.0, np.random.default_rng(seed))
    assert len(t) == round(37.3 * 20.0)
    assert np.all(np.diff(t) >= 0) and t[0] >= 0.0 and t[-1] < 20.0


def test_same_seed_same_arrivals():
    a = traffic.arrivals(POISSON, 50.0, 5.0, np.random.default_rng(3))
    b = traffic.arrivals(POISSON, 50.0, 5.0, np.random.default_rng(3))
    c = traffic.arrivals(POISSON, 50.0, 5.0, np.random.default_rng(4))
    assert np.array_equal(a, b) and not np.array_equal(a, c)


def test_every_seed_offers_the_same_gaps():
    """The seed reorders one set of Poisson-shaped gaps and never changes
    them, so the work a window offers does not depend on the seed."""
    def gaps(seed):
        t = traffic.arrivals(POISSON, 100.0, 200.0,
                             np.random.default_rng(seed))
        return np.sort(np.diff(np.concatenate([[0.0], t, [200.0]])))

    a, b = gaps(0), gaps(1)
    assert 0.9 < a.std() / a.mean() < 1.1
    np.testing.assert_allclose(a, b, rtol=1e-9, atol=1e-9)


def test_unknown_process_is_an_error():
    with pytest.raises(ValueError):
        traffic.arrivals({"process": "mmpp"}, 1.0, 1.0,
                         np.random.default_rng(0))


def test_mix_file_loads():
    mix = traffic.load_mix("steady")
    assert mix["process"] == "poisson" and mix["load"] > 0
