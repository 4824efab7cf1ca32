"""The benchmark's copies of the data, arrival and user generators give the
program's data for a seed (the on/off process: its distribution)."""
import numpy as np
import pytest

from perfbench import arrivals, data


def test_data_copy_gives_the_programs_data():
    from repro.data import synthetic_poi
    kw = dict(n_users=150, n_items=110, n_ratings=1200, n_cities=5)
    for seed in (0, 3):
        ours = data.generate(data.DataConfig(**kw, seed=seed))
        theirs = synthetic_poi.generate(
            synthetic_poi.POIDatasetConfig(**kw, seed=seed))
        for f in ("train", "test", "user_coords", "user_city", "item_city"):
            np.testing.assert_array_equal(getattr(ours, f), getattr(theirs, f))


def test_poisson_and_zipf_copies_give_the_programs_stream():
    from repro.scheduling import workload
    cfg = workload.WorkloadConfig(n_requests=30000, rate_rps=5000.0,
                                  users="powerlaw", zipf_s=1.1, seed=9)
    want = np.diff(workload.arrival_times(cfg, np.random.default_rng(9)))
    got = np.diff(arrivals.poisson_fixed(5000.0, 6.0, np.random.default_rng(9)))
    assert len(got) == len(want)
    for stat in (np.mean, np.std, np.median):       # exponential gaps alike
        assert stat(got) == pytest.approx(stat(want), rel=0.03)
    cfg = workload.WorkloadConfig(n_requests=3000, users="powerlaw",
                                  zipf_s=1.1, seed=9)
    want_u = workload.sample_users(cfg, 777, np.random.default_rng(4))
    np.testing.assert_array_equal(
        arrivals.zipf_users(3000, 777, 1.1, np.random.default_rng(4)), want_u)


@pytest.mark.parametrize("burst,duty,period", [(4.0, 0.2, 0.05), (2.0, 0.3, 0.02)])
def test_vectorised_onoff_matches_the_programs_moments(burst, duty, period):
    from repro.scheduling import workload
    rate, seconds = 3000.0, 60.0
    ours = arrivals.onoff(rate, seconds, np.random.default_rng(1),
                          burst_factor=burst, duty_cycle=duty, period_s=period)
    cfg = workload.WorkloadConfig(n_requests=len(ours), rate_rps=rate,
                                  process="onoff", burst_factor=burst,
                                  duty_cycle=duty, period_s=period)
    theirs = workload.arrival_times(cfg, np.random.default_rng(2))

    def moments(t):
        phase = (t % period) / period
        counts = np.bincount((t // period).astype(int))
        return (len(t) / (t[-1] - t[0]), np.mean(phase < duty),
                counts.mean(), counts.var())

    a, b = moments(ours), moments(theirs)
    assert a[0] == pytest.approx(rate, rel=0.02)
    assert a[0] == pytest.approx(b[0], rel=0.03)       # mean rate
    assert a[1] == pytest.approx(b[1], abs=0.01)       # share in ON windows
    assert a[1] == pytest.approx(burst * duty, abs=0.01)
    assert a[2] == pytest.approx(b[2], rel=0.03)       # per-period count
    assert a[3] == pytest.approx(b[3], rel=0.15)       # and its variance
    assert (np.diff(ours) >= 0).all() and ours.min() >= 0 and ours.max() < seconds


def test_window_arrivals_fill_the_window_for_any_seed():
    trf = {"arrivals": {"process": "poisson", "rate_rps": 2000.0}}
    gaps = []
    for seed in (0, 2**31 + 11):
        t = arrivals.window_arrivals(trf, 3.0, np.random.default_rng(seed))
        assert t[0] == 0.0 and t[-1] < 3.0 and len(t) == 6000
        assert (np.diff(t) >= 0).all()
        gaps.append(np.diff(t))
    # every seed offers the same gaps, in another order
    assert not np.array_equal(gaps[0], gaps[1])
    np.testing.assert_allclose(np.sort(gaps[0]), np.sort(gaps[1]), atol=1e-12)
    # and they are exponential at the rate: mean 1/rate, sd about the mean
    assert gaps[0].mean() == pytest.approx(1 / 2000.0, rel=0.01)
    assert gaps[0].std() == pytest.approx(1 / 2000.0, rel=0.05)
