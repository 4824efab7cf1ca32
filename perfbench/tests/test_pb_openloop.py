"""The serving open loop times each request from when it was due, against
a fake engine with a fixed service time."""
import time

import numpy as np
import pytest

from perfbench.kinds import serve

SERVICE_S = 0.004


class FixedEngine:
    def __init__(self):
        self.batches = []

    def serve_microbatch(self, users):
        time.sleep(SERVICE_S)
        self.batches.append(len(users))
        n = len(users)
        return (np.zeros((n, 2), np.float32), np.tile(np.int32(users)[:, None],
                                                       (1, 2)), SERVICE_S)


def session(times, R=4):
    s = serve.Session.__new__(serve.Session)
    s.engine, s.R, s.k = FixedEngine(), R, 2
    s.times = np.asarray(times, np.float64)
    s.users = np.arange(len(times))
    return s


def test_a_burst_waits_for_the_dispatches_ahead_of_it():
    s = session([0.0] * 10)
    rec = s.window(1.0)
    assert s.engine.batches == [4, 4, 2]
    lat = rec["done"] - rec["times"]
    wait = rec["start"] - rec["times"]
    # the k-th dispatch ends no earlier than k service times after the due time
    for k, rows in enumerate((slice(0, 4), slice(4, 8), slice(8, 10)), 1):
        assert (lat[rows] >= k * SERVICE_S).all()
        assert (wait[rows] >= (k - 1) * SERVICE_S).all()
    assert (rec["ids"][:, 0] == np.arange(10)).all()
    e2e = s.end_to_end(rec)
    assert e2e["serve_p95_ms"] >= 3 * SERVICE_S * 1e3
    assert e2e["serve_rps"] == 10.0


def test_spaced_arrivals_are_served_one_by_one_from_their_due_time():
    times = np.arange(8) * 0.02
    s = session(times)
    rec = s.window(1.0)
    assert s.engine.batches == [1] * 8
    lat = rec["done"] - rec["times"]
    assert (lat >= SERVICE_S).all()
    assert np.median(lat) < 4 * SERVICE_S      # nothing queues behind
    assert (rec["start"] >= rec["times"]).all()


def test_requests_due_after_the_window_are_not_offered():
    s = session([0.0, 0.1, 0.5, 2.0])
    rec = s.window(1.0)
    assert len(rec["times"]) == 3 and sum(s.engine.batches) == 3
    assert s.end_to_end(rec)["serve_rps"] == pytest.approx(3.0)
