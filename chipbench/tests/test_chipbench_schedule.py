"""The open-loop schedule: fixed by the seed, timed from the due time,
shed requests counted as failed."""
import threading

import numpy as np

from chipbench.kinds import serve
from chipbench.stats import percentile

ZIPF = {"users": "zipf", "zipf_alpha": 1.0}
UNIFORM = {"users": "uniform"}


def test_schedule_is_fixed_by_the_seed():
    a = serve.schedule(ZIPF, 1000, 500.0, 4.0, np.random.default_rng([7, 2]))
    b = serve.schedule(ZIPF, 1000, 500.0, 4.0, np.random.default_rng([7, 2]))
    c = serve.schedule(ZIPF, 1000, 500.0, 4.0, np.random.default_rng([8, 2]))
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)
    assert not np.array_equal(a[0], c[0])
    # every seed offers the same number of requests over the window
    assert len(a[0]) == len(c[0]) == 2000
    due = a[0]
    assert np.all(np.diff(due) >= 0) and due[0] >= 0 and due[-1] < 4.0


def test_large_seed_draws_a_schedule():
    due, users = serve.schedule(UNIFORM, 50, 10.0, 1.0,
                                np.random.default_rng([2**31 + 12345, 2]))
    assert len(due) == 10 and users.max() < 50


def test_zipf_users_skew_and_uniform_users_do_not():
    rng = np.random.default_rng(0)
    z = serve.draw_users(ZIPF, 10000, 200000, rng)
    u = serve.draw_users(UNIFORM, 10000, 200000, rng)
    top = np.sort(np.bincount(z, minlength=10000))[::-1]
    # Zipf(1) over 10,000 users: the 100 most popular take ~53%
    assert 0.45 < top[:100].sum() / 200000 < 0.6
    assert np.bincount(u, minlength=10000).max() < 60


class _Handle:
    def __init__(self):
        self.done = threading.Event()
        self.err = None
        self.scores = None
        self.t_done = 0.0


class _Shed(Exception):
    pass


def test_latency_runs_from_the_due_time_and_shed_is_failed():
    """A fake clock: the server answers each request 5 ms after it was
    submitted, but the generator submits request 2 late by 30 ms and
    admission sheds request 3; request 4 is never answered."""
    now = [0.0]
    due = np.array([0.0, 0.01, 0.02, 0.03, 0.04])
    users = np.arange(5)
    handles = []

    def clock():
        return now[0]

    def submit(rows):
        i = len(handles)
        if i == 2:
            now[0] += 0.03                 # the generator stalls
        if i == 3:
            handles.append(None)
            raise _Shed()
        h = _Handle()
        h.scores = np.full((1, 2), float(rows[0]))
        if i != 4:
            h.t_done = now[0] + 0.005
            h.done.set()
        handles.append(h)
        return h

    import time
    sleep = time.sleep
    time.sleep = lambda s: now.__setitem__(0, now[0] + s)
    try:
        outcomes, late, backlog = serve.offer(submit, due, users, 1.0,
                                              clock, _Shed)
    finally:
        time.sleep = sleep
    out = serve.tally(outcomes, due, users, 1.0, t_wait=1.5)
    assert out["attempted"] == 5
    assert out["shed"] == 1 and out["unanswered"] == 1 and out["failed"] == 2
    lat = out["latency_ms"]
    np.testing.assert_allclose(lat[:2], [5.0, 5.0], atol=1e-6)
    # late submission: 30 ms stall + 5 ms service, counted from due
    np.testing.assert_allclose(lat[2], 35.0, atol=1e-6)
    # failed requests wait until the run stopped waiting
    np.testing.assert_allclose(lat[3], (1.5 - 1.03) * 1e3, atol=1e-6)
    np.testing.assert_allclose(lat[4], (1.5 - 1.04) * 1e3, atol=1e-6)
    np.testing.assert_allclose(late[2], 0.03, atol=1e-9)
    np.testing.assert_array_equal(out["rows"], [0, 1, 2])
    assert [float(x[0, 0]) for x in out["scores"]] == [0.0, 1.0, 2.0]
    assert outcomes.open == {4: handles[4]}      # answered handles released
    assert percentile(lat, 50) == lat[2]


def test_percentile_is_nearest_rank_over_all_values():
    v = np.arange(1, 201, dtype=float)
    assert percentile(v, 99) == 198.0
    assert percentile(v, 50) == 100.0
    assert percentile([3.0], 99) == 3.0
