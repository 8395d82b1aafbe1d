"""Repository-wide pytest settings: a time limit for every test.

A test that blocks on a wait with no end would hold its xdist worker,
and every later test of its file, until the whole run is cut. Here each
phase of a test (setup, call, teardown) gets ``LIMIT_S`` seconds. On
expiry every thread's stack is dumped to stderr (so the report shows
where it waited) and the phase fails with a message naming the limit;
the worker then goes on to the next test. The alarm is ``SIGALRM`` in
the main thread, where pytest and its xdist workers run tests.
"""
import contextlib
import faulthandler
import signal
import threading

import pytest

# more than 3x the slowest passing test (about 36 s on a CPU host)
LIMIT_S = 120


def _expire(signum, frame):
    faulthandler.dump_traceback(file=2, all_threads=True)
    pytest.fail(f"exceeded the per-test time limit of {LIMIT_S} s "
                f"(LIMIT_S in conftest.py)")


@contextlib.contextmanager
def _time_limit():
    if threading.current_thread() is not threading.main_thread():
        yield
        return
    previous = signal.signal(signal.SIGALRM, _expire)
    signal.alarm(LIMIT_S)
    try:
        yield
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, previous)


@pytest.hookimpl(wrapper=True)
def pytest_runtest_setup(item):
    with _time_limit():
        return (yield)


@pytest.hookimpl(wrapper=True)
def pytest_runtest_call(item):
    with _time_limit():
        return (yield)


@pytest.hookimpl(wrapper=True)
def pytest_runtest_teardown(item, nextitem):
    with _time_limit():
        return (yield)
