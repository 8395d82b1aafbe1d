"""Set-up: process start to the window's start (JAX start, silos and
weights on the device, the federation's set-up, compilation or cache
loads, warm-up), on the host clock."""


def read(r):
    return r["setup_s"]
