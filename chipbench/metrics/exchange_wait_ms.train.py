"""Milliseconds per round the master waited on the channel for the
members' activations (its CommStats.recv_wait_s over the window)."""


def read(r):
    return 1e3 * r["exchange_wait_s"] / r["steps"] if r["steps"] else None
