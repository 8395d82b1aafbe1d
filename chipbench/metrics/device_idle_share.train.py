"""Percent of the traced window in which no op ran on the device: one
minus the union of the device ops' intervals over the window, from the
profiler's trace (averaged over the chips used)."""
from chipbench.trace import idle_share


def read(r):
    return idle_share(r)
