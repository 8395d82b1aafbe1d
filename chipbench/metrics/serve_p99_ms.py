"""99th percentile (nearest rank) of the latency of every request due in
the window, each timed from when it was due; a failed request counts
with the time it waited until the run stopped waiting."""
from chipbench.stats import percentile


def read(r):
    return percentile(r["latency_ms"], 99)
