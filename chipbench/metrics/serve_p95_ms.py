"""95th percentile (nearest rank) of the latency of every request due in
the window, each timed from when it was due (see serve_p99_ms)."""
from chipbench.stats import percentile


def read(r):
    return percentile(r["latency_ms"], 95)
