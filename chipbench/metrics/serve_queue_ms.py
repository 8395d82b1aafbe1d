"""Mean milliseconds a request waited in the batcher's admission queue
(ServeStats.queue_s over the requests completed in the window)."""


def read(r):
    n = r["completed"]
    return 1e3 * r["queue_s"] / n if n else None
