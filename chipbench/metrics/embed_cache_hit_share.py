"""Share of member embedding lookups served by the members' embed cache
in the window (EmbedCache hits over hits and misses), in percent."""


def read(r):
    n = r["cache_hits"] + r["cache_misses"]
    return 100.0 * r["cache_hits"] / n if n else None
