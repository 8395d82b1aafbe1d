"""Every sample of every round in the window, over the whole window
(host clock, from before the fit call to the end of the last round)."""


def read(r):
    return r["samples"] / r["window_s"]
