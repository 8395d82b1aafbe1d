"""XLA compilations inside the window (JAX's backend_compile events): a
round size or program that set-up did not warm."""


def read(r):
    return r["compiles_in_window"]
