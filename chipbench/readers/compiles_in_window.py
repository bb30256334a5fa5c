"""``compiles_in_window``: programs handed to the compiler between the
window's opening and its close (``jax.monitoring``); must be 0."""


def read(run):
    return run["compiles_in_window"]
