"""``allreduce_ms``: time per step during which a collective operation is
under way on the first device (an asynchronous one from its start to its
done), from the trace."""


def read(run):
    if run["trace"] is None:
        return None
    d = run["trace"]["devices"][0]
    if not d["steps"] or not d["collective_s"]:
        return None
    return 1e3 * d["collective_s"] / d["steps"]
