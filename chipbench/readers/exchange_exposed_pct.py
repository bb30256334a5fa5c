"""``exchange_exposed_pct``: share of the traced steady window in which a
collective is under way on the first device and no compute operation
runs there."""


def read(run):
    if run["trace"] is None:
        return None
    d = run["trace"]["devices"][0]
    if not d["collective_s"]:
        return None
    return 100.0 * d["exposed_collective_s"] / d["window_s"]
