"""``cache_retrieval_s``: seconds the program's compile ledger holds for
fetching the step program's executable from the persistent cache, before
the window opened; 0 on a cold run (``chipbench/scopes.py``)."""

from chipbench import scopes


def read(run):
    return scopes.step_compile_seconds(run, ("cache_retrieval",))
