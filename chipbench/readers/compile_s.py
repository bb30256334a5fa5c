"""``compile_s``: host seconds around ``.lower().compile()`` of the step,
a fetch from the persistent cache included."""


def read(run):
    return run["compile_s"]
