"""Process start to the first timed step: imports, cache loading, data and
weights, compilation or its cache hits, and the checked first steps."""


def read(run):
    return run.setup_s
