"""Traces of a jitted program inside the window: the program's `traces`
counter, bumped in the Python bodies of its round step and eval, which
run only when JAX traces them (each such trace compiles or hits the
cache). The window should see none."""
from benchlib import spans

spans.start()


def read(run):
    n = spans.reading(run, spans.counter, "traces")
    return None if n is None else float(n)
