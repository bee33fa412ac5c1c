"""Milliseconds per round in which the device runs no operation while the
host is in one of the program's pulls of results (`fetch` of the round's
metrics, `eval_fetch` of the eval hit counts)."""
from benchlib import spans

spans.start()


def read(run):
    split = spans.reading(run, spans.idle_split_ms)
    return None if split is None else split[1]
