"""Milliseconds per round in which the device runs no operation while the
host is inside the program's `run_round` span but not in a pull of results
(`fetch`, `eval_fetch`): idle time the driver's own work causes."""
from benchlib import spans

spans.start()


def read(run):
    split = spans.reading(run, spans.idle_split_ms)
    return None if split is None else split[0]
