"""Device-to-host pulls per round: the program's `host_syncs` counter over
the window's rounds (one per array pulled), over the rounds."""
from benchlib import spans

spans.start()


def read(run):
    n = spans.reading(run, spans.counter, "host_syncs")
    return None if n is None else n / run.trace.steps
