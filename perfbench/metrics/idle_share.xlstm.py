"""Share of the traced window, in %, in which no operation ran on the
device during the LM client's steps: 1 - (union of device op intervals /
window), averaged over chips."""


def read(run):
    t = run.trace
    if t is None or not t.ops or t.window_s <= 0:
        return None
    return 100.0 * (1.0 - t.mean_busy_s() / t.window_s)
