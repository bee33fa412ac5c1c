"""Client training samples consumed by local updates over the whole
window, per second of window and per chip (host clock)."""


def read(run):
    if run.cell.unit != "samples" or run.window_s <= 0:
        return None
    return run.work / run.window_s / run.chips
