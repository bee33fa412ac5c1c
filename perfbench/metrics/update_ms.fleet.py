"""Device milliseconds per round of the operations under the round step's
`update` named scope (the participants' local updates)."""


def read(run):
    t = run.trace
    if t is None or t.steps == 0:
        return None
    s = t.scope_s(["update"])
    return s / t.steps * 1e3 if s > 0 else None
