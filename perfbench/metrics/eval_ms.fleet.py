"""Device milliseconds per round in the per-round eval program (the
jitted stacked-client hit count, module `jit_hits`)."""


def read(run):
    t = run.trace
    if t is None or t.steps == 0:
        return None
    s = t.module_s(r"\bjit_hits\b|jit\(hits\)")
    return s / t.steps * 1e3 if s > 0 else None
