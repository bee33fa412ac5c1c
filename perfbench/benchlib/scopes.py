"""Device time under a program's named scopes, read from a reduced trace
(`benchlib.trace.Trace`).

JAX writes a `jax.named_scope` into an operation's name stack as a path
element of its own (`.../mlstm/...`), or wrapped in the transformations
applied under it (`vmap(jvp(cors_loss))`, `vmap(transpose(jvp(head)))` for
the backward pass); `Trace.scope_s` reads only the first form. A scope
counts here in either form.
"""
from __future__ import annotations

import re
from typing import Optional, Sequence


def _matcher(scopes: Sequence[str]):
    names = "|".join(re.escape(s) for s in scopes)
    rx = re.compile(r"(?:[\w.]+\()*(?:%s)\)*" % names)
    return lambda op: any(rx.fullmatch(e) for e in op.scope.split("/"))


def scope_s(trace, scopes: Sequence[str]) -> float:
    """Device seconds of the window's ops under any of `scopes`, averaged
    over the device planes."""
    return trace._sum(_matcher(scopes))


def ms_per_step(run, scopes: Sequence[str]) -> Optional[float]:
    """Device milliseconds per traced step under `scopes`; None without a
    trace, or where no op carries them (a program without these scopes)."""
    t = run.trace
    if t is None or t.steps == 0:
        return None
    s = scope_s(t, scopes)
    return s / t.steps * 1e3 if s > 0 else None
