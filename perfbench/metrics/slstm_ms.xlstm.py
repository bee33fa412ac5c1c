"""Device milliseconds per training step of the operations under the
program's `slstm` named scope: the sLSTM layers (`nn/xlstm.py`) with their
scans over the tokens, forward, backward and recomputed, without their
blocks' pre-norms and feed-forward sub-blocks."""
from benchlib import scopes


def read(run):
    return scopes.ms_per_step(run, ["slstm"])
