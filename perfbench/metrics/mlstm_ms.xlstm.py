"""Device milliseconds per training step of the operations under the
program's `mlstm` named scope: the mLSTM layers (`nn/xlstm.py`) forward,
backward and recomputed, without their blocks' pre-norms."""
from benchlib import scopes


def read(run):
    return scopes.ms_per_step(run, ["mlstm"])
