"""Device milliseconds per training step of Adam's update of the whole
parameter tree: the operations under the program's `adam` named scope
(`launch/train.py`)."""
from benchlib import scopes


def read(run):
    return scopes.ms_per_step(run, ["adam"])
