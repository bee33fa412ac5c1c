"""Device milliseconds per training step of the LM head and the CoRS
objective, forward and backward: the operations under the program's
`head` (`models/lm.py`) and `cors_loss` (`launch/train.py`: cross-entropy,
the KD pull and the sampled discriminator) named scopes."""
from benchlib import scopes


def read(run):
    return scopes.ms_per_step(run, ["head", "cors_loss"])
