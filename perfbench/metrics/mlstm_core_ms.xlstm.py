"""Device milliseconds per training step of the operations under the
program's `mlstm_core` named scope: the mLSTM layers' chunkwise core
(`nn/xlstm.py` `mlstm_chunked`: scores, intra- and inter-chunk terms and
the chunk states) forward, backward and recomputed. A part of
`mlstm_ms.xlstm`; None for a program without that scope."""
from benchlib import scopes


def read(run):
    return scopes.ms_per_step(run, ["mlstm_core"])
