"""Host milliseconds per round that the program spends inside its
`run_round` span and outside its pulls of results (`fetch`, `eval_fetch`):
the fleet driver's own work, from the program's span store on the trace's
clock."""
from benchlib import spans

spans.start()


def read(run):
    return spans.reading(run, spans.driver_ms)
