"""Share, in %, of the traced window in which no operation ran on the
device while BFS was served: 1 - (union of XLA op intervals) / window."""


def read(run):
    return None if run.trace is None else run.trace.idle_pct()
