"""A kernel's share of its HBM roofline, from the trace.

The work is counted from the problem (edges, nonzeros), not from the
program's padded layout, so it reads the same whatever implements the
kernel. The time is that of every XLA module that ran in the traced
window: the window serves one op, and no other program runs in it.
"""


def share(run, bytes_key: str):
    if run.trace is None:
        return None
    seconds = run.trace.modules_s()
    moved = run.total(bytes_key)
    if not seconds or moved is None:
        return None
    return 100.0 * moved / run.peaks["hbm_bytes_per_s"] / seconds
