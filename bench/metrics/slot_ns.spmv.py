"""Nanoseconds of device time per padded index slot that the SpMV program
gathers: the summed time of the XLA modules in the traced window over the
program's ``spmv.slots`` counter, summed over the requests the timed
service served. It tells fewer slots from cheaper ones. None for a program
without the counter."""


def read(run):
    slots = run.service_stats.get("counters", {}).get("spmv.slots")
    seconds = None if run.trace is None else run.trace.modules_s()
    return seconds / slots * 1e9 if slots and seconds else None
