"""Mean milliseconds of the program's ``engine.derived`` span over the
requests the timed service served (the window): the op's traffic model,
bytes moved, metrics and prediction after each product, and for SpMV the
lookups of the stats it keeps per matrix (no scan of ``cols`` once a
matrix has been seen). Read from the service's span totals; None for a
program without the span."""


def read(run):
    seconds = run.service_stats.get("span_seconds", {}).get("engine.derived")
    count = run.service_stats.get("span_counts", {}).get("engine.derived")
    return seconds / count * 1e3 if count else None
