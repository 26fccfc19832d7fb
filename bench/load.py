"""The one traffic generator: reads a mix's parameters and drives a cell.

A mix (``bench/traffic/<name>.json``) is data:

- ``{"loop": "closed", "clients": C}``: C clients, each sends its next
  request when its last result is on the host, until ``seconds`` have
  passed; the requests then in flight finish and count.

Each request is ``cell.request(i)``; its result is fetched to the host
(``numpy.asarray``) and handed to ``cell.keep``. Host spans
(``bench.submit``, ``bench.wait``, ``bench.fetch``) name
what the client did, for the trace's idle gaps.
"""
from __future__ import annotations

import dataclasses
import math
import threading
import time

import numpy as np
from jax.profiler import TraceAnnotation

# a request due in the window may finish this long after it closes
GRACE_S = 60.0


@dataclasses.dataclass
class Record:
    index: int
    tag: int
    due: float
    submit: float
    done: float = math.nan  # result on the host; nan if it never came
    error: str | None = None

    @property
    def ok(self) -> bool:
        return self.error is None and not math.isnan(self.done)


@dataclasses.dataclass
class Window:
    t0: float
    t1: float  # the last result on the host
    records: list[Record]

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0


def _serve(cell, record: Record, future, lock: threading.Lock) -> None:
    try:
        with TraceAnnotation("bench.wait"):
            response = future.result(timeout=max(1.0, record.due + GRACE_S - time.perf_counter()))
        with TraceAnnotation("bench.fetch"):
            host = np.asarray(response.result)
        record.done = time.perf_counter()
        with lock:
            cell.keep(record.index, record.tag, host)
    except Exception as exc:  # the client records the failure and goes on
        record.error = f"{type(exc).__name__}: {exc}"


def closed_loop(service, cell, seconds: float, clients: int = 1) -> Window:
    lock = threading.Lock()
    records: list[Record] = []
    t0 = time.perf_counter()
    end = t0 + seconds

    def client():
        while time.perf_counter() < end:
            with lock:
                index = len(records)
                request, tag = cell.request(index)
                now = time.perf_counter()
                record = Record(index, tag, due=now, submit=now)
                records.append(record)
            with TraceAnnotation("bench.submit"):
                future = service.submit(request)
            _serve(cell, record, future, lock)

    threads = [threading.Thread(target=client, name=f"bench-client-{c}") for c in range(clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    done = [r.done for r in records if r.ok]
    return Window(t0, max(done, default=end), records)


def drive(traffic: dict, service, cell, seconds: float) -> Window:
    if traffic["loop"] == "closed":
        return closed_loop(service, cell, seconds, int(traffic.get("clients", 1)))
    raise ValueError(f"unknown loop {traffic['loop']!r}")
