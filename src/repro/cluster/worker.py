"""Cluster worker process: one `EngineService` behind a socket (§1h).

Spawned by the launcher as ``python -m repro.cluster.worker --connect
HOST:PORT --worker-id K``, it dials back to the coordinator, sends a
``hello``, and serves the protocol until ``shutdown`` or EOF:

- ``submit`` — rebuild the :class:`~repro.engine.request.Request` from its
  wire form and run it through this process's own :class:`EngineService`
  worker loop. The worker therefore has everything the in-process serving
  plane has — plan cache with jitted executables, QoS, admission — which is
  what makes cluster results *structurally* bit-identical to
  ``engine.run``: the same pipeline executes, one process over.
- ``kernel_call`` — execute one substrate kernel on forwarded arguments
  (the :class:`~repro.cluster.substrate.ClusterSubstrate` fast path).
  Calls are wrapped in ``jax.jit`` with Python-scalar positional arguments
  pinned static — mirroring how the in-process plan cache closes over
  statics — and cached per value-independent signature, so repeated calls
  hit a warm executable. Kernels that need concrete values on the host
  fall back to eager, once, and stay pinned eager; a compile error
  propagates.
- ``submit_many`` — a coordinator-coalesced frame: each item is a full
  submit (ticket + request) sharing the frame's segment table; they fan
  out to the pool exactly as if they had arrived one frame each.
- ``put_blob`` / ``blob_gone`` — content-addressed data plane: shipped
  blobs land in a byte-budgeted LRU :class:`~repro.cluster.blobs.BlobStore`
  (digest-verified — corrupt shipments are refused); requests referencing
  a ``blobref`` this worker no longer holds block in ``ensure`` while a
  ``need_blob`` round trip re-fetches the bytes.
- ``ping`` — answered inline by the reader thread, *never* queued behind
  compute, so a busy worker still heartbeats and only a dead or truly hung
  process misses its deadline.

Log records from the ``repro`` logger tree are forwarded to the
coordinator as ``log`` messages (one line of a worker's warning shows up
in the coordinator's log, attributed to the worker).
"""
from __future__ import annotations

import argparse
import logging
import os
import socket
import threading
from concurrent.futures import ThreadPoolExecutor
from typing import Any

from .protocol import Channel

log = logging.getLogger("repro.cluster.worker")


class _ForwardingLogHandler(logging.Handler):
    """Ships ``repro.*`` log records to the coordinator as ``log`` frames."""

    def __init__(self, channel: Channel, worker_id: int):
        super().__init__(level=logging.INFO)
        self._channel = channel
        self._worker_id = worker_id

    def emit(self, record: logging.LogRecord) -> None:
        if record.name.startswith("repro.cluster"):
            return  # don't forward our own transport chatter (loop risk)
        try:
            self._channel.send({
                "kind": "log",
                "worker_id": self._worker_id,
                "level": record.levelname,
                "logger": record.name,
                "msg": self.format(record),
            })
        except Exception:
            pass  # a dying channel must not take the service down


class _KernelCache:
    """Warm per-signature executables for forwarded kernel calls.

    Key: (op, value-independent argument signature, canonical kwargs).
    Python-scalar positional args are made ``static_argnums`` — the same
    constant-folding the in-process executor gets by closing over them —
    so e.g. a BFS ``root`` or gsana ``k`` compiles exactly as it would
    have locally. A kernel that needs concrete values on the host (the
    tracer's concretization/conversion errors) runs eager and the key is
    pinned eager from then on; every other error propagates.
    """

    def __init__(self):
        self._lock = threading.Lock()
        self._fns: dict[Any, Any] = {}

    def call(self, substrate: Any, op: str, args: tuple, kwargs: dict) -> Any:
        import jax

        from ..engine.api import args_signature
        from ..engine.wire import canonical_bytes

        key = (
            op,
            substrate.cache_fingerprint(),
            args_signature(args),
            canonical_bytes(kwargs),
        )
        with self._lock:
            fn = self._fns.get(key)
        if fn is not None:
            return fn(*args)
        kern = substrate.kernel(op)
        static = tuple(
            i
            for i, a in enumerate(args)
            if a is None or isinstance(a, (bool, int, float, str))
        )
        jitted = jax.jit(lambda *xs: kern(*xs, **kwargs), static_argnums=static)
        try:
            result = jitted(*args)
            chosen = jitted
        except (
            jax.errors.ConcretizationTypeError,
            jax.errors.TracerArrayConversionError,
            jax.errors.TracerIntegerConversionError,
        ):
            # host-side work the tracer cannot see: run (and stay) eager.
            # Any other error (a compile refusal, a bad argument) propagates:
            # rerunning it op by op would only hide it behind a slow path.
            def chosen(*xs):
                return kern(*xs, **kwargs)

            result = chosen(*args)
        with self._lock:
            self._fns[key] = chosen
        return result


def serve(
    connect: "tuple[str, int]",
    worker_id: int,
    *,
    substrate: str = "local",
    service_workers: int = 2,
    token: "str | None" = None,
) -> None:
    """Dial the coordinator and serve until ``shutdown`` or EOF."""
    from ..engine.request import Request
    from ..engine.service import EngineService
    from ..engine.substrate import get_substrate
    from ..engine.wire import (
        SegmentTable,
        collect_blob_digests,
        decode_value,
        encode_value,
    )
    from .blobs import BlobMissing, BlobStore

    token = token if token is not None else os.environ.get("REPRO_CLUSTER_TOKEN", "")
    sock = socket.create_connection(connect, timeout=30)
    sock.settimeout(None)
    channel = Channel(sock)
    handler = _ForwardingLogHandler(channel, worker_id)
    logging.getLogger("repro").addHandler(handler)

    service = EngineService(substrate=substrate, workers=service_workers)
    service.start()
    sub = get_substrate(substrate)
    kernels = _KernelCache()
    blob_store = BlobStore()
    pool = ThreadPoolExecutor(
        max_workers=max(2, service_workers), thread_name_prefix=f"w{worker_id}"
    )
    channel.send({
        "kind": "hello",
        "worker_id": worker_id,
        "pid": os.getpid(),
        "token": token,
        "substrate": substrate,
        "slots": sub.placement_slots(),
    })

    def request_blobs(missing: "list[str]") -> None:
        channel.send({"kind": "need_blob", "digests": missing})

    def decode_with_blobs(decode):
        """Run ``decode()`` with every referenced blob present, re-fetching
        via ``need_blob`` when the LRU evicted one between arrival and
        decode (bounded — a blob the coordinator cannot produce raises)."""
        for _attempt in range(3):
            try:
                return decode()
            except BlobMissing as exc:
                blob_store.ensure([exc.digest], request_blobs)
        return decode()

    def finish_submit(ticket: int, payload: dict) -> None:
        try:
            digests = collect_blob_digests(payload)
            if digests:
                blob_store.ensure(digests, request_blobs)
            request = decode_with_blobs(
                lambda: Request.from_wire(
                    payload, blob_resolver=blob_store.resolve
                )
            )
            response = service.submit(request).result()
            table = SegmentTable()
            channel.send({
                "kind": "result",
                "ticket": ticket,
                "result": encode_value(response.result, segments=table),
                "report": encode_value(response.report, segments=table),
            }, table.segments)
        except Exception as exc:  # noqa: BLE001 — every ticket must answer
            _send_error(ticket, exc)

    def finish_kernel(ticket: int, message: dict) -> None:
        try:
            digests = collect_blob_digests([message["args"], message["kwargs"]])
            if digests:
                blob_store.ensure(digests, request_blobs)
            args, kwargs = decode_with_blobs(
                lambda: (
                    decode_value(
                        message["args"], blob_resolver=blob_store.resolve
                    ),
                    decode_value(
                        message["kwargs"], blob_resolver=blob_store.resolve
                    ),
                )
            )
            result = kernels.call(sub, message["op"], tuple(args), kwargs)
            table = SegmentTable()
            channel.send({
                "kind": "result",
                "ticket": ticket,
                "result": encode_value(result, segments=table),
                "report": None,
            }, table.segments)
        except Exception as exc:  # noqa: BLE001
            _send_error(ticket, exc)

    def _send_error(ticket: int, exc: BaseException) -> None:
        try:
            channel.send({
                "kind": "error",
                "ticket": ticket,
                "etype": type(exc).__name__,
                "error": str(exc),
            })
        except Exception:
            pass

    try:
        while True:
            message = channel.recv()
            if message is None:
                break  # coordinator gone
            kind = message["kind"]
            if kind == "ping":
                channel.send({"kind": "pong", "inflight": len(service)})
            elif kind == "submit":
                pool.submit(finish_submit, message["ticket"], message["request"])
            elif kind == "submit_many":
                for item in message["items"]:
                    pool.submit(finish_submit, item["ticket"], item["request"])
            elif kind == "put_blob":
                # verify-then-store inline on the reader: the bytes must be
                # in the store before any frame referencing them decodes
                try:
                    blob_store.put(
                        message["digest"], decode_value(message["blob"])
                    )
                except Exception:
                    log.exception(
                        "worker %d: refused blob %s", worker_id,
                        message.get("digest"),
                    )
            elif kind == "blob_gone":
                blob_store.mark_gone(message["digest"])
            elif kind == "kernel_call":
                pool.submit(finish_kernel, message["ticket"], message)
            elif kind == "stats":
                stats = service.stats()
                stats.wire_bytes_sent = channel.bytes_sent
                stats.wire_bytes_received = channel.bytes_received
                store_stats = blob_store.stats()
                stats.blob_hits = store_stats["hits"]
                stats.blob_misses = store_stats["misses"]
                row = stats.to_dict()
                row["blob_store"] = store_stats
                channel.send({
                    "kind": "stats_reply",
                    "ticket": message["ticket"],
                    "stats": row,
                })
            elif kind == "shutdown":
                break
            else:
                log.warning("worker %d: unknown message kind %r", worker_id, kind)
    finally:
        pool.shutdown(wait=False)
        try:
            service.stop(drain=False)
        except Exception:
            pass
        logging.getLogger("repro").removeHandler(handler)
        channel.close()


def main(argv: "list[str] | None" = None) -> None:
    parser = argparse.ArgumentParser(description="repro cluster worker process")
    parser.add_argument("--connect", required=True, help="coordinator HOST:PORT")
    parser.add_argument("--worker-id", type=int, required=True)
    parser.add_argument("--substrate", default="local")
    parser.add_argument("--service-workers", type=int, default=2)
    args = parser.parse_args(argv)
    from ..runtime.compile_cache import enable_compile_cache

    enable_compile_cache()
    host, _, port = args.connect.rpartition(":")
    serve(
        (host, int(port)),
        args.worker_id,
        substrate=args.substrate,
        service_workers=args.service_workers,
    )


if __name__ == "__main__":
    main()
