"""Cluster plane (repro/cluster/): multi-process serving, substrate, failover.

ISSUE 9 acceptance, as tests:

- a 2-worker localhost cluster serves a mixed SpMV/BFS/MoE-dispatch stream
  **bit-identically** to in-process ``engine.run`` — the request-level wire
  path (``Coordinator.submit``), with requests actually distributed across
  both worker processes;
- ``EngineService(substrate="cluster")`` drives the PR-5 executor pool over
  process-spanning placement slots (the kernel-level path), same parity;
- SIGKILLing one worker mid-load leaves **every future terminated** and the
  retried results bit-identical (ops are pure, so replaying an in-flight
  request on a survivor is safe), with the death visible in the stats
  (failovers/retries) and the topology fingerprint (plan-cache keys must
  not alias across memberships).

The launcher/backends and the autoscaler signal (``resize_signal``) are
pinned with process-free unit tests at the bottom — they must not cost a
cluster launch to check a pod manifest or a threshold comparison.
"""
import json
import signal
import time

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import partition_ell
from repro.engine import (
    BFSInputs,
    EngineService,
    MoEDispatchInputs,
    Request,
    ServiceStats,
    SpMVInputs,
    get_substrate,
    run,
)
from repro.sparse import edges_to_csr, erdos_renyi_edges, laplacian_2d, partition_graph


def _mixed_requests(n: int, seed: int = 0):
    rng = np.random.default_rng(seed)
    spmv_pool = []
    for size in (8, 12):
        a = partition_ell(laplacian_2d(size), 4)
        x = jnp.asarray(rng.standard_normal(size * size).astype(np.float32))
        spmv_pool.append(SpMVInputs(a, x))
    g = partition_graph(edges_to_csr(erdos_renyi_edges(6, 4, seed=seed), 64), 4)
    moe = MoEDispatchInputs(
        x=jnp.asarray(rng.standard_normal((16, 8)).astype(np.float32)),
        router=jnp.asarray(rng.standard_normal((8, 4)).astype(np.float32)),
        nodelets=2,
    )
    requests = []
    for i in range(n):
        if i % 4 == 2:
            requests.append(Request("bfs", BFSInputs(g, 0)))
        elif i % 4 == 3:
            requests.append(Request("moe_dispatch", moe))
        else:
            requests.append(Request("spmv", spmv_pool[i % 2]))
    return requests


def _assert_bit_identical(a, b):
    import jax

    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# -- live 2-worker cluster (module-scoped: one launch pays for all) -----------


@pytest.fixture(scope="module")
def cluster():
    from repro.cluster import launch_cluster

    with launch_cluster(n_workers=2, service_workers=1) as c:
        yield c


def test_submit_parity_and_distribution(cluster):
    requests = _mixed_requests(12)
    futures = [cluster.submit(r) for r in requests]
    responses = [f.result(timeout=300) for f in futures]
    for request, response in zip(requests, responses):
        oracle, _ = run(request, iters=1, warmup=0)
        _assert_bit_identical(response.result, oracle)
        assert response.report is not None
    stats = cluster.stats()
    served = {w["worker_id"]: w["served"] for w in stats["workers"]}
    assert sum(served.values()) >= len(requests)
    assert sum(1 for n in served.values() if n > 0) == 2, served
    assert stats["n_healthy"] == 2
    assert stats["retries"] == 0 and stats["failovers"] == 0


def test_sticky_placement_pins_same_signature_to_one_worker(cluster):
    requests = _mixed_requests(8)
    spmv_like = [r for r in requests if r.op == "spmv"][:4]
    responses = [cluster.submit(r).result(timeout=300) for r in spmv_like]
    by_signature = {}
    for request, response in zip(spmv_like, responses):
        key = id(request.inputs.a)  # two pooled signatures alternate
        by_signature.setdefault(key, set()).add(response.worker_id)
    for workers in by_signature.values():
        assert len(workers) == 1  # a signature never bounces between workers


def test_remote_errors_propagate_and_are_not_retried(cluster):
    bad = Request("spmv", _mixed_requests(4)[2].inputs)  # BFS inputs to spmv
    before = cluster.stats()["retries"]
    from repro.cluster import RemoteOpError

    with pytest.raises(RemoteOpError):
        cluster.submit(bad).result(timeout=300)
    assert cluster.stats()["retries"] == before  # deterministic: no retry
    assert cluster.stats()["n_healthy"] == 2  # and no worker was condemned


def test_cluster_substrate_spans_processes(cluster):
    sub = get_substrate("cluster")
    assert sub.placement_slots() == 2
    fp = sub.cache_fingerprint()
    assert fp[0] == "cluster"
    generation, members = fp[1]
    assert len(members) == 2  # topology is part of every plan-cache key
    assert sub.jit_plans is False  # socket I/O must stay out of jax.jit


def test_engine_service_pool_over_cluster_substrate(cluster):
    requests = _mixed_requests(8)
    svc = EngineService(substrate="cluster", workers=2).start()
    try:
        futures = [
            svc.submit(Request(r.op, r.inputs, r.strategy, "cluster"))
            for r in requests
        ]
        responses = [f.result(timeout=300) for f in futures]
    finally:
        svc.stop()
    assert len(responses) == len(requests)
    for request, response in zip(requests, responses):
        oracle, _ = run(request, iters=1, warmup=0)
        _assert_bit_identical(response.result, oracle)
    assert cluster.stats()["kernel_calls"] > 0  # genuinely crossed processes
    stats = svc.stats()
    assert stats.workers == 2
    assert stats.resize_signal() in ("grow", "hold", "shrink")


# -- failover (own cluster: this one loses a worker) --------------------------


def test_sigkill_failover_terminates_every_future_with_parity():
    from repro.cluster import launch_cluster

    with launch_cluster(
        n_workers=2, service_workers=1, activate=False,
        heartbeat_interval=0.2, heartbeat_timeout=3.0,
    ) as cluster:
        fp_before = cluster.coordinator.topology_fingerprint()
        requests = _mixed_requests(12, seed=1)
        futures = [cluster.submit(r) for r in requests]
        victim = cluster.coordinator.healthy_workers()[0].worker_id
        cluster.kill_worker(victim, sig=signal.SIGKILL)
        responses = [f.result(timeout=300) for f in futures]  # all terminate
        for request, response in zip(requests, responses):
            oracle, _ = run(request, iters=1, warmup=0)
            _assert_bit_identical(response.result, oracle)
        stats = cluster.stats()
        assert stats["failovers"] == 1
        assert stats["n_healthy"] == 1
        dead = [w for w in stats["workers"] if w["worker_id"] == victim]
        assert dead and dead[0]["state"] == "dead"
        # survivors absorbed the victim's load; membership re-fingerprints
        # so no plan-cache entry aliases across the two topologies
        assert cluster.coordinator.topology_fingerprint() != fp_before
        survivor_served = sum(
            w["served"] for w in stats["workers"] if w["worker_id"] != victim
        )
        assert survivor_served > 0


# -- launcher backends and supervisor (no processes needed) -------------------


def test_k8s_backend_emits_pod_spec_but_does_not_schedule():
    from repro.cluster import K8sBackend, WorkerSpec

    spec = WorkerSpec(
        worker_id=3, connect=("10.0.0.7", 4242), substrate="local", token="tok",
    )
    backend = K8sBackend(image="repro-serving:v1", namespace="serving")
    pod = backend.pod_spec(spec)
    assert pod["kind"] == "Pod"
    assert pod["metadata"]["name"] == "repro-worker-3"
    assert pod["metadata"]["namespace"] == "serving"
    container = pod["spec"]["containers"][0]
    assert container["image"] == "repro-serving:v1"
    assert container["command"] == spec.argv()
    assert "--connect" in container["command"]
    assert "10.0.0.7:4242" in container["command"]
    assert {"name": "REPRO_CLUSTER_TOKEN", "value": "tok"} in container["env"]
    json.dumps(pod)  # manifest must be plain-JSON appliable
    with pytest.raises(NotImplementedError):
        backend.start(spec)


def test_process_supervisor_restart_budget():
    from repro.runtime.supervisor import ProcessSupervisor

    class Fake:
        def __init__(self):
            self.returncode = None

    spawned = []

    def restart():
        handle = Fake()
        spawned.append(handle)
        return handle

    sup = ProcessSupervisor(max_restarts=1)
    first = Fake()
    sup.watch("w", first, alive=lambda h: h.returncode is None, restart=restart)
    assert sup.poll() == []  # alive: nothing to report
    first.returncode = -9
    (event,) = sup.poll()
    assert event.restarted and event.restarts == 1
    assert sup.handles()["w"] is spawned[0]
    spawned[0].returncode = 1
    (event,) = sup.poll()
    assert not event.restarted  # budget exhausted
    assert sup.poll() == []  # idempotent on a process already seen down


def test_worker_spec_argv_is_reproducible_entrypoint():
    from repro.cluster import WorkerSpec

    argv = WorkerSpec(worker_id=0, connect=("127.0.0.1", 9000)).argv()
    assert argv[1:3] == ["-m", "repro.cluster.worker"]
    assert "--worker-id" in argv and "0" in argv


# -- resize signal (autoscaler trigger; pure threshold logic) -----------------


def _stats(occupancy, wall=10.0):
    return ServiceStats(
        requests=8, wall_seconds=wall, workers=len(occupancy),
        worker_occupancy=list(occupancy),
        occupancy_hwm=max(occupancy, default=0.0),
    )


def test_resize_signal_grow_on_saturated_pool():
    assert _stats([0.9, 0.8]).resize_signal() == "grow"
    assert _stats([0.75, 0.75]).resize_signal() == "grow"  # mean at threshold


def test_resize_signal_shrink_on_idle_pool():
    assert _stats([0.1, 0.2]).resize_signal() == "shrink"
    # a single worker never shrinks below itself
    assert _stats([0.05]).resize_signal() == "hold"


def test_resize_signal_hold_between_thresholds_and_on_empty():
    assert _stats([0.5, 0.4]).resize_signal() == "hold"
    # one busy worker keeps the pool: max occupancy above shrink line
    assert _stats([0.9, 0.05]).resize_signal() == "hold"
    assert _stats([]).resize_signal() == "hold"
    assert _stats([0.9], wall=0.0).resize_signal() == "hold"


def test_resize_signal_custom_thresholds_and_to_dict():
    stats = _stats([0.6, 0.6])
    assert stats.resize_signal(grow_above=0.5) == "grow"
    assert _stats([0.3, 0.3]).resize_signal(shrink_below=0.35) == "shrink"
    row = stats.to_dict()
    assert row["resize_signal"] == "hold"
    assert row["occupancy_hwm"] == 0.6
    assert row["worker_occupancy"] == [0.6, 0.6]


# -- chip ownership and compile errors -----------------------------------------


def test_launch_refuses_chip_owning_workers_on_an_accelerator(monkeypatch):
    """A chip belongs to one process: on a TPU host the launcher refuses
    workers that would inherit this process's chips, before starting any."""
    import jax

    from repro.cluster import launch_cluster
    from repro.cluster.coordinator import ClusterError

    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    started = []
    monkeypatch.setattr(
        "repro.cluster.launch.LocalProcessBackend.start",
        lambda self, spec: started.append(spec),
    )
    with pytest.raises(ClusterError, match="one process per chip"):
        launch_cluster(n_workers=1)
    assert started == []


class _FakeSubstrate:
    """Just enough substrate for the worker's forwarded-kernel cache."""

    def __init__(self, kern):
        self._kern = kern

    def cache_fingerprint(self):
        return ("fake", id(self))

    def kernel(self, op):
        return self._kern


def test_worker_kernel_cache_propagates_compile_errors():
    """Only the tracer's host-side errors fall back to eager; any other
    error from compiling a forwarded kernel propagates, and the kernel is
    not rerun op by op."""
    from repro.cluster.worker import _KernelCache

    calls = []

    def refused(x):
        calls.append(type(x).__name__)
        raise NotImplementedError("Only 2D gather is supported")

    with pytest.raises(NotImplementedError, match="2D gather"):
        _KernelCache().call(_FakeSubstrate(refused), "spmv", (jnp.ones(4),), {})
    assert len(calls) == 1  # traced once, never rerun eagerly


def test_worker_kernel_cache_runs_host_side_kernels_eagerly():
    from repro.cluster.worker import _KernelCache

    def host_side(x):
        return jnp.asarray(np.asarray(x) * 2)  # needs concrete values

    cache = _KernelCache()
    sub = _FakeSubstrate(host_side)
    for _ in range(2):  # second call hits the pinned-eager entry
        out = cache.call(sub, "host", (jnp.arange(3.0),), {})
        np.testing.assert_array_equal(np.asarray(out), [0.0, 2.0, 4.0])
