"""STREAM-like microbenchmark suite -> machine file (DESIGN.md §1f).

The "Microbenchmark Characterization of the Emu Chick" methodology
(arXiv:1809.07696) applied to whatever this process runs on: measure what
the machine *sustains* — not what the datasheet promises — and write it
down so the cost models can speak seconds.

    python -m repro.machine.microbench --quick          # CI calibration
    python -m repro.machine.microbench --out path.json  # pinned location

Per registered substrate: sustained memory bandwidth in three access
classes (a jitted triad, a random-index gather, a random-index scatter —
the latter two are the paper's irregular-access measurement and differ
from the triad by 20-50x on XLA-CPU), per-call dispatch overhead (the
jit-call floor every prediction owes), and — when the host exposes >1 device — per-collective
alpha-beta models over the nodelet mesh axis (all_gather / all_to_all /
psum at several message sizes, least-squares fit to ``t = α + β·bytes``).
Plus one matmul peak-FLOPs probe and the host parallel-capacity probe the
serve suite pioneered. Single-device hosts get mesh collective terms
*derived* from local numbers (marked ``source="derived"``) instead of
silently keeping defaults.
"""
from __future__ import annotations

import argparse
import time
from typing import Callable, Iterable

import numpy as np

from .machine import (
    AlphaBeta,
    MachineProfile,
    Peaks,
    SubstrateProfile,
    default_machine_path,
    machine_fingerprint,
)

# message/buffer sizes (bytes) per mode; quick keeps CI calibration seconds
STREAM_SIZES = {"quick": (1 << 20, 4 << 20), "full": (4 << 20, 16 << 20, 64 << 20)}
COLLECTIVE_SIZES = {
    "quick": (16 << 10, 256 << 10, 1 << 20),
    "full": (16 << 10, 256 << 10, 4 << 20, 16 << 20),
}


def _median_seconds(fn: Callable[[], object], iters: int, warmup: int = 1) -> float:
    import jax

    for _ in range(warmup):
        jax.block_until_ready(fn())
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        times.append(time.perf_counter() - t0)
    times.sort()
    return times[len(times) // 2]


def fit_alpha_beta(
    nbytes: Iterable[float], seconds: Iterable[float]
) -> AlphaBeta:
    """Least-squares fit of ``t = alpha + beta * n`` with both terms clamped
    nonnegative (noisy small-message timings can produce a negative
    intercept; a negative latency or bandwidth is never meaningful)."""
    n = np.asarray(list(nbytes), dtype=np.float64)
    t = np.asarray(list(seconds), dtype=np.float64)
    if n.size == 0:
        raise ValueError("fit_alpha_beta needs at least one sample")
    if n.size == 1:
        return AlphaBeta(alpha=0.0, beta=float(t[0] / max(n[0], 1.0)))
    coeffs, *_ = np.linalg.lstsq(np.stack([np.ones_like(n), n], axis=1), t, rcond=None)
    alpha, beta = float(coeffs[0]), float(coeffs[1])
    if beta < 0:  # degenerate (timings not increasing): bandwidth-only refit
        beta = float(t.sum() / max(n.sum(), 1.0))
        alpha = 0.0
    return AlphaBeta(alpha=max(0.0, alpha), beta=max(0.0, beta))


def measure_stream_bw(sizes: "tuple[int, ...]", iters: int = 3) -> float:
    """Sustained bytes/s of a jitted scale-add triad (reads one array,
    writes one: 2 touched bytes per element-byte), max over buffer sizes —
    the STREAM number the memory term of every prediction divides by."""
    import jax
    import jax.numpy as jnp

    kernel = jax.jit(lambda x: x * 1.000001 + 0.5)
    best = 0.0
    for size in sizes:
        x = jnp.arange(size // 4, dtype=jnp.float32)
        sec = _median_seconds(lambda x=x: kernel(x), iters=iters)
        best = max(best, 2.0 * size / max(sec, 1e-9))
    return best


def _random_access_bw(kernel, sizes: "tuple[int, ...]", iters: int) -> float:
    """Shared harness for the random-access probes: run ``kernel(x, idx)``
    over random int32 indices at each size, charge 12 bytes per element
    (4B index read + 4B random data touch + 4B result), keep the best."""
    import jax.numpy as jnp

    rng = np.random.default_rng(7)
    best = 0.0
    for size in sizes:
        n = max(1, size // 12)
        x = jnp.arange(n, dtype=jnp.float32)
        idx = jnp.asarray(rng.integers(0, n, size=n).astype(np.int32))
        sec = _median_seconds(lambda x=x, idx=idx: kernel(x, idx), iters=iters)
        best = max(best, 12.0 * n / max(sec, 1e-9))
    return best


def measure_gather_bw(sizes: "tuple[int, ...]", iters: int = 3) -> float:
    """Sustained bytes/s of a jitted random-index *gather* (``x[idx]``) —
    the irregular-read analogue of the triad. SpMV-style kernels (random
    reads, sequential writes) see this rate."""
    import jax

    return _random_access_bw(jax.jit(lambda x, idx: x[idx] + 1.0), sizes, iters)


def measure_scatter_bw(sizes: "tuple[int, ...]", iters: int = 3) -> float:
    """Sustained bytes/s of a jitted random-index *scatter*
    (``x.at[idx].add``) — what frontier expansion and remote-write
    lowering actually execute. On XLA-CPU this is serialized and lands
    20-50x below the triad; charging scatter-bound sweeps at STREAM is
    precisely the unit-level model bug the band gate exists to catch."""
    import jax

    return _random_access_bw(
        jax.jit(lambda x, idx: x.at[idx].add(1.0)), sizes, iters
    )


def measure_dispatch_overhead(iters: int = 30) -> float:
    """Seconds per warm jitted call on a tiny operand — the per-call floor
    (trace-cache lookup + dispatch + sync) that dominates small problems."""
    import jax
    import jax.numpy as jnp

    kernel = jax.jit(lambda x: x + 1.0)
    x = jnp.zeros((8,), jnp.float32)
    return _median_seconds(lambda: kernel(x), iters=iters, warmup=3)


def measure_matmul_flops(n: int = 512, iters: int = 3) -> float:
    """Sustained FLOP/s of one jitted f32 matmul — the calibrated stand-in
    for the roofline's peak-FLOPs constant."""
    import jax
    import jax.numpy as jnp

    a = jnp.ones((n, n), jnp.float32)
    kernel = jax.jit(lambda a: a @ a)
    sec = _median_seconds(lambda: kernel(a), iters=iters)
    return 2.0 * n**3 / max(sec, 1e-9)


def measure_collectives(
    sizes: "tuple[int, ...]",
    kinds: "tuple[str, ...]" = ("all_gather", "all_to_all", "psum"),
    axis_name: str = "nodelet",
    iters: int = 3,
) -> dict[str, AlphaBeta]:
    """Alpha-beta models per collective over a 1-D mesh of every host
    device. Empty dict on single-device hosts (nothing to wire-measure)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from ..launch.mesh import make_nodelet_mesh

    n_dev = len(jax.devices())
    if n_dev < 2:
        return {}
    mesh = make_nodelet_mesh(n_dev)

    def body(kind):
        def f(x):
            if kind == "all_gather":
                return jax.lax.all_gather(x, axis_name, tiled=True)
            if kind == "all_to_all":
                return jax.lax.all_to_all(
                    x.reshape(n_dev, -1), axis_name, 0, 0, tiled=False
                )
            return jax.lax.psum(x, axis_name)

        return f

    out: dict[str, AlphaBeta] = {}
    for kind in kinds:
        f = jax.jit(
            jax.shard_map(
                body(kind), mesh=mesh, check_vma=False, in_specs=P(axis_name), out_specs=(
                    P() if kind == "psum" else P(axis_name)
                ),
            )
        )
        samples = []
        for size in sizes:
            elems = max(n_dev * n_dev, size // 4 // n_dev * n_dev)
            x = jnp.arange(elems, dtype=jnp.float32)
            sec = _median_seconds(lambda x=x: f(x), iters=iters)
            samples.append((elems * 4, sec))
        out[kind] = fit_alpha_beta(*zip(*samples))
    return out


def measure_host_parallel_capacity(quick: bool = True) -> float:
    """How much the host scales two concurrent GIL-releasing workers vs one
    (2.0 = perfect). The executor pool's speedup ceiling; recorded so a
    sub-linear pool reading on a throttled host stays interpretable."""
    import threading

    n = 192 if quick else 384
    reps = 6 if quick else 12
    a = np.random.default_rng(0).standard_normal((n, n))

    def work():
        for _ in range(reps):
            a @ a  # numpy dot releases the GIL

    def timed(k: int) -> float:
        threads = [threading.Thread(target=work) for _ in range(k)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        return time.perf_counter() - t0

    timed(1)  # warm the BLAS pool
    one, two = timed(1), timed(2)
    return max(1.0, 2.0 * one / max(two, 1e-9))


def calibrate(*, quick: bool = True, mesh_dispatch_iters: int = 5) -> MachineProfile:
    """Run the full suite and assemble a calibrated, fingerprinted
    :class:`MachineProfile` for this process's topology. Does not save —
    callers decide the path (:meth:`MachineProfile.save`)."""
    import jax

    mode = "quick" if quick else "full"
    stream = measure_stream_bw(STREAM_SIZES[mode])
    gather = measure_gather_bw(STREAM_SIZES[mode])
    scatter = measure_scatter_bw(STREAM_SIZES[mode])
    dispatch = measure_dispatch_overhead()
    flops = measure_matmul_flops(n=384 if quick else 1024)
    collectives = measure_collectives(COLLECTIVE_SIZES[mode])
    capacity = measure_host_parallel_capacity(quick=quick)

    local = SubstrateProfile(
        stream_bw=stream, dispatch_overhead=dispatch, collectives={},
        source="measured", gather_bw=gather, scatter_bw=scatter,
    )
    if collectives:
        # mesh dispatch overhead: one warm shard_map'd no-op collective call
        # at the smallest size is already folded into the alpha terms; take
        # the all_gather alpha as the per-call floor
        mesh_dispatch = max(dispatch, collectives["all_gather"].alpha)
        mesh = SubstrateProfile(
            stream_bw=stream, dispatch_overhead=mesh_dispatch,
            collectives=collectives, source="measured",
            gather_bw=gather, scatter_bw=scatter,
        )
        ici = max(1.0 / max(ab.beta, 1e-18) for ab in collectives.values())
    else:
        # single-device host: the mesh substrate would refuse multi-nodelet
        # plans anyway; derive wire terms from the memory system so
        # predictions stay finite and honest about their provenance
        mesh = SubstrateProfile(
            stream_bw=stream, dispatch_overhead=dispatch,
            collectives={
                k: AlphaBeta(alpha=dispatch, beta=2.0 / stream)
                for k in ("all_gather", "all_to_all", "psum")
            },
            source="derived", gather_bw=gather, scatter_bw=scatter,
        )
        ici = stream / 2.0
    profile = MachineProfile(
        fingerprint=machine_fingerprint(),
        peaks=Peaks(flops=flops, hbm_bw=stream, ici_bw=ici),
        substrates={"local": local, "mesh": mesh, "pallas": local},
        host_parallel_capacity=capacity,
        calibrated=True,
        quick=quick,
        created=time.strftime("%Y-%m-%dT%H:%M:%S%z"),
    )
    del jax, mesh_dispatch_iters
    return profile


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--quick", action="store_true", help="CI-fast sizes")
    ap.add_argument("--full", action="store_true", help="large-buffer sizes")
    ap.add_argument("--out", default=None, help="machine file path "
                    "(default: experiments/machine.json)")
    args = ap.parse_args(argv)
    profile = calibrate(quick=not args.full)
    path = profile.save(args.out if args.out else default_machine_path())
    local = profile.substrate("local")
    mesh = profile.substrate("mesh")
    print(f"# machine file -> {path}")
    print(f"# fingerprint: {profile.fingerprint}")
    print(
        f"# local: stream {local.stream_bw / 1e9:.2f} GB/s, "
        f"gather {local.access_bw('gather') / 1e9:.2f} GB/s, "
        f"scatter {local.access_bw('scatter') / 1e9:.3f} GB/s, "
        f"dispatch {local.dispatch_overhead * 1e6:.1f} us; "
        f"peak {profile.peaks.flops / 1e9:.1f} GFLOP/s; "
        f"host capacity {profile.host_parallel_capacity:.2f}x"
    )
    for kind, ab in sorted(mesh.collectives.items()):
        print(
            f"# mesh {kind} ({mesh.source}): alpha {ab.alpha * 1e6:.1f} us, "
            f"beta {1.0 / max(ab.beta, 1e-18) / 1e9:.2f} GB/s"
        )


if __name__ == "__main__":
    main()
