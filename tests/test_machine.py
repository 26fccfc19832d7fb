"""Calibration plane: machine file lifecycle, alpha-beta fits, and the
predicted-seconds contract (DESIGN.md §1f).

ISSUE 6 acceptance: with a calibrated machine file the autotuner ranks in
predicted wall seconds and RunReports carry the model-honesty columns; with
no machine file every ranking and report is bit-identical to the
traffic-unit behavior. The rank-correlation tests check the prediction
*ordering* against exhaustive measured engine sweeps (Spearman on the
sweep's reported traffic, the same cross-check lens test_autotune.py uses —
wall seconds on the single-device local oracle are noise for
execution-inert strategy axes)."""
import json
import warnings

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import bucketize, generate_alignment_pair, partition_ell, pick_grid
from repro.engine import (
    BFSInputs,
    GSANAInputs,
    PlanCache,
    ProbeStore,
    SpMVInputs,
    autotune,
    candidate_grid,
    rank_strategies,
    run,
)
from repro.machine import (
    DEFAULT_PROFILE,
    AlphaBeta,
    MachineProfile,
    Peaks,
    PerformanceModel,
    SubstrateProfile,
    default_machine,
    fit_alpha_beta,
    load_machine,
    machine_fingerprint,
    reset_default_machine_cache,
)
from repro.sparse import (
    edges_to_csr,
    erdos_renyi_edges,
    laplacian_2d,
    partition_graph,
    rmat_edges,
    skewed_matrix,
)


def _calibrated_profile(fingerprint=None) -> MachineProfile:
    """A synthetic calibrated profile (no measurement): plausible sustained
    rates, fingerprinted to this topology unless told otherwise."""
    sub = SubstrateProfile(
        stream_bw=10e9,
        dispatch_overhead=20e-6,
        collectives={
            "all_gather": AlphaBeta(alpha=50e-6, beta=1.0 / 5e9),
            "all_to_all": AlphaBeta(alpha=50e-6, beta=1.0 / 5e9),
            "psum": AlphaBeta(alpha=50e-6, beta=1.0 / 5e9),
        },
        source="measured",
    )
    return MachineProfile(
        fingerprint=fingerprint if fingerprint is not None else machine_fingerprint(),
        peaks=Peaks(flops=1e12, hbm_bw=10e9, ici_bw=5e9),
        substrates={"local": sub, "mesh": sub, "pallas": sub},
        host_parallel_capacity=1.8,
        calibrated=True,
        created="2026-08-09T00:00:00",
    )


@pytest.fixture
def calibrated_machine(tmp_path, monkeypatch):
    """A calibrated machine file installed as the process default."""
    path = tmp_path / "machine.json"
    _calibrated_profile().save(path)
    monkeypatch.setenv("REPRO_MACHINE_PATH", str(path))
    reset_default_machine_cache()
    yield path
    reset_default_machine_cache()


# -- machine file lifecycle ----------------------------------------------------


def test_machine_file_roundtrip(tmp_path):
    profile = _calibrated_profile()
    path = profile.save(tmp_path / "machine.json")
    loaded = load_machine(path)
    assert loaded is not None
    assert loaded.calibrated
    assert loaded.fingerprint == profile.fingerprint
    assert loaded.peaks == profile.peaks
    assert loaded.substrate("local").collective("all_gather") == AlphaBeta(
        alpha=50e-6, beta=1.0 / 5e9
    )
    assert loaded.host_parallel_capacity == pytest.approx(1.8)


def test_absent_machine_file_is_silent_none(tmp_path):
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        assert load_machine(tmp_path / "never_written.json") is None


@pytest.mark.parametrize("payload", [
    '{"peaks": {',                 # truncated
    '{"peaks": null}',             # wrong shape
    '{}',                          # missing peaks entirely
    'null',                        # not an object
])
def test_corrupt_machine_file_warns_and_falls_back(tmp_path, payload):
    path = tmp_path / "machine.json"
    path.write_text(payload)
    with pytest.warns(RuntimeWarning, match="corrupt machine file"):
        assert load_machine(path) is None


def test_newer_schema_machine_file_warns(tmp_path):
    blob = _calibrated_profile().to_dict()
    blob["version"] = 999
    path = tmp_path / "machine.json"
    path.write_text(json.dumps(blob))
    with pytest.warns(RuntimeWarning, match="schema v999"):
        assert load_machine(path) is None


def test_stale_fingerprint_rejected_unless_allowed(tmp_path):
    foreign = dict(machine_fingerprint(), device_count=424242)
    path = _calibrated_profile(fingerprint=foreign).save(tmp_path / "machine.json")
    with pytest.warns(RuntimeWarning, match="different topology"):
        assert load_machine(path) is None
    assert load_machine(path, allow_stale=True) is not None


def test_default_profile_is_uncalibrated_with_roofline_peaks():
    # the session fixture points REPRO_MACHINE_PATH at a nonexistent file
    profile = default_machine()
    assert profile.calibrated is False
    assert profile.stale() is False  # the bundled default claims no topology
    # the bundled peaks are the roofline's former hardcoded constants
    assert DEFAULT_PROFILE.peaks == Peaks(flops=197e12, hbm_bw=819e9, ici_bw=50e9)
    # unknown substrate degrades to the local profile, never raises
    assert profile.substrate("tpu-pod") == profile.substrate("local")


def test_chip_peaks_come_from_the_device_kind_table(monkeypatch):
    """On an accelerator the peaks are the device kind's published row; a
    kind missing from the table is an error, never the CPU default."""
    import jax

    from repro.machine.machine import CHIP_PEAKS, NOT_A_CHIP, chip_peaks

    v5e = chip_peaks("TPU v5 lite")
    assert (v5e.flops, v5e.hbm_bw) == (197e12, 819e9)
    assert "TPU v5e" in v5e.source and v5e.source != NOT_A_CHIP
    assert DEFAULT_PROFILE.peaks.source == NOT_A_CHIP
    with pytest.raises(ValueError, match="no published peaks"):
        chip_peaks("TPU v99")

    class _Chip:
        def __init__(self, kind):
            self.device_kind = kind

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "devices", lambda: [_Chip("TPU v5 lite")])
    reset_default_machine_cache()
    assert default_machine().peaks == CHIP_PEAKS["TPU v5 lite"]
    monkeypatch.setattr(jax, "devices", lambda: [_Chip("TPU v99")])
    reset_default_machine_cache()
    with pytest.raises(ValueError, match="TPU v99"):
        default_machine()
    monkeypatch.undo()
    reset_default_machine_cache()
    assert default_machine().peaks == DEFAULT_PROFILE.peaks


def test_default_machine_cache_tracks_mtime(tmp_path, monkeypatch):
    path = tmp_path / "machine.json"
    monkeypatch.setenv("REPRO_MACHINE_PATH", str(path))
    reset_default_machine_cache()
    assert default_machine().calibrated is False
    _calibrated_profile().save(path)
    assert default_machine().calibrated is True  # picked up without a reset
    reset_default_machine_cache()


# -- alpha-beta fitting --------------------------------------------------------


def test_fit_alpha_beta_recovers_synthetic_model():
    alpha, beta = 2e-4, 1.0 / 5e9
    sizes = [1e4, 1e5, 1e6, 1e7]
    fit = fit_alpha_beta(sizes, [alpha + beta * n for n in sizes])
    assert fit.alpha == pytest.approx(alpha, rel=1e-6)
    assert fit.beta == pytest.approx(beta, rel=1e-6)
    assert fit.seconds(1e6, launches=2.0) == pytest.approx(2 * alpha + beta * 1e6)


def test_fit_alpha_beta_clamps_noise_nonnegative():
    # constant timings (pure latency): beta degenerates but never negative
    fit = fit_alpha_beta([1e3, 1e4, 1e5], [1e-4, 1e-4, 1e-4])
    assert fit.alpha >= 0.0 and fit.beta >= 0.0
    # decreasing timings (timer noise): bandwidth-only refit, still nonneg
    fit = fit_alpha_beta([1e3, 1e6], [5e-4, 1e-4])
    assert fit.alpha >= 0.0 and fit.beta >= 0.0
    with pytest.raises(ValueError):
        fit_alpha_beta([], [])


# -- predicted-seconds vs exhaustive measured sweeps ---------------------------


def _spmv_inputs(kind: str) -> SpMVInputs:
    if kind == "laplacian":
        a, n = laplacian_2d(10), 100
    else:
        a, n = skewed_matrix(400, 6, 48, seed=1), 400
    lens = np.diff(np.asarray(a.indptr))
    x = jnp.asarray(np.random.default_rng(0).standard_normal(n).astype(np.float32))
    return SpMVInputs(partition_ell(a, 8, k=int(lens.max())), x)


def _bfs_inputs(kind: str) -> BFSInputs:
    scale = 8
    edges = (
        erdos_renyi_edges(scale, 6, seed=7) if kind == "er"
        else rmat_edges(scale, 6, seed=7)
    )
    return BFSInputs(partition_graph(edges_to_csr(edges, 1 << scale), 8), 0)


def _gsana_inputs(n: int) -> GSANAInputs:
    vs1, vs2, pi = generate_alignment_pair(n, seed=3)
    grid = pick_grid(n, 32)
    cap = max(bucketize(vs1, grid).cap, bucketize(vs2, grid).cap)
    return GSANAInputs(
        vs1, vs2, bucketize(vs1, grid, cap=cap), bucketize(vs2, grid, cap=cap),
        ground_truth=pi,
    )


def _spearman(a, b) -> float:
    """Spearman rank correlation with average ranks for ties."""

    def ranks(xs):
        order = np.argsort(xs, kind="stable")
        r = np.empty(len(xs))
        i = 0
        while i < len(xs):
            j = i
            while j + 1 < len(xs) and xs[order[j + 1]] == xs[order[i]]:
                j += 1
            r[order[i : j + 1]] = (i + j) / 2.0
            i = j + 1
        return r
    ra, rb = ranks(np.asarray(a, float)), ranks(np.asarray(b, float))
    da, db = ra - ra.mean(), rb - rb.mean()
    denom = np.sqrt((da**2).sum() * (db**2).sum())
    if denom == 0:  # all ties on a side: orderings cannot disagree
        return 1.0
    return float((da * db).sum() / denom)


SCENARIOS = [
    ("spmv", "laplacian"),
    ("spmv", "skewed"),
    ("bfs", "er"),
    ("bfs", "rmat"),
    ("gsana", "n128"),
    ("gsana", "n192"),
]


def _inputs_for(op: str, case: str):
    if op == "spmv":
        return _spmv_inputs(case)
    if op == "bfs":
        return _bfs_inputs(case)
    return _gsana_inputs(128 if case == "n128" else 192)


@pytest.mark.parametrize("op,case", SCENARIOS)
def test_predicted_seconds_rank_correlates_with_measured_sweep(op, case):
    """The prediction's *ordering* must agree (Spearman >= 0.7) with an
    exhaustive engine sweep's measured traffic on the local substrate."""
    inputs = _inputs_for(op, case)
    model = PerformanceModel(_calibrated_profile())
    ranked = rank_strategies(op, inputs, machine=_calibrated_profile())
    assert all(e.predicted_seconds is not None for e in ranked)
    # predicted seconds are sorted best-first by construction
    preds = [e.predicted_seconds for e in ranked]
    assert preds == sorted(preds)

    cache = PlanCache()
    by_strategy = {}
    for st in candidate_grid(op):
        _, rep = run(op, inputs, st, "local", iters=1, warmup=0, cache=cache)
        by_strategy[st] = rep.traffic.total_bytes
    measured = [by_strategy[e.strategy] for e in ranked]
    rho = _spearman(preds, measured)
    assert rho >= 0.7, f"Spearman {rho:.3f} for {op}/{case}: {list(zip(preds, measured))}"
    # the model-optimal pick also achieves the sweep's measured minimum
    assert by_strategy[ranked[0].strategy] == min(measured)
    # prediction parts are finite, nonnegative, and sum to the total
    parts = model.predict_parts(ranked[0], "local")
    assert all(v >= 0.0 for v in parts.values())
    assert sum(parts.values()) == pytest.approx(ranked[0].predicted_seconds)


# -- calibrated engine behavior ------------------------------------------------


def test_calibrated_auto_ranks_in_predicted_seconds(calibrated_machine):
    inputs = _spmv_inputs("laplacian")
    tuned = autotune("spmv", inputs, "local")
    assert tuned.ranked_by == "predicted_seconds"
    assert all(c.predicted_seconds is not None for c in tuned.candidates)
    assert "predicted_seconds" in tuned.table()[0]
    _, rep = run("spmv", inputs, "auto", "local", cache=PlanCache())
    assert rep.strategy["replicate_x"] is True  # same pick, now in seconds
    assert rep.predicted_seconds is not None and rep.predicted_seconds > 0
    assert rep.model_error == pytest.approx(rep.predicted_seconds / rep.seconds)
    row = rep.to_dict()
    assert row["predicted_seconds"] == rep.predicted_seconds
    assert row["model_error"] == rep.model_error


def test_uncalibrated_fallback_is_bit_identical():
    # session fixture: no machine file -> the traffic-unit contract
    inputs = _bfs_inputs("er")
    ranked = rank_strategies("bfs", inputs)
    assert all(e.predicted_seconds is None for e in ranked)
    keys = [e.rank_key() for e in ranked]
    assert keys == sorted(keys)  # pure traffic-unit ordering
    tuned = autotune("bfs", inputs, "local")
    assert tuned.ranked_by == "traffic_bytes"
    assert "predicted_seconds" not in tuned.table()[0]
    _, rep = run("bfs", inputs, "auto", "local", cache=PlanCache())
    assert rep.predicted_seconds is None and rep.model_error is None
    row = rep.to_dict()
    assert "predicted_seconds" not in row and "model_error" not in row


# -- probe store fingerprinting ------------------------------------------------

KEY = ("spmv", ("local",), ("remote_write", True, "hcb", "pair", None), (), "sig")


def test_probe_store_ignores_and_prunes_foreign_fingerprints(tmp_path):
    from repro.machine import fingerprint_key

    path = tmp_path / "probes.json"
    foreign = fingerprint_key(dict(machine_fingerprint(), device_count=424242))
    path.write_text(json.dumps({
        "version": 2,
        "probes": {
            ProbeStore.encode_key(KEY): {"seconds": 0.25, "machine": foreign},
            "legacy-v1-entry": 0.125,  # schema v1: no provenance
        },
    }))
    store = ProbeStore(path)
    assert len(store) == 2  # loaded, but...
    assert store.get(KEY) is None  # ...foreign entries read as absent
    assert store.stale == 1
    store.record(KEY, 0.5)  # re-measured here
    store.save()
    assert store.pruned == 1  # the legacy v1 entry; KEY was overwritten
    saved = json.loads(path.read_text())
    assert saved["version"] == 2
    assert list(saved["probes"]) == [ProbeStore.encode_key(KEY)]
    fresh = ProbeStore(path)
    assert fresh.get(KEY) == 0.5  # same machine: served
    assert fresh.reused == 1


def test_probe_store_roundtrip_carries_this_machine(tmp_path):
    path = tmp_path / "probes.json"
    store = ProbeStore(path)
    store.record(KEY, 0.125)
    store.save()
    entry = next(iter(json.loads(path.read_text())["probes"].values()))
    assert entry["seconds"] == 0.125
    assert entry["machine"] == json.dumps(
        machine_fingerprint(), sort_keys=True, default=str
    )


# -- op cost-model memory declarations (§1f calibration contract) --------------


def test_moe_dispatch_declares_memory_class_and_model_consumes_it():
    """ISSUE 9 satellite pin: ``moe_dispatch``'s cost model declares its
    per-launch working set (``memory_bytes_per_launch`` + stream access
    class), and ``PerformanceModel.predict_parts`` charges exactly that —
    launches x bytes at STREAM rate — not the generic bytes_moved/gather
    fallback. Guards the declaration from silently regressing to the
    fallback (a 4x rate error on the synthetic profile)."""
    import dataclasses

    from repro.engine import MoEDispatchInputs, rank_strategies

    rng = np.random.default_rng(0)
    inputs = MoEDispatchInputs(
        x=jnp.asarray(rng.standard_normal((32, 16)).astype(np.float32)),
        router=jnp.asarray(rng.standard_normal((16, 8)).astype(np.float32)),
        nodelets=4,
    )
    profile = _calibrated_profile()
    model = PerformanceModel(profile)
    ranked = rank_strategies("moe_dispatch", inputs, machine=profile)
    assert ranked
    local = profile.substrate("local")
    assert local.access_bw("stream") != local.access_bw("gather")
    for est in ranked:
        detail = est.detail
        assert detail["memory_access"] == "stream"
        assert detail["memory_bytes_per_launch"] > 0
        assert "collective_launches" in detail

        parts = model.predict_parts(est, "local")
        launches = max(1.0, float(detail["collective_launches"]))
        expected = (
            launches * float(detail["memory_bytes_per_launch"])
            / local.access_bw("stream")
        )
        assert parts["memory"] == pytest.approx(expected)

        # strip the declaration: the model must fall back to charging
        # bytes_moved at gather rate, which predicts a different memory term
        stripped = dataclasses.replace(est, detail={
            k: v for k, v in detail.items()
            if k not in ("memory_bytes_per_launch", "memory_access")
        })
        fallback = model.predict_parts(
            stripped, "local",
            bytes_moved=float(detail["memory_bytes_per_launch"]),
        )
        assert fallback["memory"] == pytest.approx(
            float(detail["memory_bytes_per_launch"]) / local.access_bw("gather")
        )
        assert fallback["memory"] != pytest.approx(parts["memory"])
