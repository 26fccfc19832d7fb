"""Wire codec (engine/wire.py): the stable serialization shared by the
cluster protocol and the dedup content hash (DESIGN.md §1h).

Pins the two properties everything downstream rests on:

- **bit-exact round trips** — arrays come back with the same dtype, shape,
  and raw bytes (base64 of the C-order buffer, no float repr loss); enums
  come back as enum members (the str-mixin Comm/Layout/Scheme must not
  flatten to bare strings); dataclasses rebuild through the ``repro.*``-only
  class allowlist.
- **canonical bytes** — ``canonical_bytes`` is deterministic across dict
  insertion order and process boundaries, so "same computation" hashes the
  same everywhere. A Request deduped in-process and the same Request routed
  to a worker share one identity: ``_content_hash`` over the original and
  over a wire round trip agree.
"""
import json

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import Comm, Layout, MigratoryStrategy, Scheme, partition_ell
from repro.engine import (
    BFSInputs,
    MoEDispatchInputs,
    Request,
    SpMVInputs,
    WireError,
    canonical_bytes,
    decode_value,
    encode_value,
    run,
)
from repro.engine.service import _content_hash
from repro.sparse import edges_to_csr, erdos_renyi_edges, laplacian_2d, partition_graph


def _roundtrip(value):
    return decode_value(json.loads(json.dumps(encode_value(value))))


# -- scalar / container round trips -------------------------------------------


@pytest.mark.parametrize("value", [
    None, True, False, 0, -7, 3.25, "text", "",
    (1, 2, 3), [1.5, None, "x"], {"a": 1, "b": (2, 3)},
    {"nested": {"t": (1, [2, {"deep": True}])}},
])
def test_json_values_roundtrip(value):
    assert _roundtrip(value) == value


def test_tuple_list_distinction_survives():
    assert _roundtrip((1, 2)) == (1, 2)
    assert isinstance(_roundtrip((1, 2)), tuple)
    assert isinstance(_roundtrip([1, 2]), list)
    assert isinstance(_roundtrip(((1,), [2])), tuple)


def test_nan_and_inf_roundtrip():
    out = _roundtrip([float("inf"), float("-inf")])
    assert out == [float("inf"), float("-inf")]
    assert np.isnan(_roundtrip(float("nan")))


# -- arrays: dtype/shape/bit-exactness ----------------------------------------


@pytest.mark.parametrize("dtype", ["float32", "float64", "int32", "int64", "bool"])
def test_ndarray_roundtrip_preserves_dtype_and_bits(dtype):
    rng = np.random.default_rng(3)
    arr = (rng.standard_normal((5, 7)) * 100).astype(dtype)
    back = _roundtrip(arr)
    assert isinstance(back, np.ndarray)
    assert back.dtype == arr.dtype
    assert back.shape == arr.shape
    assert back.tobytes() == arr.tobytes()  # bit-exact, not approx


def test_jax_array_roundtrips_as_numpy():
    x = jnp.asarray(np.arange(12, dtype=np.float32).reshape(3, 4))
    back = _roundtrip(x)
    assert isinstance(back, np.ndarray)
    assert back.dtype == np.float32
    assert np.array_equal(back, np.asarray(x))


def test_noncontiguous_array_encodes_c_order():
    arr = np.arange(24, dtype=np.int32).reshape(4, 6).T  # F-order view
    back = _roundtrip(arr)
    assert np.array_equal(back, arr)


def test_zero_dim_and_empty_arrays():
    assert _roundtrip(np.float32(2.5)) == np.float32(2.5)
    back = _roundtrip(np.empty((0, 3), dtype=np.int64))
    assert back.shape == (0, 3) and back.dtype == np.int64


def test_object_dtype_refused():
    with pytest.raises(WireError, match="object-dtype"):
        encode_value(np.array([object()], dtype=object))


# -- enums and dataclasses ----------------------------------------------------


@pytest.mark.parametrize("member", [
    Comm.MIGRATE, Comm.REMOTE_WRITE, Layout.HCB, Scheme.PAIR,
])
def test_str_mixin_enums_roundtrip_as_members(member):
    back = _roundtrip(member)
    assert back is member  # the member, not its bare string value
    # and the encoding is tagged, not a bare scalar (str-Enum trap)
    assert isinstance(encode_value(member), dict)


def test_strategy_dataclass_roundtrip():
    st = MigratoryStrategy(
        comm=Comm.MIGRATE, replicate_x=False, layout=Layout.BLK,
        scheme=Scheme.ALL, grain=64,
    )
    back = _roundtrip(st)
    assert back == st
    assert back.cache_key() == st.cache_key()
    assert isinstance(back.comm, Comm)


def test_non_repro_class_refused_on_decode():
    payload = {
        "__wire__": "dc",
        "cls": "subprocess:Popen",
        "fields": {"args": ["true"]},
    }
    with pytest.raises(WireError, match="only repro"):
        decode_value(payload)


def test_repr_fallback_hashes_but_refuses_decode():
    class Opaque:
        pass

    encoded = encode_value(Opaque())
    assert encoded["__wire__"] == "repr"  # hash identity still works
    canonical_bytes(Opaque())  # and canonicalizes without raising
    with pytest.raises(WireError, match="hash-only"):
        decode_value(encoded)


def test_unknown_tag_refused():
    with pytest.raises(WireError, match="unknown wire tag"):
        decode_value({"__wire__": "no-such-tag"})


# -- canonical bytes ----------------------------------------------------------


def test_canonical_bytes_insertion_order_independent():
    a = {"x": 1, "y": (2, 3), "z": np.arange(3)}
    b = {"z": np.arange(3), "y": (2, 3), "x": 1}
    assert canonical_bytes(a) == canonical_bytes(b)


def test_canonical_bytes_distinguishes_values_and_dtypes():
    assert canonical_bytes(np.float32(1)) != canonical_bytes(np.float64(1))
    assert canonical_bytes((1, 2)) != canonical_bytes([1, 2])
    assert canonical_bytes({"a": 1}) != canonical_bytes({"a": 2})


# -- Request wire form --------------------------------------------------------


def _mixed_requests():
    rng = np.random.default_rng(0)
    a = partition_ell(laplacian_2d(8), 4)
    x = jnp.asarray(rng.standard_normal(64).astype(np.float32))
    g = partition_graph(edges_to_csr(erdos_renyi_edges(6, 4, seed=1), 64), 4)
    moe = MoEDispatchInputs(
        x=jnp.asarray(rng.standard_normal((16, 8)).astype(np.float32)),
        router=jnp.asarray(rng.standard_normal((8, 4)).astype(np.float32)),
        nodelets=2,
    )
    return [
        Request("spmv", SpMVInputs(a, x), MigratoryStrategy(), "local"),
        Request("bfs", BFSInputs(g, 0)),
        Request("moe_dispatch", moe, qos=2.0, timeout=30.0),
    ]


@pytest.mark.parametrize("idx", [0, 1, 2])
def test_request_roundtrip_and_execution_parity(idx):
    request = _mixed_requests()[idx]
    payload = request.to_wire()
    # the wire form is honest JSON: survives a dumps/loads boundary
    rebuilt = Request.from_wire(json.loads(json.dumps(payload)))
    assert rebuilt.qos == request.qos and rebuilt.timeout == request.timeout
    y0, _ = run(request, iters=1, warmup=0)
    y1, _ = run(rebuilt, iters=1, warmup=0)
    np.testing.assert_array_equal(np.asarray(y0), np.asarray(y1))


def test_split_spmv_matrix_roundtrips_and_runs():
    """A layout with hub rows split keeps its row map and each row's last
    ELL row across the wire."""
    from repro.sparse import skewed_matrix

    a = partition_ell(skewed_matrix(256, 6.0, 120, seed=3), 4)
    assert a.row_of is not None
    x = jnp.asarray(np.random.default_rng(1).standard_normal(256).astype(np.float32))
    request = Request("spmv", SpMVInputs(a, x), MigratoryStrategy(), "local")
    rebuilt = Request.from_wire(json.loads(json.dumps(request.to_wire())))
    np.testing.assert_array_equal(np.asarray(rebuilt.inputs.a.row_of), np.asarray(a.row_of))
    np.testing.assert_array_equal(np.asarray(rebuilt.inputs.a.last_ell), np.asarray(a.last_ell))
    assert rebuilt.inputs.a.shape == a.shape
    y0, _ = run(request, iters=1, warmup=0)
    y1, _ = run(rebuilt, iters=1, warmup=0)
    np.testing.assert_array_equal(np.asarray(y0), np.asarray(y1))


def test_request_wire_version_checked():
    payload = _mixed_requests()[0].to_wire()
    payload["v"] = 999
    with pytest.raises(WireError, match="version"):
        Request.from_wire(payload)


def test_request_op_instance_travels_by_name():
    from repro.engine import SpMVOp

    req = _mixed_requests()[0]
    payload = Request(SpMVOp(), req.inputs).to_wire()
    assert payload["op"] == "spmv"


def test_request_unregistered_substrate_refused():
    from repro.engine import Substrate

    class Rogue(Substrate):
        name = "never-registered"

    req = _mixed_requests()[0]
    with pytest.raises(WireError, match="registered substrate"):
        Request(req.op, req.inputs, substrate=Rogue()).to_wire()


def test_dedup_hash_shared_with_wire_identity():
    """The dedup content hash and the wire form agree on request identity:
    a request that crossed the wire hashes identically to the original."""
    request = _mixed_requests()[0]
    rebuilt = Request.from_wire(json.loads(json.dumps(request.to_wire())))
    h0 = _content_hash(request.op, request.inputs, request.strategy, "local")
    h1 = _content_hash(rebuilt.op, rebuilt.inputs, rebuilt.strategy, "local")
    assert h0 == h1
    # and different inputs hash differently
    other = _mixed_requests()[1]
    h2 = _content_hash(other.op, other.inputs, other.strategy, "local")
    assert h2 != h0


# -- segment / blobref modes (protocol v2 data plane) --------------------------


def test_segment_mode_emits_ndref_and_roundtrips_bit_identically():
    from repro.engine import SegmentTable

    table = SegmentTable()
    arr = np.arange(24, dtype=np.int64).reshape(4, 6)
    encoded = encode_value({"a": arr, "k": 3}, segments=table)
    assert len(table) == 1 and table.nbytes() == arr.nbytes
    # the envelope carries no tensor bytes, only the ref
    flat = json.dumps(encoded)
    assert "ndref" in flat and "data" not in flat
    # decode path: the protocol layer attaches the raw buffer
    from repro.cluster.protocol import attach_segments

    parsed = json.loads(flat)
    attach_segments(parsed, [bytes(s) for s in table.segments])
    out = decode_value(parsed)
    np.testing.assert_array_equal(out["a"], arr)
    assert out["a"].dtype == arr.dtype and out["k"] == 3


def test_segment_decode_returns_writable_copies():
    """v1 'nd' parity: an ndref decodes to a fresh writable array, not a
    read-only view pinning the frame buffer."""
    from repro.engine import SegmentTable

    from repro.cluster.protocol import attach_segments

    table = SegmentTable()
    encoded = encode_value(np.arange(8, dtype=np.float32), segments=table)
    parsed = json.loads(json.dumps(encoded))
    attach_segments(parsed, [bytes(s) for s in table.segments])
    out = decode_value(parsed)
    assert out.flags.writeable and out.flags.owndata
    out[0] = -1.0  # downstream in-place mutation keeps working


def test_unattached_ndref_is_refused():
    from repro.engine import SegmentTable

    encoded = encode_value(np.ones(3), segments=SegmentTable())
    with pytest.raises(WireError, match="not attached"):
        decode_value(json.loads(json.dumps(encoded)))


def test_blob_sink_emits_blobref_and_resolver_decodes():
    from repro.engine import SegmentTable, collect_blob_digests, content_digest

    big = np.arange(64, dtype=np.float32)
    small = np.ones(2, dtype=np.float32)
    store = {}

    def sink(original, arr):
        if arr.nbytes < 64:
            return None
        digest = content_digest(arr)
        store[digest] = arr
        return digest

    table = SegmentTable()
    encoded = encode_value((big, small), segments=table, blob_sink=sink)
    assert len(store) == 1  # only the big array was claimed
    assert len(table) == 1  # the small one rides as a segment
    assert collect_blob_digests(encoded) == list(store)
    from repro.cluster.protocol import attach_segments

    attach_segments(encoded, [bytes(s) for s in table.segments])
    out = decode_value(encoded, blob_resolver=store.__getitem__)
    np.testing.assert_array_equal(out[0], big)
    np.testing.assert_array_equal(out[1], small)
    with pytest.raises(WireError, match="blob store"):
        decode_value(encoded, blob_resolver=None)


def test_canonical_bytes_ignore_transport_encoding():
    """Dedup identity must not depend on how a value crossed the wire."""
    from repro.engine import SegmentTable, content_digest

    a = partition_ell(laplacian_2d(6), 2)
    x = jnp.asarray(np.arange(36, dtype=np.float32))
    value = SpMVInputs(a, x)
    baseline = canonical_bytes(value)
    # encoding the same value in segment/blob modes leaves identity alone
    encode_value(value, segments=SegmentTable())
    encode_value(value, blob_sink=lambda o, arr: content_digest(arr))
    assert canonical_bytes(value) == baseline
    # and a segment-mode wire round trip reproduces the same canonical bytes
    from repro.cluster.protocol import attach_segments

    table = SegmentTable()
    encoded = json.loads(json.dumps(encode_value(value, segments=table)))
    attach_segments(encoded, [bytes(s) for s in table.segments])
    assert canonical_bytes(decode_value(encoded)) == baseline


def test_request_to_wire_threads_segments_and_blobs():
    from repro.engine import SegmentTable, collect_blob_digests, content_digest

    a = partition_ell(laplacian_2d(6), 2)
    x = jnp.asarray(np.arange(36, dtype=np.float32))
    request = Request("spmv", SpMVInputs(a, x), strategy=None)
    blobs = {}

    def sink(original, arr):
        if arr.nbytes < 128:
            return None
        digest = content_digest(arr)
        blobs[digest] = arr
        return digest

    table = SegmentTable()
    payload = request.to_wire(segments=table, blob_sink=sink)
    digests = collect_blob_digests(payload)
    assert digests and set(digests) == set(blobs)
    from repro.cluster.protocol import attach_segments

    parsed = json.loads(json.dumps(payload))
    attach_segments(parsed, [bytes(s) for s in table.segments])
    rebuilt = Request.from_wire(parsed, blob_resolver=blobs.__getitem__)
    oracle, _ = run(request, iters=1, warmup=0)
    got, _ = run(rebuilt, iters=1, warmup=0)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(oracle))
