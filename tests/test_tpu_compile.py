"""Main-path kernels compiled ahead of time for a described TPU v5e.

The TPU's compiler is installed with JAX, so each program here is lowered
and compiled for a ``v5e:2x2`` topology that is described, not attached: a
compile that passes says the chip's compiler accepts the program and its
memory fits; it runs nothing. Sizes follow the chip smoke (``chip_smoke.py``):
``laplacian_2d(2048)`` SpMV over 8 nodelets, uniform-random BFS at scale 21
(edge factor 16, max degree ~68; the smoke runs scale 22, whose compile
takes ~20 s on a CPU host), GSANA n=8192 PAIR tasks, and a
32-head x 2048 x 128 bf16 attention.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and test workers import every file.
"""
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import MigratoryStrategy, bfs_local, spmv_local
from repro.core.spmv import PartitionedELL
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.topk_sim.kernel import topk_sim_pallas
from repro.sparse.graph import PartitionedGraph

P = 8  # nodelets
LAP_ROWS, LAP_K = 2048 * 2048, 5
BFS_SCALE, BFS_K = 21, 68
GSANA_TASKS, GSANA_CAP, GSANA_FEATURES = 2304, 49, 5 + 16 + 16 + 64  # n=8192
V5E_HBM_BYTES = 16 * 2**30


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache but
    cannot be read back without one; keep these compiles out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture
def shapes(one_chip, no_persistent_cache):
    def shape(dims, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    return shape


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    mem = compiled.memory_analysis()
    used = mem.argument_size_in_bytes + mem.output_size_in_bytes + mem.temp_size_in_bytes
    assert used < V5E_HBM_BYTES, f"{used / 2**30:.1f} GiB does not fit one v5e"
    return compiled


def _compile_local_spmv(shapes, replicate_x, n=LAP_ROWS, k=LAP_K, ell_rows=None, grain=None):
    """``ell_rows``: R_p' of a layout whose long rows are split, with its
    row map and each row's last ELL row; None for one ELL row per row.
    ``grain`` None is the dynamic grain."""
    rp = -(-n // P)
    rows = ell_rows or rp
    a = PartitionedELL(
        cols=shapes((P, rows, k), jnp.int32), vals=shapes((P, rows, k)), shape=(n, n),
        row_of=shapes((P, rows), jnp.int32) if ell_rows else None,
        last_ell=shapes((P, rp), jnp.int32) if ell_rows else None,
    )
    x = shapes((n,)) if replicate_x else shapes((P, rp))
    st = MigratoryStrategy(replicate_x=replicate_x, grain=grain)
    return _compile(lambda a, x: spmv_local(a, x, st), a, x)


def _while_body_tiles(hlo: str) -> list[tuple[tuple[int, ...], int]]:
    """``(dims, size of the minor dim)`` of every ``T(8,128)``-tiled array in
    the while loops' bodies of an optimized HLO module, callees included."""
    comps, name = {}, None
    for line in hlo.splitlines():
        head = re.match(r"^(?:ENTRY )?%([\w.\-]+) .*\{$", line)
        if head:
            name = head.group(1)
            comps[name] = []
        elif line == "}":
            name = None
        elif name:
            comps[name].append(line.split(", metadata=")[0])
    todo = [b for lines in comps.values() for line in lines
            for b in re.findall(r"\bbody=%([\w.\-]+)", line)]
    seen = set()
    while todo:
        c = todo.pop()
        if c not in seen:
            seen.add(c)
            todo += [m for line in comps[c]
                     for m in re.findall(r"\b(?:calls|to_apply|body|condition)=%([\w.\-]+)", line)]
    tiles = []
    for c in seen:
        for line in comps[c]:
            for dims, layout in re.findall(r"\b[a-z]\w*\[([\d,]+)\]\{([\d,]+):T\(8,128\)", line):
                d = tuple(int(v) for v in dims.split(","))
                tiles.append((d, d[int(layout.split(",")[0])]))
    return tiles


@pytest.mark.parametrize("replicate_x", [True, False], ids=["replicated_x", "striped_x"])
def test_local_spmv_compiles_for_v5e(shapes, replicate_x):
    _compile_local_spmv(shapes, replicate_x)


@pytest.mark.parametrize(
    "n, k, grain, temp_limit",
    [
        # the cell's Laplacian: grain 1024 rows against K = 5 (177 MB K-minor)
        (LAP_ROWS, LAP_K, 1024, 128 * 10**6),
        # a tenth of Table 3's Stanford (n 28,200), unsplit: K = 3,860
        # against a grain of 6 rows; temp as with the K-minor chunks before
        # (1,343.6 MB)
        (28_200, 3860, 6, 1400 * 10**6),
    ],
    ids=["laplacian_k5", "stanford_k3860"],
)
def test_local_spmv_row_chunks_are_lane_dense_for_v5e(shapes, n, k, grain, temp_limit):
    """The row-chunk loop puts the longer of K and the chunk's rows (the
    dynamic grain) on the lanes: no array in the loop body that holds the K
    axis has the shorter one as its minor dimension under the (8, 128)
    tile, which pads it to 128 lanes. The loop holds one gather whatever K
    is."""
    compiled = _compile_local_spmv(shapes, replicate_x=True, n=n, k=k)
    hlo = compiled.as_text()
    tiles = _while_body_tiles(hlo)
    assert tiles, "no while loop over row chunks in the compiled program"
    short = min(k, grain)
    padded = sorted({dims for dims, minor in tiles if minor == short and k in dims})
    assert not padded, f"arrays with {short} lanes in the row-chunk loop: {padded}"
    assert len(re.findall(r" gather\(", hlo)) == 1
    assert compiled.memory_analysis().temp_size_in_bytes < temp_limit


# Table 3's Stanford at the paper's size as the cell spmv.skewed.stanford
# lays it out and serves it: hub rows split at K = 3 into 139,107 ELL rows
# per nodelet, 2,048 of them per task
STANFORD_ROWS, STANFORD_ELL_ROWS, STANFORD_K, STANFORD_GRAIN = 281_903, 139_107, 3, 2048


@pytest.mark.parametrize("split", [True, False], ids=["stanford_split", "laplacian"])
def test_local_spmv_folds_pieces_only_where_rows_are_split(shapes, split):
    """The split layout compiles for a v5e with one gather in the row-chunk
    loop and a fold of the pieces onto their rows that is a segmented scan
    and one gather of each row's last ELL row, with no scatter, in bounded
    temp; a layout without a split (the Laplacian) compiles no fold: one
    gather, no scatter."""
    if split:
        compiled = _compile_local_spmv(shapes, True, n=STANFORD_ROWS, k=STANFORD_K,
                                       ell_rows=STANFORD_ELL_ROWS, grain=STANFORD_GRAIN)
    else:
        compiled = _compile_local_spmv(shapes, True)
    hlo = compiled.as_text()
    assert len(re.findall(r" gather\(", hlo)) == 1 + int(split)
    assert not re.findall(r" scatter\(", hlo)
    assert compiled.memory_analysis().temp_size_in_bytes < 128 * 10**6


def test_local_bfs_compiles_for_v5e(shapes):
    n = 1 << BFS_SCALE
    g = PartitionedGraph(
        adj=shapes((P, n // P, BFS_K), jnp.int32),
        deg=shapes((P, n // P), jnp.int32),
        n_vertices=n,
    )
    _compile(lambda g: bfs_local(g, 0), g)


def test_flash_attention_compiles_for_v5e(shapes):
    q = shapes((1, 32, 2048, 128), jnp.bfloat16)
    compiled = _compile(lambda q, k, v: flash_attention(q, k, v, interpret=False), q, q, q)
    assert "tpu_custom_call" in compiled.as_text()


def test_topk_similarity_compiles_for_v5e(shapes):
    feats = shapes((GSANA_TASKS, GSANA_CAP, GSANA_FEATURES))
    mask = shapes((GSANA_TASKS, GSANA_CAP))
    compiled = _compile(
        lambda fv, fu, mv, mu: topk_sim_pallas(
            fv, fu, mv, mu, t1=16, t2=16, t3=64, k=4, interpret=False
        ),
        feats, feats, mask, mask,
    )
    assert "tpu_custom_call" in compiled.as_text()
