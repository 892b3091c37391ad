"""Compile the main path for a described (not attached) TPU v5e chip.

Nothing runs: each test lowers one jitted program of the default executors
at ``rmat-medium`` shapes (n = 32,737, m = 234,101 after degeneracy
relabelling; both wedge tables pad to 2^25 rows) and compiles it with the
TPU compiler, which refuses what the chip cannot run — unaligned blocks,
unsupported gathers, programs that do not fit the device.  The topology is
described inside a module fixture (never at import, in a ``skipif`` or in
``parametrize``), so every pytest worker collects the same tests and only
the one that runs this file loads the TPU library.
"""

import importlib
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

support = importlib.import_module("repro.core.support")
pkt = importlib.import_module("repro.core.pkt")

#: rmat-medium after degeneracy relabelling (graphs/gen.py, scale 15)
N_V, M = 32737, 234101
TABLE = 1 << 25                     # both tables, pow2-padded
CHUNK = 1 << 14                     # auto_chunk for a 2^25-row table
ITERS, S_ITERS = 13, 11             # peel-table / oriented support search bounds


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means no compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # entries compiled for a described chip cannot be read back without one
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", cache_on)


def _sds(sh, shape, dtype=jnp.int32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sh)


def _peel_tables(sh):
    return pkt.PeelTables(*[_sds(sh, (TABLE,))] * 3, _sds(sh, (M,)),
                          _sds(sh, (M,)), _sds(sh, (M,), jnp.bool_))


def _fits_v5e(compiled):
    ma = compiled.memory_analysis()
    used = (ma.temp_size_in_bytes + ma.argument_size_in_bytes
            + ma.output_size_in_bytes)
    assert 0 < used < 16 * 2**30, used
    return ma


def test_support_device_jit_compiles(one_chip):
    s = one_chip
    c = support._support_device_jit.lower(
        _sds(s, (M,)), _sds(s, (M,)), _sds(s, (N_V + 1,)), _sds(s, (N_V,)),
        _sds(s, (2 * M,)), _sds(s, (2 * M,)), _sds(s, ()),
        m=M, size=TABLE, iters=S_ITERS).compile()
    _fits_v5e(c)


def test_build_peel_table_dev_compiles(one_chip):
    s = one_chip
    c = support._build_peel_table_dev.lower(
        _sds(s, (M,)), _sds(s, (M,)), _sds(s, (N_V + 1,)),
        _sds(s, (2 * M,)), _sds(s, (2 * M,)), _sds(s, ()),
        m=M, size=TABLE, chunk=CHUNK, iters=ITERS).compile()
    ma = _fits_v5e(c)
    assert ma.output_size_in_bytes >= 3 * 4 * TABLE   # the three row arrays


def test_triangle_rows_dev_compiles(one_chip):
    """The full rebuild's selection of each triangle once from the peel
    table: a whole table read into a quarter of its rows (T <= rows / 3)."""
    s = one_chip
    c = support._triangle_rows_dev.lower(
        *[_sds(s, (TABLE,))] * 3, rows=TABLE, out=TABLE // 4).compile()
    ma = _fits_v5e(c)
    assert ma.output_size_in_bytes >= 3 * 4 * TABLE // 4


def test_pkt_peel_jit_compiles(one_chip):
    s = one_chip
    c = pkt._pkt_peel_jit.lower(
        _sds(s, (M,)), _peel_tables(s),
        m=M, chunk=CHUNK, n_chunks=TABLE // CHUNK,
        mode="chunked").compile()
    _fits_v5e(c)


def test_peel_segment_jit_compiles(one_chip):
    s = one_chip
    c = pkt._peel_segment_jit.lower(
        _sds(s, (M + 1,)), _sds(s, (M + 1,), jnp.bool_), _sds(s, ()), None,
        _peel_tables(s), m=M, chunk=CHUNK, n_chunks=TABLE // CHUNK,
        mode="chunked").compile()
    _fits_v5e(c)


def test_engine_batched_flush_compiles(one_chip):
    """One bucket of 8 graphs in the 2^12-edge size class, tables built
    in-jit (``table_mode="device"``, the engine default)."""
    from repro.serve.truss_engine import CSROperand, _batched_truss_dev

    s, B, m_pad, n_pad, tab, chunk = one_chip, 8, 1 << 12, 1 << 11, 1 << 16, 1 << 12
    ops = CSROperand(
        N=_sds(s, (B, 2 * m_pad)), Eid=_sds(s, (B, 2 * m_pad)),
        Es=_sds(s, (B, n_pad + 1)), Eo=_sds(s, (B, n_pad)),
        u=_sds(s, (B, m_pad)), v=_sds(s, (B, m_pad)), m_real=_sds(s, (B,)))
    c = _batched_truss_dev.lower(
        ops, m=m_pad, chunk=chunk, n_chunks=tab // chunk, iters=14,
        mode="chunked", sup_pad=tab, peel_pad=tab).compile()
    _fits_v5e(c)


def test_hierarchy_flood_jit_compiles(one_chip):
    from repro.core.hierarchy import _labelprop

    s, rows, mp = one_chip, 1 << 22, 1 << 18
    c = _labelprop.lower(
        _sds(s, (rows, 3)), _sds(s, (rows,)), _sds(s, ()), _sds(s, ()),
        _sds(s, (mp,)), sz=rows, mp=mp).compile()
    _fits_v5e(c)


def test_shapes_match_rmat_medium():
    """The constants above are rmat-medium's (host-only O(m) sizing)."""
    from repro.graphs.csr import build_csr, degeneracy_order, relabel
    from repro.graphs.datasets import named_graph
    from repro.kernels.wedge_common import next_pow2, pow2_chunk

    E = named_graph("rmat-medium")
    n = int(E.max()) + 1
    g = build_csr(relabel(E, degeneracy_order(E, n)), n)
    assert (g.n, g.m) == (N_V, M)
    for size in (support.support_table_size(g), support.peel_table_size(g)):
        assert next_pow2(size) == TABLE
        assert pow2_chunk(TABLE, None, size=size) == CHUNK
    assert support._search_iters(g) == ITERS
    assert support._search_iters(g, oriented=True) == S_ITERS
    assert np.all(g.El[:, 0] < g.El[:, 1])
