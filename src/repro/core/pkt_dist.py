"""Distributed PKT — shard_map bulk-synchronous truss decomposition.

The paper closes with: "porting this algorithm to GPU and distributed-memory
settings appears to be non-trivial." This module is that port, in the BSP
idiom natural to an SPMD mesh:

  * the flat peel table (the unit of peel work) is sharded across a mesh
    axis: each device resolves its own contiguous slice of the wedge rows
    from the replicated CSR arrays (one probe a row, hits kept as triangle
    rows ``(e1, e2, e3)``, ``core.support.peel_rows``) along with its own
    per-edge chunk ranges, so no device ever holds the whole table;
  * edge state (S, processed, frontier) is replicated; each device runs the
    single-device chunk-skipping peel loop (``core.pkt._peel_loop``) over its
    slice, and one `psum` of the decrement vector per sub-level is the only
    communication — the distributed analogue of the paper's per-sub-level
    barrier;
  * support computation fans out the same way (each device builds and probes
    its slice of the oriented wedge table with the flat jnp program, then
    one psum of the partial supports).

Work per sub-level per device: the chunks of its slice that overlap the
frontier. Communication per sub-level: one all-reduce of an m-vector.
"""

from __future__ import annotations

import functools
from typing import Sequence

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.graphs.csr import CSRGraph
from repro.core import support as support_mod
from repro.core.pkt import PeelTables, _SENTINEL_S, _peel_loop
from repro.kernels import wedge_common


def _psum(x, axes: Sequence[str]):
    for ax in axes:
        x = jax.lax.psum(x, ax)
    return x


def _dist_support_body(N, Eid, e1, cand, lo, hi, *, m: int, iters: int,
                       axes: Sequence[str]):
    """Sharded AM4 support over this device's table slice (inside shard_map):
    integer-exact addition and one psum give the single-device support."""
    S = support_mod._support_jit(N, Eid, e1, cand, lo, hi, iters, m)
    return _psum(S, axes)


def _dist_support_dev_body(u, v, Es, Eo, N, Eid, *, m: int, per_shard: int,
                           iters: int, axes: Sequence[str]):
    """Build this device's support-table slice, then probe it."""
    start = jax.lax.axis_index(tuple(axes)) * per_shard
    e1, cand, lo, hi, _ = support_mod.support_rows(
        u, v, Es, Eo, jnp.int32(m), m=m, size=per_shard, start=start)
    return _dist_support_body(N, Eid, e1, cand, lo, hi, m=m, iters=iters,
                              axes=axes)


def _dist_peel_rows_body(u, v, Es, N, Eid, *, m: int, per_shard: int,
                         chunk: int, iters: int, axes: Sequence[str]):
    """This device's resolved peel-table slice and its own chunk ranges."""
    start = jax.lax.axis_index(tuple(axes)) * per_shard
    e1, e2, e3, _off, c_start, c_end, has = support_mod.peel_rows(
        u, v, Es, N, Eid, jnp.int32(m), m=m, size=per_shard, chunk=chunk,
        iters=iters, start=start)
    return e1, e2, e3, c_start, c_end, has


def _dist_peel_body(S0, e1, e2, e3, c_start, c_end, has, *, m: int,
                    chunk: int, axes: Sequence[str]):
    """Chunk-skipping peel over this device's table slice (inside shard_map).

    ``c_start``/``c_end``/``has`` are this device's own (m,) chunk ranges
    into its slice, so each device visits only its chunks that overlap the
    frontier.
    """
    tabs = PeelTables(e1, e2, e3, c_start, c_end, has)
    S_ext0 = jnp.concatenate([S0.astype(jnp.int32), jnp.full((1,), _SENTINEL_S)])
    processed0 = jnp.zeros((m + 1,), jnp.bool_).at[m].set(True)
    S_ext, _, levels, subs, _ = _peel_loop(
        S_ext0, processed0, tabs, m=m, chunk=chunk,
        n_chunks=e1.shape[0] // chunk, mode="chunked",
        reduce=functools.partial(_psum, axes=axes))
    return S_ext[:m], levels, subs


@functools.lru_cache(maxsize=None)
def make_pkt_dist(mesh: jax.sharding.Mesh, axes: tuple[str, ...], *, m: int,
                  chunk: int = 1 << 14):
    """Builds the jitted distributed peel for dry-run or execution.

    The returned fn takes (S0, e1, e2, e3, c_start, c_end, has): S0
    replicated, the rest sharded over ``axes`` — each device holds a slice
    of table rows (a whole number of ``chunk``-row chunks) and the (m,)
    chunk ranges of that slice.  Returns replicated (S_final, levels,
    sublevels).  Cached per argument set, so a repeated decomposition
    compiles nothing.
    """
    rep, sh = P(), P(tuple(axes))
    fn = jax.shard_map(
        functools.partial(_dist_peel_body, m=m, chunk=chunk, axes=axes),
        mesh=mesh, in_specs=(rep,) + (sh,) * 6,
        out_specs=(rep, rep, rep), check_vma=False)
    return jax.jit(fn)


@functools.lru_cache(maxsize=None)
def make_support_dist(mesh: jax.sharding.Mesh, axes: tuple[str, ...], *,
                      m: int, iters: int):
    """Jitted shard_map support over prebuilt sharded tables (DESIGN.md §6).

    Wedge-table shards live per-device along ``axes``; each device counts
    triangles for its shard against the replicated CSR arrays and the
    results are psum-reduced to a replicated (m,) support vector.
    """
    rep, sh = P(), P(tuple(axes))
    fn = jax.shard_map(
        functools.partial(_dist_support_body, m=m, iters=iters, axes=axes),
        mesh=mesh, in_specs=(rep, rep, sh, sh, sh, sh), out_specs=rep,
        check_vma=False)
    return jax.jit(fn)


@functools.lru_cache(maxsize=None)
def _support_dev_dist(mesh: jax.sharding.Mesh, axes: tuple[str, ...], *,
                      m: int, per_shard: int, iters: int):
    """Jitted shard_map: each device builds and probes its support slice."""
    return jax.jit(jax.shard_map(
        functools.partial(_dist_support_dev_body, m=m, per_shard=per_shard,
                          iters=iters, axes=axes),
        mesh=mesh, in_specs=(P(),) * 6, out_specs=P(), check_vma=False))


@functools.lru_cache(maxsize=None)
def _peel_rows_dist(mesh: jax.sharding.Mesh, axes: tuple[str, ...], *,
                    m: int, per_shard: int, chunk: int, iters: int):
    """Jitted shard_map: each device resolves its peel-table slice."""
    return jax.jit(jax.shard_map(
        functools.partial(_dist_peel_rows_body, m=m, per_shard=per_shard,
                          chunk=chunk, iters=iters, axes=axes),
        mesh=mesh, in_specs=(P(),) * 5, out_specs=(P(axes),) * 6,
        check_vma=False))


def _host_peel_shards(g: CSRGraph, n_shards: int, per: int,
                      chunk: int) -> list[np.ndarray]:
    """Host-resolved peel rows split evenly into ``n_shards`` slices of
    ``per`` rows, with each slice's (m,) chunk ranges; every array is the
    shards' parts concatenated, ready to shard along the mesh axes."""
    from repro.core.pkt import chunk_ranges

    rows = support_mod.resolve_peel_table(g)
    step = -(-rows.size // n_shards)
    parts = [[] for _ in range(6)]
    for k in range(n_shards):
        a, b = min(k * step, rows.size), min((k + 1) * step, rows.size)
        for col, x in zip(parts, (rows.e1, rows.e2, rows.e3)):
            col.append(wedge_common.pad1(x[a:b], per, g.m))
        local = np.clip(rows.off - a, 0, b - a)
        has, c_start, c_end = chunk_ranges(local, chunk)
        for col, x in zip(parts[3:], (c_start, c_end, has)):
            col.append(x)
    return [np.concatenate(col) for col in parts]


def pkt_dist(g: CSRGraph, mesh: jax.sharding.Mesh | None = None,
             axes: Sequence[str] = ("data",), chunk: int | None = None,
             table_mode: str = "device") -> np.ndarray:
    """Run distributed PKT over ``mesh``. Returns trussness (m,).

    ``mesh`` defaults to a 1-D mesh over every device.  The CSR arrays are
    replicated onto the mesh; with ``table_mode="device"`` (the default)
    each device builds only its own slice of both wedge tables, so the
    tables are split across the devices from the start and never pass
    through the host or one device.  "numpy" keeps the host builders as the
    parity oracle and uploads each slice straight to its device.  The peel
    is the sharded chunk-skipping loop.  ``chunk`` (``None``:
    ``kernels.wedge_common.auto_chunk`` of the per-device slice) sets the
    peel chunk.

    Raises:
        ValueError: unknown ``table_mode``.
    """
    if table_mode not in support_mod.TABLE_MODES:
        raise ValueError(f"table_mode must be one of "
                         f"{support_mod.TABLE_MODES}, got {table_mode!r}")
    if mesh is None:
        mesh = jax.sharding.Mesh(np.array(jax.devices()), ("data",))
        axes = ("data",)
    if g.m == 0:
        return np.zeros(0, np.int64)
    axes = tuple(axes)
    n_shards = int(np.prod([mesh.shape[a] for a in axes]))
    rep = NamedSharding(mesh, P())
    sh = NamedSharding(mesh, P(axes))
    csr = {k: jax.device_put(x, rep) for k, x in (
        ("N", g.N), ("Eid", g.Eid), ("Es", g.Es), ("Eo", g.Eo),
        ("u", np.ascontiguousarray(g.El[:, 0])),
        ("v", np.ascontiguousarray(g.El[:, 1])))}

    # ---- support: each shard probes its slice, one psum ---------------------
    s_iters = support_mod._search_iters(g, oriented=True)
    per_shard = max(1, -(-support_mod.support_table_size(g) // n_shards))
    support_mod._check_table_size(per_shard * n_shards)
    if table_mode == "device":
        sup_fn = _support_dev_dist(mesh, axes, m=g.m, per_shard=per_shard,
                                   iters=s_iters)
        S0 = sup_fn(csr["u"], csr["v"], csr["Es"], csr["Eo"], csr["N"],
                    csr["Eid"])
    else:
        stab = support_mod.build_support_table(g)
        ssize = per_shard * n_shards
        s_tab = [jax.device_put(wedge_common.pad1(a, ssize, fill), sh) for a, fill in (
            (stab.e1, g.m), (stab.cand_slot, 0), (stab.lo, 0), (stab.hi, 0))]
        sup_fn = make_support_dist(mesh, axes, m=g.m, iters=s_iters)
        S0 = sup_fn(csr["N"], csr["Eid"], *s_tab)

    # ---- peel tables: each shard holds a whole number of chunks -------------
    p_size = support_mod.peel_table_size(g)
    per = max(1, -(-p_size // n_shards))
    chunk = wedge_common.pow2_chunk(
        wedge_common.next_pow2(per), chunk, size=per)
    per = -(-per // chunk) * chunk
    support_mod._check_table_size(per * n_shards)
    if table_mode == "device":
        rows_fn = _peel_rows_dist(mesh, axes, m=g.m, per_shard=per,
                                  chunk=chunk,
                                  iters=support_mod._search_iters(g))
        p_tab = rows_fn(csr["u"], csr["v"], csr["Es"], csr["N"], csr["Eid"])
    else:
        p_tab = [jax.device_put(x, sh)
                 for x in _host_peel_shards(g, n_shards, per, chunk)]
    peel_fn = make_pkt_dist(mesh, axes, m=g.m, chunk=chunk)
    S, _levels, _subs = peel_fn(S0, *p_tab)
    return np.asarray(S).astype(np.int64) + 2
