"""Pallas TPU kernel for the PKT peel phase (Algorithm 5's SCAN hot loop).

One grid step evaluates one peel-table chunk: the chunk's resolved
triangle rows ``(e1, e2, e3)`` — anchor edge and the two edges that close
its triangle, resolved once by the table builder (``core.support.
peel_rows``), sentinel ``m`` in all three columns on padding rows — are
staged in VMEM next to the replicated edge-state vectors, and the
frontier / processed / tie-break predicates of ProcessSubLevel are
evaluated as dense masks over gathers of that state; no adjacency and no
search enter the kernel.  The decrement fold is fused on-chip: the kernel
owns a single ``(m + 1,)`` accumulator output block pinned to block 0 for
every grid step, so it stays resident in VMEM across the whole (sequential)
grid.  Grid step 0 zeroes it; every step scatter-adds its chunk's two
decrement targets — the edge id of each non-anchor triangle edge when the
paper's AtomicSub would fire, or the absorbing sentinel slot ``m``
otherwise — directly into the accumulator.  Integer addition is exact, so
the fused fold is bitwise identical to the jnp executors (and to the retired
target-stream + host-side scatter) regardless of accumulation order.

The incremental layer's ``pinned`` schedule mask (edges that process their
triangles at a replayed level but never receive decrements,
core/truss_inc.py) rides into the kernel as one more replicated (m+1,)
state vector and suppresses the decrement predicate in place — the retired
stream path had to re-route pinned targets to the sentinel on the host.

Chunk skipping (the paper's dynamic scheduling) survives as an ``active``
mask input: a Pallas grid is static, so sub-levels that only touch a few
chunks still *stream* every block, but inactive blocks short-circuit to
sentinel writes — compute is masked even though DMA is not.  The
work-efficient ``mode="chunked"`` while_loop in ``core/pkt.py`` remains the
right choice for very sparse frontiers; this kernel wins when frontiers are
wide (dense sub-levels dominate total peel time, paper Fig. 6).

VMEM per grid step ≈ 4·(3·chunk + 5·(m+1)) bytes; callers pick ``chunk``
so this stays well under the ~16 MiB budget.  On non-TPU backends the
kernel runs in interpret mode (the CI contract: the lowering is exercised
on every PR, the Mosaic path on TPU runners).

Chunk layout and the BlockSpec helpers are shared with the support kernel
via ``kernels/wedge_common.py``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import wedge_common

_interpret_default = wedge_common.interpret_default


def _peel_chunk_kernel(act_ref, l_ref, e1_ref, e2_ref, e3_ref, s_ref,
                       proc_ref, curr_ref, pin_ref, dec_ref, *, m: int):
    """One peel-table chunk folded into the (m+1,) decrement accumulator."""
    @pl.when(pl.program_id(0) == 0)
    def _zero():
        dec_ref[...] = jnp.zeros_like(dec_ref)

    S = s_ref[...]                 # (m+1,)  int32 extended support
    proc = proc_ref[...] != 0      # (m+1,)  processed mask
    curr = curr_ref[...] != 0      # (m+1,)  current-frontier mask
    pin = pin_ref[...] != 0        # (m+1,)  pinned schedule mask
    act = act_ref[0] != 0          # chunk overlaps a frontier edge's range
    l = l_ref[0]                   # current peel level

    e1 = e1_ref[...]               # (chunk,) anchor edge ids (m = padding)
    e2 = e2_ref[...]               # (chunk,) closing edge ids (m = padding)
    e3 = e3_ref[...]               # (chunk,) closing edge ids (m = padding)

    in1 = curr[e1]                 # padding rows carry e1 == m → curr[m] False
    valid = act & in1 & (~proc[e2]) & (~proc[e3])
    # the paper's tie-break: of two frontier edges sharing a triangle, the
    # lower edge id processes it (each triangle decremented exactly once)
    dec2 = valid & (S[e2] > l) & ((~curr[e3]) | (e1 < e3)) & (~pin[e2])
    dec3 = valid & (S[e3] > l) & ((~curr[e2]) | (e1 < e2)) & (~pin[e3])
    tgt2 = jnp.where(dec2, e2, m).astype(jnp.int32)
    tgt3 = jnp.where(dec3, e3, m).astype(jnp.int32)
    dec_ref[...] = dec_ref[...].at[tgt2].add(1).at[tgt3].add(1)


def peel_decrement_fold(active, l, e1, e2, e3, S_ext, processed, inCurr,
                        pinned, *, chunk: int, n_chunks: int, m: int,
                        interpret: bool = True):
    """Fused decrement fold over the peel table at sub-level ``l``.

    active: (n_chunks,) int32 chunk mask; l: (1,) int32; table rows
    e1/e2/e3: (n_chunks*chunk,) int32 (``core.pkt.PeelTables``);
    S_ext/processed/inCurr/pinned: (m+1,) int32 (pinned all-zero when the
    caller has no schedule edges).  Returns the (m+1,) int32 decrement
    vector accumulated on-chip — slot ``m`` absorbs sentinel writes; read
    the result below index m.  Trace-level: ``core/pkt.py`` calls this
    inside its jitted peel loop.
    """
    kernel = functools.partial(_peel_chunk_kernel, m=m)
    chunk_spec = wedge_common.chunk_spec(chunk)
    full = wedge_common.replicated_spec
    return pl.pallas_call(
        kernel,
        grid=(n_chunks,),
        in_specs=[
            wedge_common.chunk_spec(1),           # active (per chunk)
            full(1),                              # l (replicated scalar)
            chunk_spec, chunk_spec, chunk_spec,   # e1, e2, e3
            full(m + 1), full(m + 1),             # S_ext, processed
            full(m + 1), full(m + 1),             # inCurr, pinned
        ],
        out_specs=[full(m + 1)],
        out_shape=[jax.ShapeDtypeStruct((m + 1,), jnp.int32)],
        interpret=interpret,
    )(active, l, e1, e2, e3, S_ext, processed, inCurr, pinned)[0]


@functools.partial(jax.jit, static_argnames=("chunk", "n_chunks", "m",
                                             "interpret"))
def peel_decrements(active, l, e1, e2, e3, S_ext, processed, inCurr, *,
                    chunk: int, n_chunks: int, m: int,
                    interpret: bool = True):
    """Jitted convenience wrapper: fused fold with no pinned edges → (m+1,)
    decrement vector (slot m absorbs sentinel writes). Used directly by tests
    and the CI interpret-compile gate; ``core/pkt.py`` traces
    ``peel_decrement_fold`` inside its peel loop."""
    return peel_decrement_fold(
        active, l, e1, e2, e3, S_ext, processed, inCurr,
        jnp.zeros((m + 1,), jnp.int32),
        chunk=chunk, n_chunks=n_chunks, m=m, interpret=interpret)
