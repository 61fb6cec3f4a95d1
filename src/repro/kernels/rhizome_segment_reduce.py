"""Pallas TPU kernel: blocked semiring segment reduction.

The compute hot-spot of the diffusive engine is the per-shard inbox
reduction: E_max edge messages collapse into R replica slots
(min for BFS/SSSP, + for PageRank). On GPU this is an atomic scatter;
TPU has no fast scatter, so we re-block it for the VPU
(hardware adaptation — DESIGN.md §2):

* edge arrays ride lane-dense as ``(E_pad / 128, 128)`` rows, and the
  edge axis is tiled into ``EBLK``-edge chunks — one ``(ROWS, 128)``
  block, a whole int32/f32 vreg tile, so the HBM and Mosaic layouts of
  every edge operand agree;
* the segment axis is tiled into ``SBLK`` blocks; grid cell (i, j)
  compares, row by row, the chunk's ids against the block's segment
  numbers laid down the sublanes — an ``(SBLK, 128)`` hit mask per row —
  and folds the selected messages into an ``(SBLK, 128)`` accumulator
  with elementwise min / add (VPU only, no gather, no scatter);
* one transpose and a sublane reduction turn the accumulator into the
  lane-dense ``(1, SBLK)`` block contribution, which is combined into
  the output block for segment block *i* — revisited across all *j* and
  accumulated in place (VMEM-resident);
* because the engine sorts edges by destination, each edge chunk touches
  a narrow segment range: a scalar-prefetched per-chunk [lo, hi] id range
  lets grid cells **skip** non-intersecting (i, j) pairs entirely — the
  sorted-CSR sparsity exploited without dynamic shapes.

The min semiring is exact; the sum semiring differs from a sequential
sum only by float reassociation.  Messages are reduced in f32 (bf16
inputs are upcast by the launcher).  Validated against
``ref.segment_combine_ref`` in interpret mode (CPU); the compiled path
is rehearsed for a v5e chip in ``tests/test_tpu_compile.py``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128          # TPU lane width: edge rows are this wide
ROWS = 8             # edge rows per chunk (one sublane tile)
EBLK = ROWS * LANES  # edge-axis tile (edges per chunk)
SBLK = 256           # segment-axis tile


def identity_of(kind: str) -> float:
    return jnp.inf if kind == "min" else 0.0


def combine(kind: str, a, b):
    return jnp.minimum(a, b) if kind == "min" else a + b


def round_up(x: int, m: int) -> int:
    return -(-max(x, 1) // m) * m


def block_contrib(ids_ref, msg_row, seg0, kind: str):
    """(1, SBLK) contribution of one edge chunk to the segment block that
    starts at ``seg0``.  ``ids_ref`` is the chunk's (ROWS, LANES) id
    block; ``msg_row(r)`` loads row r's (1, LANES) messages."""
    ident = identity_of(kind)
    seg = jax.lax.broadcasted_iota(jnp.int32, (SBLK, LANES), 0) + seg0
    acc = jnp.full((SBLK, LANES), ident, jnp.float32)
    for r in range(ROWS):
        hit = seg == ids_ref[r:r + 1, :]
        acc = combine(kind, acc, jnp.where(hit, msg_row(r), ident))
    reduce = jnp.min if kind == "min" else jnp.sum
    return reduce(acc.T, axis=0, keepdims=True)


def edge_rows(x):
    """(e_pad,) -> the lane-dense (e_pad / LANES, LANES) edge layout."""
    return x.reshape(-1, LANES)


def _kernel(chunk_lo_ref, chunk_hi_ref, ids_ref, msg_ref, out_ref, *, kind):
    i = pl.program_id(0)  # segment block
    j = pl.program_id(1)  # edge chunk

    @pl.when(j == 0)
    def _init():
        out_ref[...] = jnp.full(out_ref.shape, identity_of(kind),
                                out_ref.dtype)

    seg0 = i * SBLK
    # sorted-edges block skip: chunk j covers ids [chunk_lo[j], chunk_hi[j]]
    intersects = (chunk_hi_ref[j] >= seg0) & (chunk_lo_ref[j] < seg0 + SBLK)

    @pl.when(intersects)
    def _compute():
        contrib = block_contrib(ids_ref, lambda r: msg_ref[r:r + 1, :],
                                seg0, kind)
        out_ref[...] = combine(kind, out_ref[...], contrib)


@functools.partial(jax.jit, static_argnames=("num_segments", "kind",
                                             "interpret"))
def segment_combine_pallas(data, segment_ids, num_segments: int, kind: str,
                           *, interpret: bool):
    """Blocked semiring segment reduce. data: (E,), ids: (E,) sorted or not;
    returns (num_segments,). Padding edges must carry id 0 with identity data
    or any id with identity data (identity never changes a reduction)."""
    e = data.shape[0]
    e_pad = round_up(e, EBLK)
    s_pad = round_up(num_segments, SBLK)
    identity = identity_of(kind)
    data_p = jnp.full((e_pad,), identity, jnp.float32).at[:e].set(
        data.astype(jnp.float32))
    ids_p = jnp.zeros((e_pad,), jnp.int32).at[:e].set(
        segment_ids.astype(jnp.int32))

    # per-chunk id ranges for the sorted-skip (scalar-prefetch operands)
    idc = ids_p.reshape(e_pad // EBLK, EBLK)
    mask = (jnp.arange(e_pad) < e).reshape(e_pad // EBLK, EBLK)
    chunk_lo = jnp.where(mask, idc, jnp.iinfo(jnp.int32).max).min(axis=1)
    chunk_hi = jnp.where(mask, idc, -1).max(axis=1)

    n_i = s_pad // SBLK
    edge_spec = pl.BlockSpec((ROWS, LANES), lambda i, j, lo, hi: (j, 0))
    out = pl.pallas_call(
        functools.partial(_kernel, kind=kind),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n_i, e_pad // EBLK),
            in_specs=[edge_spec, edge_spec],
            out_specs=pl.BlockSpec((pl.Squeezed(), 1, SBLK),
                                   lambda i, j, lo, hi: (i, 0, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((n_i, 1, SBLK), jnp.float32),
        interpret=interpret,
    )(chunk_lo, chunk_hi, edge_rows(ids_p), edge_rows(data_p))
    return out.reshape(s_pad)[:num_segments].astype(data.dtype)
