"""Pallas stream-compaction (prefix-scan) kernel.

After index intersection, the engine needs the *positions* of set mask bits
to gather selected documents.  The parallel primitive is an exclusive
prefix sum over the mask; the scatter that finishes compaction is left to
XLA (it is memory-bound either way).

The kernel walks row-blocks sequentially, carrying the running count in
SMEM scratch — the canonical "scan with carry" pattern on TPU where grid
steps execute in order.  Within a block, a 2-D (8, L) tile is scanned
row-major: a lane-wise scan plus per-sublane offsets.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["compact", "mask_prefix_sum_batched", "compact_batched"]

DEFAULT_BLOCK = 8 * 512


def _inclusive_scan(x, axis: int):
    """Inclusive prefix sum of an int32 tile along ``axis`` in log2 steps
    of shift-and-add (Hillis–Steele).  Mosaic has no cumsum lowering; a
    roll is one cross-lane (or cross-sublane) rotate per step."""
    n = x.shape[axis]
    pos = jax.lax.broadcasted_iota(jnp.int32, x.shape, axis)
    shift = 1
    while shift < n:
        x = x + jnp.where(pos >= shift, pltpu.roll(x, shift, axis), 0)
        shift *= 2
    return x


def _scan_batched_kernel(mask_ref, pos_ref, total_ref, carry_ref):
    """Per-(shard, row-block) scan step; the carry resets at each shard's
    first block, so one launch scans a whole wave of shards.  A (8, L)
    tile is scanned row-major: lane-wise scan plus per-sublane offsets."""
    i = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        carry_ref[0] = 0

    x = mask_ref[0, 0].astype(jnp.int32)           # (8, L)
    lane_cs = _inclusive_scan(x, 1)                # inclusive along lanes
    last = x.shape[1] - 1
    row_tot = jnp.broadcast_to(lane_cs[:, last:], x.shape)
    row_off = _inclusive_scan(row_tot, 0) - row_tot
    carry = carry_ref[0]
    pos_ref[0, 0] = lane_cs - x + row_off + carry   # exclusive
    carry_ref[0] = carry + jnp.sum(x, dtype=jnp.int32)
    total_ref[i] = carry_ref[0]                    # last block's write wins


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def mask_prefix_sum_batched(masks: jnp.ndarray, block: int = DEFAULT_BLOCK,
                            interpret: bool = False):
    """masks [S, N] bool → (exclusive prefix sums [S, N] int32, counts [S]).

    The wave dimension S stacks shards (ragged lengths False-padded to the
    wave max by the caller); the grid walks (shard, row-block) with the
    running count carried in SMEM and reset per shard, so the whole wave is
    one kernel launch.  Grid order is sequential in both dimensions
    (``arbitrary`` semantics) — the scan-with-carry pattern requires it.
    The per-shard counts are one SMEM word each, so S stays far below
    SMEM's size for any wave the engine builds.
    """
    s, n = masks.shape
    if n == 0 or s == 0:
        return (jnp.zeros((s, n), jnp.int32), jnp.zeros((s,), jnp.int32))
    padded = pl.cdiv(n, block) * block
    m_p = jnp.zeros((s, padded), jnp.bool_).at[:, :n].set(masks)
    m2 = m_p.reshape(s, -1, 8, block // 8)
    nblk = m2.shape[1]
    pos, totals = pl.pallas_call(
        _scan_batched_kernel,
        name="mask_prefix_sum_batched",
        grid=(s, nblk),
        in_specs=[pl.BlockSpec((1, 1, 8, block // 8),
                               lambda i, j: (i, j, 0, 0))],
        out_specs=[
            pl.BlockSpec((1, 1, 8, block // 8), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(m2.shape, jnp.int32),
            jax.ShapeDtypeStruct((s,), jnp.int32),
        ],
        scratch_shapes=[pltpu.SMEM((1,), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(m2)
    return pos.reshape(s, -1)[:, :n], totals


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def compact_batched(masks: jnp.ndarray, block: int = DEFAULT_BLOCK,
                    interpret: bool = False):
    """masks [S, N] → (indices [S, N] int32, -1 padded; counts [S])."""
    s, n = masks.shape
    pos, counts = mask_prefix_sum_batched(masks, block=block,
                                          interpret=interpret)
    slot = jnp.where(masks, pos, n)
    rows = jax.lax.broadcasted_iota(jnp.int32, (s, n), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (s, n), 1)
    idx = jnp.full((s, n), -1, jnp.int32)
    idx = idx.at[rows, slot].set(cols, mode="drop")
    return idx, counts


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def compact(mask: jnp.ndarray, block: int = DEFAULT_BLOCK,
            interpret: bool = False):
    """mask [N] → (indices [N] int32, -1 padded; count int32): the
    one-shard case of :func:`compact_batched`."""
    idx, counts = compact_batched(mask[None], block=block,
                                  interpret=interpret)
    return idx[0], counts[0]
