"""Pallas bitset kernels — the index-intersection hot loop.

The paper's find() intersects per-index postings; with bitmap postings that
is word-wise AND/OR/ANDNOT plus a popcount for selectivity stats.  On TPU
this is pure VPU work: uint32 lanes, 8×128 vregs.  The kernels tile the
word array into VMEM blocks; ``bitmap_intersect`` AND-reduces K stacked
probe bitmaps in one pass and emits per-block popcounts so the host gets
``rows_selected`` without a second pass.

Blocks are (8, 512) words = 16 KiB per operand — far under VMEM, wide
enough to keep all 8 sublanes × 128 lanes busy.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["bitset_binary", "bitmap_intersect", "bitmap_intersect_batched",
           "DEFAULT_BLOCK_WORDS"]

DEFAULT_BLOCK_WORDS = 8 * 512       # one (8, 512) vreg-aligned tile


def _binary_kernel(a_ref, b_ref, o_ref, *, op: str):
    a = a_ref[...]
    b = b_ref[...]
    if op == "and":
        o_ref[...] = a & b
    elif op == "or":
        o_ref[...] = a | b
    elif op == "andnot":
        o_ref[...] = a & ~b
    else:
        raise ValueError(op)


@functools.partial(jax.jit, static_argnames=("op", "block_words",
                                             "interpret"))
def bitset_binary(a: jnp.ndarray, b: jnp.ndarray, op: str = "and",
                  block_words: int = DEFAULT_BLOCK_WORDS,
                  interpret: bool = False) -> jnp.ndarray:
    """Element-wise bitmap algebra over uint32 word arrays [W]."""
    w = a.shape[0]
    padded = pl.cdiv(w, block_words) * block_words
    a_p = jnp.zeros((padded,), jnp.uint32).at[:w].set(a)
    b_p = jnp.zeros((padded,), jnp.uint32).at[:w].set(b)
    a2 = a_p.reshape(-1, 8, block_words // 8)
    b2 = b_p.reshape(-1, 8, block_words // 8)
    grid = (a2.shape[0],)
    out = pl.pallas_call(
        functools.partial(_binary_kernel, op=op),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 8, block_words // 8), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, 8, block_words // 8), lambda i: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 8, block_words // 8), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct(a2.shape, jnp.uint32),
        interpret=interpret,
    )(a2, b2)
    return out.reshape(-1)[:w]


def _popcount(x):
    """Per-word set-bit count of a uint32 array (SWAR), as int32."""
    x = x - ((x >> 1) & jnp.uint32(0x55555555))
    x = (x & jnp.uint32(0x33333333)) + ((x >> 2) & jnp.uint32(0x33333333))
    x = (x + (x >> 4)) & jnp.uint32(0x0F0F0F0F)
    return ((x * jnp.uint32(0x01010101)) >> jnp.uint32(24)).astype(jnp.int32)


def _intersect_batched_kernel(stack_ref, o_ref, cnt_ref):
    """One (shard, word-block) grid step: AND-reduce that shard's K probes
    for the block and popcount the result.  The count leaves as 128
    per-lane partial sums: a (1, 128) row is a lane-aligned block, where a
    single scalar per block would be a store the TPU cannot make to VMEM."""
    k = stack_ref.shape[1]
    acc = stack_ref[0, 0, 0]                   # (8, L) uint32
    for i in range(1, k):           # K is small & static (probes per query)
        acc = acc & stack_ref[0, i, 0]
    o_ref[0, 0] = acc
    per_lane = jnp.sum(_popcount(acc), axis=0, keepdims=True,
                       dtype=jnp.int32)                     # (1, L)
    part = per_lane[:, 0:128]
    for j in range(128, per_lane.shape[1], 128):
        part = part + per_lane[:, j:j + 128]
    cnt_ref[0, 0] = part


@functools.partial(jax.jit, static_argnames=("block_words", "interpret"))
def bitmap_intersect(stack: jnp.ndarray,
                     block_words: int = DEFAULT_BLOCK_WORDS,
                     interpret: bool = False):
    """AND-reduce probe bitmaps [K, W] → (bitmap [W], total popcount):
    the one-shard case of :func:`bitmap_intersect_batched`."""
    bm, cnt = bitmap_intersect_batched(stack[None], block_words=block_words,
                                       interpret=interpret)
    return bm[0], cnt[0]


@functools.partial(jax.jit, static_argnames=("block_words", "interpret"))
def bitmap_intersect_batched(stack: jnp.ndarray,
                             block_words: int = DEFAULT_BLOCK_WORDS,
                             interpret: bool = False):
    """Multi-shard AND-reduce [S, K, W] → (bitmaps [S, W], popcounts [S]).

    The wave dimension S stacks shards (ragged word counts zero-padded to
    the wave max by the caller); one launch covers the whole wave instead
    of one ``bitmap_intersect`` per shard.  Zero padding is sound for the
    result: every stack includes the shard's valid-doc mask, whose padding
    words are zero, so AND keeps the pad region clear.
    """
    s, k, w = stack.shape
    lanes = block_words // 8
    padded = pl.cdiv(w, block_words) * block_words
    s_p = jnp.zeros((s, k, padded), jnp.uint32).at[:, :, :w].set(stack)
    s2 = s_p.reshape(s, k, -1, 8, lanes)
    nblk = s2.shape[2]
    out, cnt = pl.pallas_call(
        _intersect_batched_kernel,
        name="bitmap_intersect_batched",
        grid=(s, nblk),
        in_specs=[pl.BlockSpec((1, k, 1, 8, lanes),
                               lambda i, j: (i, 0, j, 0, 0))],
        out_specs=[
            pl.BlockSpec((1, 1, 8, lanes), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((1, 1, 1, 128), lambda i, j: (i, j, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((s, nblk, 8, lanes), jnp.uint32),
            jax.ShapeDtypeStruct((s, nblk, 1, 128), jnp.int32),
        ],
        interpret=interpret,
    )(s2)
    return out.reshape(s, -1)[:, :w], cnt.sum(axis=(1, 2, 3))
