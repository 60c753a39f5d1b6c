"""Pallas group-by partial-aggregation kernel (aggregate_produce, §4.3.4).

Per-shard servers reduce (count, sum, sumsq) per group — enough to finish
count/sum/avg/std_dev at the Mixer.  On TPU the natural formulation is a
one-hot matmul: for a row tile T and group tile G,

    onehot[G, T] = (group_base + iota(G) == gid[None, :])
    [sum; sumsq; count] += [v; v²; 1] @ onehotᵀ     (one MXU product)

which turns a scatter-heavy reduction into dense systolic work — the
paper's CPU hash aggregation re-thought for the MXU (see DESIGN.md
§hardware adaptation).  Grid: (group-blocks, row-blocks); row dimension is
sequential and accumulates into the same output block.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["segment_agg"]

DEFAULT_ROW_BLOCK = 512
DEFAULT_GROUP_BLOCK = 128


def _seg_kernel(gid_ref, val_ref, out_ref, *, group_block: int):
    g = pl.program_id(0)
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    gid = gid_ref[...]                                # (1, T) int32
    v = val_ref[...]                                  # (1, T) float32
    groups = g * group_block + jax.lax.broadcasted_iota(
        jnp.int32, (group_block, gid.shape[1]), 0)
    onehot = (groups == gid).astype(jnp.float32)      # (G, T)
    # LHS rows: value, value², 1 (rows 3..7 zero) — one MXU product
    # yields sum, sum of squares and count for the whole group block
    r = jax.lax.broadcasted_iota(jnp.int32, (8, gid.shape[1]), 0)
    lhs = jnp.where(r == 0, v, jnp.where(r == 1, v * v,
                                         jnp.where(r == 2, 1.0, 0.0)))
    out_ref[...] += jax.lax.dot_general(
        lhs, onehot, (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)           # (8, G)


@functools.partial(jax.jit, static_argnames=("num_groups", "row_block",
                                             "group_block", "interpret"))
def segment_agg(group_ids: jnp.ndarray, values: jnp.ndarray,
                num_groups: int, row_block: int = DEFAULT_ROW_BLOCK,
                group_block: int = DEFAULT_GROUP_BLOCK,
                interpret: bool = False):
    """group_ids [N] int32 (−1 = masked), values [N] → count/sum/sumsq [G].

    The products run at ``HIGHEST`` precision so float32 values are not
    rounded to bfloat16 on the MXU; counts are exact below 2**24 rows per
    group."""
    n = group_ids.shape[0]
    padded_n = pl.cdiv(n, row_block) * row_block
    padded_g = pl.cdiv(num_groups, group_block) * group_block
    gid = jnp.full((padded_n,), -1, jnp.int32).at[:n].set(
        group_ids.astype(jnp.int32))
    val = jnp.zeros((padded_n,), jnp.float32).at[:n].set(
        values.astype(jnp.float32))
    gid2 = gid.reshape(1, -1)
    val2 = val.reshape(1, -1)
    n_row_blocks = padded_n // row_block
    n_grp_blocks = padded_g // group_block
    out = pl.pallas_call(
        functools.partial(_seg_kernel, group_block=group_block),
        name="segment_agg",
        grid=(n_grp_blocks, n_row_blocks),
        in_specs=[
            pl.BlockSpec((1, row_block), lambda g, t: (0, t)),
            pl.BlockSpec((1, row_block), lambda g, t: (0, t)),
        ],
        out_specs=pl.BlockSpec((8, group_block), lambda g, t: (0, g)),
        out_shape=jax.ShapeDtypeStruct((8, padded_g), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(gid2, val2)
    return (out[2, :num_groups], out[0, :num_groups], out[1, :num_groups])
