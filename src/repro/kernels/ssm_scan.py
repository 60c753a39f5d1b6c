"""Pallas chunked linear-recurrence scan (Mamba / mLSTM inner loop).

Computes h_t = a_t ⊙ h_{t-1} + bx_t over the time axis, with the state
carried across time-chunks in VMEM scratch (grid steps execute in order on
TPU, so scratch persists across the sequential chunk dimension).  Within a
chunk the recurrence is solved with an *associative scan* — log₂(T) vector
steps instead of T sequential steps, which is what makes the SSM layers
compute-dense enough to keep up with the MXU-bound attention layers.

Shapes: a, bx [B, L, D] → h [B, L, D].  D is the flattened channel×state
dim (diagonal SSM), padded to the 128-lane boundary by the wrapper.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["ssm_scan"]

DEFAULT_CHUNK = 256


def _scan_kernel(a_ref, bx_ref, h_ref, carry_ref):
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _reset():
        carry_ref[...] = jnp.zeros_like(carry_ref)

    a = a_ref[0].astype(jnp.float32)        # (T, D)
    bx = bx_ref[0].astype(jnp.float32)      # (T, D)

    def combine(x, y):
        ax, bxx = x
        ay, byy = y
        return ax * ay, byy + ay * bxx

    a_sc, b_sc = jax.lax.associative_scan(combine, (a, bx), axis=0)
    h0 = carry_ref[...]                      # (1, D)
    h = b_sc + a_sc * h0                     # broadcast over T
    h_ref[0] = h.astype(h_ref.dtype)
    carry_ref[...] = h[-1:, :]


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssm_scan(a: jnp.ndarray, bx: jnp.ndarray, chunk: int = DEFAULT_CHUNK,
             interpret: bool = False):
    """a, bx [B, L, D] → (h [B, L, D], h_final [B, D])."""
    B, L, D = a.shape
    c = min(chunk, L)
    L_p = pl.cdiv(L, c) * c
    D_p = pl.cdiv(D, 128) * 128
    a_p = jnp.zeros((B, L_p, D_p), jnp.float32).at[:, :L, :D].set(
        a.astype(jnp.float32))
    bx_p = jnp.zeros((B, L_p, D_p), jnp.float32).at[:, :L, :D].set(
        bx.astype(jnp.float32))
    h = pl.pallas_call(
        _scan_kernel,
        grid=(B, L_p // c),
        in_specs=[
            pl.BlockSpec((1, c, D_p), lambda b, t: (b, t, 0)),
            pl.BlockSpec((1, c, D_p), lambda b, t: (b, t, 0)),
        ],
        out_specs=pl.BlockSpec((1, c, D_p), lambda b, t: (b, t, 0)),
        out_shape=jax.ShapeDtypeStruct((B, L_p, D_p), a.dtype),
        scratch_shapes=[pltpu.VMEM((1, D_p), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(a_p, bx_p)
    h = h[:, :L, :D]
    return h, h[:, -1, :]
