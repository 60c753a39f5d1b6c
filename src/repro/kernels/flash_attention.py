"""Pallas flash attention (forward) for the serving/training stack.

Tiled online-softmax attention with:
  * GQA — Hq query heads read Hkv ≤ Hq KV heads via the index map,
  * causal masking with a *decode offset* (Sq may be shorter than Skv,
    aligned to the end — covers prefill-with-cache and single-token decode),
  * sliding-window masking (Mixtral SWA, Gemma-3 local layers),
  * tanh logit soft-capping (Gemma),
  * fully-masked KV blocks are skipped (causal/window block pruning).

Grid: (B·Hq, Sq/bq, Skv/bk), KV innermost & sequential; running max m,
denominator l and the output accumulator live in VMEM scratch across the
KV loop.  Blocks default to (bq, d) = (256, head_dim) and bk = 256:
q/k/v tiles are ≤ 256·256·4 B = 256 KiB total — comfortably inside VMEM,
and every matmul dimension is a multiple of the 128-wide MXU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_attention"]

DEFAULT_BLOCK_Q = 256
DEFAULT_BLOCK_K = 256
_NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  scale: float, causal: bool, window: int | None,
                  softcap: float | None, sq: int, skv: int,
                  block_q: int, block_k: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # absolute positions (query block sits at the *end* of the kv axis)
    q_pos = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0) + (skv - sq)
    k_pos = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)

    # block-level pruning: skip kv blocks fully outside the mask
    q_last = qi * block_q + block_q - 1 + (skv - sq)
    k_first = ki * block_k
    k_last = k_first + block_k - 1
    needed = True
    if causal:
        needed = k_first <= q_last
    if window is not None:
        q_first = qi * block_q + (skv - sq)
        needed = jnp.logical_and(needed, k_last > q_first - window)

    @pl.when(needed)
    def _compute():
        q = q_ref[0].astype(jnp.float32)              # (bq, d)
        k = k_ref[0].astype(jnp.float32)              # (bk, d)
        v = v_ref[0].astype(jnp.float32)              # (bk, d)
        logits = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if softcap is not None:
            logits = softcap * jnp.tanh(logits / softcap)
        mask = jnp.ones_like(logits, dtype=jnp.bool_)
        if causal:
            mask &= k_pos <= q_pos
        if window is not None:
            mask &= k_pos > q_pos - window
        mask &= k_pos < skv                            # kv padding
        logits = jnp.where(mask, logits, _NEG_INF)

        m_prev = m_ref[...][:, :1]                     # (bq, 1)
        m_cur = jnp.max(logits, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(logits - m_new)                    # (bq, bk)
        correction = jnp.exp(m_prev - m_new)           # (bq, 1)
        l_prev = l_ref[...][:, :1]
        l_new = l_prev * correction + p.sum(axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * correction + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(ki == nk - 1)
    def _finish():
        l = l_ref[...][:, :1]
        safe = jnp.where(l > 0.0, l, 1.0)
        o_ref[0] = (acc_ref[...] / safe).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "softcap", "scale", "block_q", "block_k",
    "interpret"))
def flash_attention(q, k, v, *, causal: bool = True,
                    window: int | None = None,
                    softcap: float | None = None,
                    scale: float | None = None,
                    block_q: int = DEFAULT_BLOCK_Q,
                    block_k: int = DEFAULT_BLOCK_K,
                    interpret: bool = False):
    """q [B,Hq,Sq,D], k/v [B,Hkv,Skv,D] → [B,Hq,Sq,D] (GQA)."""
    b, hq, sq, d = q.shape
    _, hkv, skv, _ = k.shape
    assert hq % hkv == 0, "GQA requires Hq % Hkv == 0"
    group = hq // hkv
    scale_v = scale if scale is not None else 1.0 / np.sqrt(d)

    bq = min(block_q, max(sq, 8))
    bk = min(block_k, max(skv, 128))
    sq_p = pl.cdiv(sq, bq) * bq
    skv_p = pl.cdiv(skv, bk) * bk
    qp = jnp.zeros((b, hq, sq_p, d), q.dtype).at[:, :, :sq].set(q)
    kp = jnp.zeros((b, hkv, skv_p, d), k.dtype).at[:, :, :skv].set(k)
    vp = jnp.zeros((b, hkv, skv_p, d), v.dtype).at[:, :, :skv].set(v)
    q3 = qp.reshape(b * hq, sq_p, d)
    k3 = kp.reshape(b * hkv, skv_p, d)
    v3 = vp.reshape(b * hkv, skv_p, d)

    def kv_head(bh):
        return (bh // hq) * hkv + (bh % hq) // group

    grid = (b * hq, sq_p // bq, skv_p // bk)
    out = pl.pallas_call(
        functools.partial(
            _flash_kernel, scale=scale_v, causal=causal, window=window,
            softcap=softcap, sq=sq, skv=skv, block_q=bq, block_k=bk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, bk, d),
                         lambda bh, qi, ki: (kv_head(bh), ki, 0)),
            pl.BlockSpec((1, bk, d),
                         lambda bh, qi, ki: (kv_head(bh), ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b * hq, sq_p, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 128), jnp.float32),     # m
            pltpu.VMEM((bq, 128), jnp.float32),     # l
            pltpu.VMEM((bq, d), jnp.float32),       # acc
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q3, k3, v3)
    return out.reshape(b, hq, sq_p, d)[:, :, :sq, :]
