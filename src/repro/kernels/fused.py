"""Fused per-wave pipeline: probe → refine → compact → segment-agg in ONE
dispatch (paper §4: pipelined evaluation; the flash-attention kernel is the
in-repo exemplar of a fused multi-stage pass).

The legacy batched path issues one launch *per primitive* per wave and
round-trips host↔device between stages.  :func:`run_wave_fused` chains the
same stage math inside a single ``jax.jit`` composition — the stacked
bitmap AND, the exact track refine (with the ordered-query first-hit edge
compare), mask compaction, and the offset-coded segment aggregation — so a
wave of shards costs one dispatch and zero intermediate host syncs.  Under
``impl="pallas"``/``"interpret"`` each stage lowers to its Pallas kernel
inside the jit; under ``"reference"`` the pure-jnp oracles compose (and the
whole call runs under ``enable_x64`` so aggregation accumulates float64 in
row order, bit-equal to the numpy oracle).

Inputs are the wave-stacked buffers the backend seam already builds:

* ``probe_stack`` [S, K, W] uint32 — row 0 the shard's valid-doc bitmap,
  rows 1.. the probe bitmaps, pad rows copies of row 0 (identity for AND).
* ``ns`` [S] int32 — per-shard doc counts (rows beyond are padding).
* ``pts``/``rows``/``cov`` — packed ragged tracks + constraint cover, or
  ``None`` when the plan has no refine stage.
* ``codes`` [S, N] int32 — per-row group codes already offset into the
  wave-global group space (−1 = padding), or ``None`` without aggregation.
* ``vals`` — tuple of [S, N] float value stacks, one per distinct
  aggregated column (a single zeros stack for count-only plans).

Returns ``(cand [S], sel_idx [S, N], sel_counts [S], segs)`` with ``cand``
the pre-refine candidate counts, ``sel_idx``/``sel_counts`` the compacted
survivor row ids, and ``segs`` a list of ``(count, sum, sumsq)`` triples
over the wave-global group space (``None`` without aggregation).

This module never imports ``kernels.ops`` (ops wraps *it* and owns launch
counting).
"""
from __future__ import annotations

import contextlib
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import bitset as _bitset
from . import compact as _compact
from . import f64_words as _f64
from . import ref as _ref
from . import refine as _refine
from . import segment_agg as _seg

__all__ = ["run_wave_fused", "run_wave_fused_multi", "postings_bitmap",
           "segment_hll"]


# --------------------------------------------------------------------------
# Stage bodies of the jitted composition
# --------------------------------------------------------------------------

def _probe_stage(impl: str, probe_stack):
    if impl == "reference":
        bm, _ = _ref.bitmap_intersect_batched_ref(probe_stack)
    else:
        bm, _ = _bitset.bitmap_intersect_batched(
            probe_stack, interpret=(impl == "interpret"))
    return bm


def _mask_stage(bm, ns, num_docs: int):
    """Word bitmaps [S, W] → per-doc bool masks [S, num_docs]."""
    docs = jnp.arange(num_docs, dtype=jnp.int32)
    words = bm[:, docs >> 5]
    bits = (words >> (docs & 31).astype(jnp.uint32)) & jnp.uint32(1)
    return (bits != 0) & (docs[None, :] < ns[:, None])


def _reduction_verdict(fh_hi, fh_lo, lh_hi, lh_lo, cnt, edges,
                       min_counts, dwells):
    """Per-doc verdict recomputed from the reduction tables (leading axes
    arbitrary; constraint axis second-to-last).  The kernel's bits==full
    mask can't express k=0 (vacuous) constraints, so the verdict ANDs
    per-constraint ``ok`` terms built from the count table instead:
    ``doc_hit ≡ cnt > 0`` exactly.  Static python loop — zero launches."""
    n_c = cnt.shape[-2]
    out = None
    for c in range(n_c):
        doc_hit = cnt[..., c, :] > 0
        k = int(min_counts[c]) if c < len(min_counts) else 1
        if k == 1:
            ok = doc_hit
        elif k <= 0:
            ok = jnp.ones_like(doc_hit)
        else:
            ok = cnt[..., c, :] >= k
        d = dwells[c] if c < len(dwells) else None
        if d is not None:
            span_ok = _f64.span_at_least(fh_hi[..., c, :], fh_lo[..., c, :],
                                         lh_hi[..., c, :], lh_lo[..., c, :],
                                         d)
            ok = ok & doc_hit & span_ok
        out = ok if out is None else (out & ok)
    for i, j in edges:               # A-then-B: first hit of i before j's
        a_hi, a_lo = fh_hi[..., i, :], fh_lo[..., i, :]
        b_hi, b_lo = fh_hi[..., j, :], fh_lo[..., j, :]
        out = out & ((a_hi < b_hi) | ((a_hi == b_hi) & (a_lo < b_lo)))
    return out


def _has_reductions(min_counts, dwells) -> bool:
    return any(int(k) != 1 for k in min_counts) \
        or any(d is not None for d in dwells)


def _refine_stage(impl: str, pts, rows, cov, num_docs: int,
                  edges: Tuple[Tuple[int, int], ...],
                  min_counts: Tuple[int, ...] = (),
                  dwells: Tuple[Optional[float], ...] = ()):
    wa = _has_reductions(min_counts, dwells)
    wf = bool(edges) and not wa
    if impl == "reference":
        r = _ref.refine_tracks_batched_ref(pts, rows, cov,
                                           num_docs=num_docs,
                                           with_first_hits=wf,
                                           with_analytics=wa)
    else:
        r = _refine.refine_tracks_batched(pts, rows, cov, num_docs,
                                          interpret=(impl == "interpret"),
                                          with_first_hits=wf,
                                          with_analytics=wa)
    if wa:
        _, fh_hi, fh_lo, lh_hi, lh_lo, cnt = r
        return _reduction_verdict(fh_hi, fh_lo, lh_hi, lh_lo, cnt, edges,
                                  min_counts, dwells)
    if not wf:
        return r
    out, fh_hi, fh_lo = r
    for i, j in edges:               # A-then-B: first hit of i before j's
        a_hi, a_lo = fh_hi[:, i, :], fh_lo[:, i, :]
        b_hi, b_lo = fh_hi[:, j, :], fh_lo[:, j, :]
        out = out & ((a_hi < b_hi) | ((a_hi == b_hi) & (a_lo < b_lo)))
    return out


def _compact_stage(impl: str, mask):
    if impl == "reference":
        return _ref.compact_batched_ref(mask)
    return _compact.compact_batched(mask, interpret=(impl == "interpret"))


def _agg_stage(impl: str, mask, codes, vals, total_groups: int,
               minmax: Tuple[bool, ...] = ()):
    """Per-value-slot segment partials.  Slots flagged in ``minmax`` grow
    per-group min/max reductions in the same pass — pure-jnp
    ``segment_min``/``segment_max`` under every impl (min/max commute with
    the f64→f32 staging cast, so interpret/pallas stay allclose and
    ``reference`` f64 is exact/order-independent); those slots return
    5-tuples ``(count, sum, sumsq, min, max)``, the rest the usual
    triples.  Groups with count 0 carry ±inf fills and are dropped by the
    backend's ``count > 0`` keep-filter."""
    gc = jnp.where(mask, codes, jnp.int32(-1)).reshape(-1)
    valid = gc >= 0
    gid = jnp.where(valid, gc, 0)
    segs = []
    for k, v in enumerate(vals):
        vv = v.reshape(-1)
        if impl == "reference":
            seg = _ref.segment_agg_ref(gc, vv, total_groups)
        else:
            seg = _seg.segment_agg(gc, vv, total_groups,
                                   interpret=(impl == "interpret"))
        if k < len(minmax) and minmax[k]:
            inf = jnp.asarray(jnp.inf, vv.dtype)
            mn = jax.ops.segment_min(jnp.where(valid, vv, inf), gid,
                                     num_segments=total_groups)
            mx = jax.ops.segment_max(jnp.where(valid, vv, -inf), gid,
                                     num_segments=total_groups)
            seg = (*seg, mn, mx)
        segs.append(seg)
    return segs


@functools.lru_cache(maxsize=None)
def _fused_fn(impl: str, num_docs: int,
              edges: Tuple[Tuple[int, int], ...], total_groups: int,
              has_refine: bool, minmax: Tuple[bool, ...] = (),
              min_counts: Tuple[int, ...] = (),
              dwells: Tuple[Optional[float], ...] = ()):
    """One jitted end-to-end wave pipeline for a static stage config."""

    def fn(probe_stack, ns, pts, rows, cov, codes, vals):
        mask = _mask_stage(_probe_stage(impl, probe_stack), ns, num_docs)
        cand = mask.sum(axis=1).astype(jnp.int32)
        if has_refine:
            mask = mask & _refine_stage(impl, pts, rows, cov, num_docs,
                                        edges, min_counts, dwells)
        sel_idx, sel_counts = _compact_stage(impl, mask)
        segs = None
        if total_groups > 0:
            segs = _agg_stage(impl, mask, codes, vals, total_groups,
                              minmax)
        return cand, sel_idx, sel_counts, segs

    return jax.jit(fn)


def run_wave_fused(probe_stack, ns, pts=None, rows=None, cov=None,
                   codes=None, vals=(), *, num_docs: int,
                   edges=(), min_counts=(), dwells=(),
                   total_groups: int = 0,
                   impl: str = "reference", minmax=()):
    """Run one wave through the fused pipeline (see module docstring).
    ``minmax`` flags which value slots also reduce per-group min/max
    (5-tuple partials); ``min_counts``/``dwells`` apply per-constraint
    count/dwell verdicts inside the refine stage — same dispatch, no
    extra launches.  Dwell verdicts subtract the packed timestamps with
    exact uint32 word arithmetic (``kernels.f64_words``)."""
    edges = tuple(tuple(e) for e in edges)
    min_counts = tuple(int(k) for k in min_counts)
    dwells = tuple(None if d is None else float(d) for d in dwells)
    vals = tuple(vals)
    minmax = tuple(bool(m) for m in minmax)
    has_refine = pts is not None
    # reference: f64 value stacks + f64 accumulation, bit-equal to the
    # host oracle
    ctx = jax.enable_x64(True) if impl == "reference" \
        else contextlib.nullcontext()
    with ctx:
        return _fused_fn(impl, num_docs, edges, total_groups,
                         has_refine, minmax, min_counts,
                         dwells)(probe_stack, ns, pts, rows, cov, codes,
                                 vals)


# --------------------------------------------------------------------------
# Multi-query fused wave — the serve layer's coalesced dispatch
# --------------------------------------------------------------------------

def _refine_multi_stage(impl: str, pts, rows, cov, num_docs: int,
                        edges_multi, min_counts_multi=(),
                        dwells_multi=()):
    """Query-axis refine: cov [Q, C, 8, R] → masks [Q, S, num_docs], with
    each query's ordering edges applied against its own slice of the
    first-hit tables (static per-query compare chain, zero launches).
    Queries carrying count/dwell reductions get their verdict recomputed
    from their slice of the analytics tables instead — same launch."""
    wa = any(_has_reductions(mc, ()) for mc in min_counts_multi) \
        or any(_has_reductions((), dw) for dw in dwells_multi)
    wf = any(len(e) > 0 for e in edges_multi) and not wa
    if impl == "reference":
        r = _ref.refine_tracks_multi_ref(pts, rows, cov,
                                         num_docs=num_docs,
                                         with_first_hits=wf,
                                         with_analytics=wa)
    else:
        r = _refine.refine_tracks_multi(pts, rows, cov, num_docs,
                                        interpret=(impl == "interpret"),
                                        with_first_hits=wf,
                                        with_analytics=wa)
    if wa:
        out, fh_hi, fh_lo, lh_hi, lh_lo, cnt = r
        per_q = []
        for qi, edges in enumerate(edges_multi):
            mc = min_counts_multi[qi] if qi < len(min_counts_multi) else ()
            dw = dwells_multi[qi] if qi < len(dwells_multi) else ()
            if _has_reductions(mc, dw):
                m = _reduction_verdict(fh_hi[qi], fh_lo[qi], lh_hi[qi],
                                       lh_lo[qi], cnt[qi], edges, mc, dw)
            else:
                m = out[qi]
                for i, j in edges:
                    a_hi, a_lo = fh_hi[qi, :, i, :], fh_lo[qi, :, i, :]
                    b_hi, b_lo = fh_hi[qi, :, j, :], fh_lo[qi, :, j, :]
                    m = m & ((a_hi < b_hi)
                             | ((a_hi == b_hi) & (a_lo < b_lo)))
            per_q.append(m)
        return jnp.stack(per_q)
    if not wf:
        return r
    out, fh_hi, fh_lo = r
    per_q = []
    for qi, edges in enumerate(edges_multi):
        m = out[qi]
        for i, j in edges:           # A-then-B: first hit of i before j's
            a_hi, a_lo = fh_hi[qi, :, i, :], fh_lo[qi, :, i, :]
            b_hi, b_lo = fh_hi[qi, :, j, :], fh_lo[qi, :, j, :]
            m = m & ((a_hi < b_hi) | ((a_hi == b_hi) & (a_lo < b_lo)))
        per_q.append(m)
    return jnp.stack(per_q)


@functools.lru_cache(maxsize=None)
def _fused_multi_fn(impl: str, num_docs: int, edges_multi, has_refine,
                    min_counts_multi=(), dwells_multi=()):
    """One jitted multi-query wave pipeline (probe → refine → compact).
    The query axis is folded into the shard axis for the probe and compact
    stages (the stacked kernels are shape-agnostic in S) and kept leading
    through the refine kernel's per-query constraint tables."""

    def fn(probe_stacks, ns, pts, rows, cov):
        q, s = probe_stacks.shape[0], probe_stacks.shape[1]
        flat = probe_stacks.reshape((q * s,) + probe_stacks.shape[2:])
        ns_flat = jnp.tile(ns, q)                     # [(Q·S)]
        mask = _mask_stage(_probe_stage(impl, flat), ns_flat, num_docs)
        mask = mask.reshape(q, s, num_docs)
        cand = mask.sum(axis=2).astype(jnp.int32)
        if has_refine:
            mask = mask & _refine_multi_stage(impl, pts, rows, cov,
                                              num_docs, edges_multi,
                                              min_counts_multi,
                                              dwells_multi)
        sel_idx, sel_counts = _compact_stage(
            impl, mask.reshape(q * s, num_docs))
        return (cand, sel_idx.reshape(q, s, num_docs),
                sel_counts.reshape(q, s))

    return jax.jit(fn)


def run_wave_fused_multi(probe_stacks, ns, pts=None, rows=None, cov=None,
                         *, num_docs: int, edges_multi=(),
                         min_counts_multi=(), dwells_multi=(),
                         impl: str = "reference"):
    """Q coalesced queries through one wave in ONE dispatch.

    ``probe_stacks`` [Q, S, K, W] uint32 — each query's wave-stacked probe
    bitmaps (pad rows AND-identity as in the single-query path); ``cov``
    [Q, C, 8, R] uint32 — per-query constraint tables padded to common
    C/R (always-hit constraints / never-hit range slots); track buffers
    are shared.  ``edges_multi`` is one edge tuple per query;
    ``min_counts_multi``/``dwells_multi`` one reduction tuple per query
    (pad constraints keep the k=1 / no-dwell defaults).  Returns
    ``(cand [Q, S], sel_idx [Q, S, N], sel_counts [Q, S])``.
    """
    edges_multi = tuple(tuple(tuple(e) for e in es) for es in edges_multi)
    min_counts_multi = tuple(tuple(int(k) for k in mc)
                             for mc in min_counts_multi)
    dwells_multi = tuple(tuple(None if d is None else float(d) for d in dw)
                         for dw in dwells_multi)
    has_refine = pts is not None
    fn = _fused_multi_fn(impl, num_docs, edges_multi, has_refine,
                         min_counts_multi, dwells_multi)
    ctx = jax.enable_x64(True) if impl == "reference" \
        else contextlib.nullcontext()
    with ctx:
        return fn(probe_stacks, ns, pts, rows, cov)


# --------------------------------------------------------------------------
# Postings OR — SpaceTimeIndex.lookup's tail lowered behind the seam
# --------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("n_docs",))
def _postings_bitmap(ids, t_min, t_max, t0, t1, n_docs: int):
    nw = (n_docs + 31) // 32
    hit = jnp.zeros((nw * 32,), jnp.bool_).at[ids].set(True, mode="drop")
    lo_key = _f64.key_from_bits(t_min[:, 1], t_min[:, 0])
    hi_key = _f64.key_from_bits(t_max[:, 1], t_max[:, 0])
    span_ok = (_f64.less_equal(*lo_key, t1[0], t1[1])
               & _f64.less_equal(t0[0], t0[1], *hi_key)
               & ~_f64.is_nan(t_min[:, 1], t_min[:, 0])
               & ~_f64.is_nan(t_max[:, 1], t_max[:, 0]))
    overlap = jnp.zeros((nw * 32,), jnp.bool_).at[:n_docs].set(span_ok)
    bits = (hit & overlap).reshape(nw, 32).astype(jnp.uint32)
    # doc 32·w + b → word w, bit b: the bitmap_from_ids word layout
    return (bits << jnp.arange(32, dtype=jnp.uint32)[None, :]).sum(
        axis=1, dtype=jnp.uint32)


def _key_words(t: float):
    """Host float64 → its sort-key (hi, lo) words as a uint32 [2] array."""
    k = int(np.float64(float(t) + 0.0).view(np.uint64))
    k = (~k & 0xFFFFFFFFFFFFFFFF) if k >> 63 else k | (1 << 63)
    return np.array([k >> 32, k & 0xFFFFFFFF], np.uint32)


def postings_bitmap(ids, t_min, t_max, t0, t1, n_docs: int):
    """OR doc ``ids`` into a word bitmap and prune docs whose ``[t_min,
    t_max]`` track span misses ``[t0, t1]`` — the host tail of
    ``SpaceTimeIndex.lookup`` as one device pass (pure-jnp lowering under
    every ``impl``; scatter-OR has no Pallas kernel).  ``t_min``/``t_max``
    are float64 spans as uint32 ``[n, 2]`` (lo, hi) bit words (the
    ``DeviceCache`` device form); the span compare runs on their sort
    keys, so it matches the host's float64 compare exactly.
    """
    if n_docs <= 0:
        return jnp.zeros((0,), jnp.uint32)
    if np.isnan(t0) or np.isnan(t1):
        return jnp.zeros(((n_docs + 31) // 32,), jnp.uint32)
    return _postings_bitmap(jnp.asarray(np.asarray(ids, np.int32)), t_min,
                            t_max, _key_words(t0), _key_words(t1), n_docs)


# --------------------------------------------------------------------------
# Segment HLL — per-group HyperLogLog register max behind the seam
# --------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("num_groups",))
def _segment_hll(group_ids, regs, num_groups: int):
    valid = group_ids >= 0
    gid = jnp.where(valid, group_ids, 0)
    r = jnp.where(valid[:, None], regs, jnp.uint8(0))
    return jax.ops.segment_max(r, gid, num_segments=num_groups)


def segment_hll(group_ids, regs, num_groups: int):
    """Per-group HLL register max: group_ids [N] int32 (< 0 masked out) ×
    regs [N, M] uint8 register rows → [num_groups, M] maxed planes.
    ``segment_max``'s identity for uint8 is 0 — exactly an empty HLL
    register — so groups with no rows come back as empty sketches.
    Register max is the HLL merge: commutative and idempotent, so the
    result is invariant to row order and partitioning by construction.
    """
    if num_groups <= 0:
        return jnp.zeros((0, int(regs.shape[1])), jnp.uint8)
    return _segment_hll(jnp.asarray(group_ids), jnp.asarray(regs),
                        num_groups)
