"""Unified jit'd entry points for every kernel, with implementation select.

``impl``:
  * ``"pallas"``     — compiled Pallas kernel (TPU target)
  * ``"interpret"``  — Pallas kernel body interpreted on CPU (correctness
                       validation of the exact kernel code)
  * ``"reference"``  — pure-jnp oracle (CPU tests at scale; the 512-device
                       dry-run lowers this path)

Default: ``pallas`` on TPU backends, ``reference`` elsewhere — override
with ``REPRO_KERNEL_IMPL`` or per call.

Every public op records one **launch** per call in a process-wide counter
(:func:`launch_counts` / :func:`reset_launch_counts`), regardless of the
selected ``impl`` — a call is one logical kernel dispatch, which is what
the batched execution path amortizes (one ``*_batched`` launch per wave of
shards instead of one launch per shard).  Tests and benchmarks use the
counter to assert the ⌈shards/wave⌉ dispatch contract.

:func:`run_wave_fused` is one logical dispatch covering *all* stages of a
wave (probe → refine → compact → segment-agg fused in a single jit; see
``kernels.fused``) — on the fused path the contract tightens to
⌈shards/wave⌉ **total** dispatches per query, not per primitive.
"""
from __future__ import annotations

import os
import threading
from collections import Counter
from typing import Dict, Optional

import jax

from . import bitset as _bitset
from . import compact as _compact
from . import flash_attention as _fa
from . import fused as _fused
from . import merge as _merge
from . import ref as _ref
from . import refine as _refine
from . import segment_agg as _seg
from . import ssm_scan as _ssm

__all__ = ["default_impl", "bitmap_binary", "bitmap_intersect",
           "bitmap_intersect_batched", "compact", "compact_batched",
           "segment_agg", "segment_hll", "refine_tracks",
           "refine_tracks_batched", "refine_tracks_multi",
           "run_wave_fused", "run_wave_fused_multi", "postings_bitmap",
           "merge_partials",
           "flash_attention", "ssm_scan",
           "launch_counts", "reset_launch_counts", "record_launch"]


def default_impl() -> str:
    env = os.environ.get("REPRO_KERNEL_IMPL")
    if env:
        return env
    return "pallas" if jax.default_backend() == "tpu" else "reference"


def _resolve(impl: Optional[str]) -> str:
    impl = impl or default_impl()
    if impl not in ("pallas", "interpret", "reference"):
        raise ValueError(f"unknown kernel impl {impl!r}")
    return impl


# --------------------------------------------------------------------------
# Launch counting — engines (and now the query server) dispatch from many
# worker threads concurrently.  Each thread owns a lock-free thread-local
# counter; the aggregate view the launch-contract tests read is the
# lock-protected process-wide sum.  ``scope="thread"`` exposes the calling
# thread's private counts (a dispatch attributed to another thread never
# leaks in), with an epoch stamp so a global reset invalidates every
# thread's stale view.
# --------------------------------------------------------------------------

_LAUNCHES: Counter = Counter()
_LAUNCH_LOCK = threading.Lock()
_LAUNCH_EPOCH = 0
_TL = threading.local()


def _thread_counter() -> Counter:
    """The calling thread's private counter for the current epoch."""
    if getattr(_TL, "epoch", None) != _LAUNCH_EPOCH:
        _TL.epoch = _LAUNCH_EPOCH
        _TL.counts = Counter()
    return _TL.counts


def record_launch(op: str) -> None:
    """Count one logical kernel dispatch under ``op``."""
    _thread_counter()[op] += 1          # thread-local: no lock needed
    with _LAUNCH_LOCK:
        _LAUNCHES[op] += 1


def launch_counts(scope: str = "aggregate") -> Dict[str, int]:
    """Snapshot of per-op dispatch counts since the last reset.

    ``scope="aggregate"`` (default) sums dispatches across all threads —
    what the ⌈shards/wave⌉ contract tests assert, since engines dispatch
    from pool threads.  ``scope="thread"`` returns only dispatches
    recorded by the *calling* thread."""
    if scope == "thread":
        return dict(_thread_counter())
    if scope != "aggregate":
        raise ValueError(f"unknown launch_counts scope {scope!r}")
    with _LAUNCH_LOCK:
        return dict(_LAUNCHES)


def reset_launch_counts() -> None:
    """Zero the aggregate counter and invalidate every thread's local
    view (their next record/read starts a fresh epoch)."""
    global _LAUNCH_EPOCH
    with _LAUNCH_LOCK:
        _LAUNCHES.clear()
        _LAUNCH_EPOCH += 1


# --------------------------------------------------------------------------
# Ops
# --------------------------------------------------------------------------

def bitmap_binary(a, b, op: str = "and", impl: Optional[str] = None):
    impl = _resolve(impl)
    record_launch("bitmap_binary")
    if impl == "reference":
        return {"and": _ref.bitset_and_ref, "or": _ref.bitset_or_ref,
                "andnot": _ref.bitset_andnot_ref}[op](a, b)
    return _bitset.bitset_binary(a, b, op=op,
                                 interpret=(impl == "interpret"))


def bitmap_intersect(stack, impl: Optional[str] = None):
    impl = _resolve(impl)
    record_launch("bitmap_intersect")
    if impl == "reference":
        bm = _ref.bitmap_intersect_ref(stack)
        return bm, _ref.popcount_ref(bm)
    return _bitset.bitmap_intersect(stack, interpret=(impl == "interpret"))


def bitmap_intersect_batched(stack, impl: Optional[str] = None):
    """Wave-stacked AND-reduce [S, K, W] → (bitmaps [S, W], counts [S])."""
    impl = _resolve(impl)
    record_launch("bitmap_intersect_batched")
    if impl == "reference":
        return _ref.bitmap_intersect_batched_ref(stack)
    return _bitset.bitmap_intersect_batched(stack,
                                            interpret=(impl == "interpret"))


def compact(mask, impl: Optional[str] = None):
    impl = _resolve(impl)
    record_launch("compact")
    if impl == "reference":
        return _ref.compact_ref(mask)
    return _compact.compact(mask, interpret=(impl == "interpret"))


def compact_batched(masks, impl: Optional[str] = None):
    """Wave-stacked compaction [S, N] → (indices [S, N], counts [S])."""
    impl = _resolve(impl)
    record_launch("compact_batched")
    if impl == "reference":
        return _ref.compact_batched_ref(masks)
    return _compact.compact_batched(masks, interpret=(impl == "interpret"))


def segment_agg(group_ids, values, num_groups: int,
                impl: Optional[str] = None):
    impl = _resolve(impl)
    record_launch("segment_agg")
    if impl == "reference":
        return _ref.segment_agg_ref(group_ids, values, num_groups)
    return _seg.segment_agg(group_ids, values, num_groups,
                            interpret=(impl == "interpret"))


def refine_tracks(pts, rows, cov, num_docs: int, impl: Optional[str] = None,
                  with_first_hits: bool = False,
                  with_analytics: bool = False):
    """Exact point-in-cover × time-window refine over one shard's packed
    ragged track → per-doc hit mask [num_docs] bool (see kernels.refine).
    ``with_first_hits`` adds the per-(constraint × doc) first-hit uint32
    (hi, lo) word tables the ordered-query edge compare consumes;
    ``with_analytics`` the full (first, last, count) reduction family —
    same fused pass, still one launch."""
    impl = _resolve(impl)
    record_launch("refine_tracks")
    if impl == "reference":
        return _ref.refine_tracks_ref(pts, rows, cov, num_docs=num_docs,
                                      with_first_hits=with_first_hits,
                                      with_analytics=with_analytics)
    return _refine.refine_tracks(pts, rows, cov, num_docs,
                                 interpret=(impl == "interpret"),
                                 with_first_hits=with_first_hits,
                                 with_analytics=with_analytics)


def refine_tracks_batched(pts, rows, cov, num_docs: int,
                          impl: Optional[str] = None,
                          with_first_hits: bool = False,
                          with_analytics: bool = False):
    """Wave-stacked refine [S, 4, P] × [C, 8, R] → hit masks
    [S, num_docs] bool — one launch per wave of shards
    (+ first-hit word tables [S, C, num_docs] × 2 under
    ``with_first_hits``; + last-hit word tables and the int32 hit-count
    table under ``with_analytics``)."""
    impl = _resolve(impl)
    record_launch("refine_tracks_batched")
    if impl == "reference":
        return _ref.refine_tracks_batched_ref(
            pts, rows, cov, num_docs=num_docs,
            with_first_hits=with_first_hits,
            with_analytics=with_analytics)
    return _refine.refine_tracks_batched(pts, rows, cov, num_docs,
                                         interpret=(impl == "interpret"),
                                         with_first_hits=with_first_hits,
                                         with_analytics=with_analytics)


def refine_tracks_multi(pts, rows, cov, num_docs: int,
                        impl: Optional[str] = None,
                        with_first_hits: bool = False,
                        with_analytics: bool = False):
    """Query-axis refine: Q coalesced queries' constraint tables
    [Q, C, 8, R] against one wave's shared track buffers [S, 4, P] →
    hit masks [Q, S, num_docs] bool in ONE launch (+ first-hit word
    tables [Q, S, C, num_docs] × 2 under ``with_first_hits``; the full
    reduction family under ``with_analytics``)."""
    impl = _resolve(impl)
    record_launch("refine_tracks_multi")
    if impl == "reference":
        return _ref.refine_tracks_multi_ref(
            pts, rows, cov, num_docs=num_docs,
            with_first_hits=with_first_hits,
            with_analytics=with_analytics)
    return _refine.refine_tracks_multi(pts, rows, cov, num_docs,
                                       interpret=(impl == "interpret"),
                                       with_first_hits=with_first_hits,
                                       with_analytics=with_analytics)


def run_wave_fused(probe_stack, ns, pts=None, rows=None, cov=None,
                   codes=None, vals=(), *, num_docs: int, edges=(),
                   min_counts=(), dwells=(), total_groups: int = 0,
                   impl: Optional[str] = None, minmax=()):
    """Whole-wave fused pipeline (probe → refine → compact → segment-agg)
    in ONE dispatch — see ``kernels.fused``.  Counts as a single launch:
    the fused path's ⌈shards/wave⌉ *total*-dispatch contract hangs off
    this counter.  Each stage lowers to its Pallas kernel under
    ``pallas``/``interpret`` and to the jnp oracle under ``reference``.
    ``minmax`` flags value slots that also reduce per-group min/max in the
    same dispatch; ``min_counts``/``dwells`` apply the per-constraint
    count/dwell reduction verdicts inside the refine stage — same single
    dispatch."""
    impl = _resolve(impl)
    record_launch("run_wave_fused")
    return _fused.run_wave_fused(probe_stack, ns, pts, rows, cov, codes,
                                 vals, num_docs=num_docs, edges=edges,
                                 min_counts=min_counts, dwells=dwells,
                                 total_groups=total_groups, impl=impl,
                                 minmax=minmax)


def run_wave_fused_multi(probe_stacks, ns, pts=None, rows=None, cov=None, *,
                         num_docs: int, edges_multi=(),
                         min_counts_multi=(), dwells_multi=(),
                         impl: Optional[str] = None):
    """Multi-query fused wave (probe → refine → compact) for Q coalesced
    queries against ONE resident wave of shards, in ONE dispatch.  The
    query axis leads every per-query table (``probe_stacks`` [Q, S, K, W],
    ``cov`` [Q, C, 8, R]); track buffers (``pts``/``rows``) are shared.
    ``min_counts_multi``/``dwells_multi`` carry per-query reduction tuples
    (aligned with ``edges_multi``).  Counts as a single launch: Q
    coalesced queries still cost ⌈shards/wave⌉ **total** dispatches — the
    serve-layer contract."""
    impl = _resolve(impl)
    record_launch("run_wave_fused_multi")
    return _fused.run_wave_fused_multi(probe_stacks, ns, pts, rows, cov,
                                       num_docs=num_docs,
                                       edges_multi=edges_multi,
                                       min_counts_multi=min_counts_multi,
                                       dwells_multi=dwells_multi,
                                       impl=impl)


def segment_hll(group_ids, regs, num_groups: int,
                impl: Optional[str] = None):
    """Per-group HyperLogLog register max: group_ids [N] int32 (< 0
    masked out) × regs [N, M] uint8 register rows → [num_groups, M]
    maxed register planes.  Register max is the HLL merge operation —
    commutative and idempotent, so the lowering is partition-invariant by
    construction.  Segment-max is a pure-jnp lowering under every
    ``impl`` (like ``postings_bitmap``) but still counts one launch."""
    _resolve(impl)                    # validate; lowering is impl-agnostic
    record_launch("segment_hll")
    return _fused.segment_hll(group_ids, regs, num_groups)


def postings_bitmap(ids, t_min, t_max, t0, t1, n_docs: int,
                    impl: Optional[str] = None):
    """Spacetime postings OR + track-span prune on device (the tail of
    ``SpaceTimeIndex.lookup``).  Scatter-OR is a pure-jnp lowering under
    every ``impl`` — there is no Pallas scatter kernel — but it still
    counts one launch."""
    _resolve(impl)                    # validate; lowering is impl-agnostic
    record_launch("postings_bitmap")
    return _fused.postings_bitmap(ids, t_min, t_max, t0, t1, n_docs)


def merge_partials(cnt, s, s2, mn, mx, msk, mesh=None,
                   impl: Optional[str] = None):
    """Cross-partition combine of aligned segment-aggregate state stacks
    (counts/sums/sum-squares accumulate in states order, min/max planes
    element-wise, presence masks OR) under ``shard_map`` over the mesh's
    ``"part"`` axis.  Like the Mixer's host merge this always runs in
    float64, so the lowering is impl-agnostic — but it still counts one
    launch: the partitioned launch contract is sum over partitions of
    ceil(shards_p/wave) fused dispatches plus exactly one merge combine
    per aggregated query."""
    _resolve(impl)                    # validate; lowering is impl-agnostic
    record_launch("merge_partials")
    return _merge.merge_partials(cnt, s, s2, mn, mx, msk, mesh=mesh)


def flash_attention(q, k, v, *, causal: bool = True, window=None,
                    softcap=None, scale=None, impl: Optional[str] = None,
                    **block_kw):
    impl = _resolve(impl)
    record_launch("flash_attention")
    if impl == "reference":
        return _ref.flash_attention_ref(q, k, v, causal=causal,
                                        window=window, softcap=softcap,
                                        scale=scale)
    return _fa.flash_attention(q, k, v, causal=causal, window=window,
                               softcap=softcap, scale=scale,
                               interpret=(impl == "interpret"), **block_kw)


def ssm_scan(a, bx, impl: Optional[str] = None, **kw):
    impl = _resolve(impl)
    record_launch("ssm_scan")
    if impl == "reference":
        return _ref.ssm_scan_ref(a, bx)
    return _ssm.ssm_scan(a, bx, interpret=(impl == "interpret"), **kw)
