"""Pluggable execution backends for the query hot path.

The paper's time-to-first-result hinges on three per-shard primitives:

  * **bitmap intersection** — AND-reduce the index-probe postings
    (``probe_shard``),
  * **mask compaction** — positions of selected rows after the residual
    filter (``apply_filter``),
  * **group-by partial aggregation** — (count, sum, sumsq) per group code
    (``aggregate_produce``),

An :class:`ExecBackend` supplies all three behind one seam so the logical
plan stays engine- and backend-agnostic:

  * ``numpy``  — the host reference (current behavior, the parity oracle),
  * ``jax``    — dispatches through :mod:`repro.kernels.ops`, which selects
    the Pallas kernels on TPU (``pallas``), the interpreted kernel bodies
    (``interpret``), or the pure-jnp oracle (``reference``) via
    ``REPRO_KERNEL_IMPL``.

Select a backend per engine (``AdHocEngine(backend="jax")``), per session
(``Session(backend="jax")``), or globally with ``REPRO_EXEC_BACKEND``.
Bit/integer primitives are exact, so selection is byte-identical across
backends; the jax ``reference`` aggregation path runs the segment kernel
math at float64 (``enable_x64``) and accumulates in row order — bit-equal
to the numpy oracle's ``bincount`` — while ``pallas``/``interpret`` keep
the MXU's float32, the TPU deployment precision.

**Batched multi-shard ops.**  The engines dispatch *waves* of shards
(``repro.exec.batched``) through ``probe_shards`` / ``compact_masks`` /
``segment_aggregate_batched``: the jax backend pads the wave's ragged
per-shard shapes into one stacked buffer and runs **one** kernel launch
per wave (``bitmap_intersect_batched`` / ``compact_batched`` / offset
group codes into one ``segment_agg``), while the numpy base-class
implementations loop shard-by-shard over the single-shard primitives —
the loop-over-shards oracle the batched path must match byte-for-byte.

**Ragged track refine.**  The exact Tesseract pass (point-in-cover ×
time-window over ragged ``(values, row_splits)`` tracks) is the fourth
op pair on the seam: ``refine_tracks`` / ``refine_tracks_batched`` emit
the per-doc hit mask that feeds ``compact_masks``.  The numpy base class
is the vectorized host oracle (:mod:`repro.exec.refine`); the jax backend
launches the Pallas ``refine`` kernel over packed integer point buffers —
one fused launch per wave — so the last big host stage of the Tesseract
hot loop runs behind the seam too.  Ordered queries hand the op an
``edges`` DAG: the same launch min-reduces per-(doc × constraint)
**first-hit** timestamps and each edge is a strict first-hit compare
applied device-side before the mask comes back — byte-parity extends to
the first-hit table itself (``with_first_hits``).

**Fused wave dispatch.**  ``run_wave_fused`` collapses a whole wave's
probe → refine → compact → segment-agg chain into ONE device dispatch
(:mod:`repro.kernels.fused`): the numpy base class is the loop-over-stages
oracle, the jax override one jitted multi-stage pipeline with zero host
syncs between stages.  On the fused path the launch contract tightens from
⌈shards/wave⌉ launches *per primitive* to ⌈shards/wave⌉ **total** fused
dispatches per query.  Engines fall back to the per-primitive path when
the op declines or is ineligible: ``REPRO_EXEC_FUSED=0``, a backend
without ``batched_dispatch``, a residual filter (needs gathered columns
host-side), more than one refine spec, a refine spec with zero or more
than 30 constraints, a shard without a packed track, or a wave whose
tracks are all empty.  The fused *aggregation* stage additionally requires
a single dense int-key group-by with only count/sum/avg/std_dev over dense
numeric columns (``exec.batched.fused_agg_plan``) — other aggregate plans
still run the fused selection stages and aggregate host-side from the
gathered columns.  ``prefetch_wave`` stages wave *k+1*'s stacked buffers
(refine point stacks, offset group codes, value stacks) through the
``DeviceCache`` keyed entries while wave *k* computes — the async-prefetch
half of the paper's pipelined evaluation.

The jax backend additionally keeps stable per-FDb buffers (column values,
valid-doc bitmaps, spacetime postings, packed track points) device-resident
across queries — ``prime_fdb`` / :mod:`repro.exec.device_cache` — so the
selective column read (``gather_columns``) pulls from resident buffers
instead of re-uploading columns per query; repeated columns use a
device-side CSR spans-concatenate gather.

Future scaling PRs (sharded device meshes, async prefetch, GPU lowering)
plug in here: ``register_backend`` a new implementation and every engine
picks it up.
"""
from __future__ import annotations

import contextlib
import os
import threading
import weakref
from typing import Callable, Dict, List, Optional, Sequence, Tuple, Union

import numpy as np

from ..fdb.index import (bitmap_from_ids, bitmap_stack, ids_from_bitmap,
                         mask_from_bitmap)
from ..spans import span
from .device_cache import device_form, host_form
from .refine import (FIRST_HIT_NONE, LAST_HIT_NONE, pack_constraints,
                     pack_constraints_multi, pack_track_points,
                     reduction_verdict, refine_tracks_host)


def _has_red(min_counts, dwells) -> bool:
    """True when the per-constraint reductions change the verdict — a
    non-default min count or any dwell predicate."""
    return ((min_counts is not None
             and any(int(k) != 1 for k in min_counts))
            or (dwells is not None and any(d is not None for d in dwells)))


def _refine_grid_meta(n_queries: int, pts_stack,
                      num_docs: int) -> Dict[str, int]:
    """``dispatch`` span meta of a fused wave's refine stage: the grid
    steps its banded walk takes (``refine_steps``) and those of the dense
    doc-block × point-block grid (``refine_dense``); none without one."""
    if pts_stack is None:
        return {}
    from ..kernels.refine import grid_steps
    s, _, p = pts_stack.shape
    steps, dense = grid_steps(n_queries, s, p, num_docs)
    return {"refine_steps": steps, "refine_dense": dense}


def _segment_minmax_host(codes: np.ndarray, values: np.ndarray,
                         num_groups: int) -> Tuple[np.ndarray, np.ndarray]:
    """Host per-group (min, max) float64 — the oracle for the fused agg
    tail's min/max slots.  Groups with no rows keep ±inf fills (dropped by
    the ``count > 0`` keep-filter downstream)."""
    codes = np.asarray(codes, dtype=np.int64)
    keep = codes >= 0
    if not keep.all():
        codes, values = codes[keep], np.asarray(values)[keep]
    v = np.asarray(values, dtype=np.float64)
    mn = np.full(num_groups, np.inf)
    mx = np.full(num_groups, -np.inf)
    np.minimum.at(mn, codes, v)
    np.maximum.at(mx, codes, v)
    return mn, mx

__all__ = ["ExecBackend", "NumpyBackend", "JaxBackend", "register_backend",
           "backend_names", "get_backend", "as_backend"]


class ExecBackend:
    """Interface every execution backend implements.

    All methods take and return **host** numpy arrays; a device-resident
    backend owns its own transfers (and may cache device buffers keyed by
    array identity).  Contracts:

      * ``intersect_bitmaps(full, bitmaps)`` → uint32 word bitmap: AND of
        ``full`` (the shard's valid-doc mask) and every probe bitmap.
      * ``select_ids(bitmap, n)`` → ascending int64 doc ids of set bits.
      * ``compact_mask(mask)`` → ascending int64 positions of True entries.
      * ``segment_aggregate(codes, values, num_groups)`` →
        ``(count[G] int64, sum[G] float64, sumsq[G] float64)`` with rows
        whose code is negative ignored.
    """

    name: str = "abstract"
    #: True when the batched ops amortize real kernel launches; engines
    #: then default to multi-shard waves.  Loop-over-shards backends keep
    #: a default wave of 1 so per-shard thread parallelism is preserved
    #: (an explicit wave=/$REPRO_EXEC_WAVE still forces wider waves).
    batched_dispatch: bool = False

    def intersect_bitmaps(self, full: np.ndarray,
                          bitmaps: Sequence[np.ndarray]) -> np.ndarray:
        raise NotImplementedError

    def select_ids(self, bitmap: np.ndarray, n: int) -> np.ndarray:
        raise NotImplementedError

    def compact_mask(self, mask: np.ndarray) -> np.ndarray:
        raise NotImplementedError

    def segment_aggregate(self, codes: np.ndarray, values: np.ndarray,
                          num_groups: int
                          ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        raise NotImplementedError

    # -------------------------------------------------- batched (per wave)
    # Base-class implementations loop shard-by-shard over the single-shard
    # primitives: that *is* the oracle the batched overrides must match
    # byte-for-byte (ragged shard sizes, empty shards included).

    def probe_shards(self, fulls: Sequence[np.ndarray],
                     probes: Sequence[Sequence[np.ndarray]]
                     ) -> List[np.ndarray]:
        """Per-shard AND of valid-doc bitmap and probe bitmaps, one wave."""
        return [self.intersect_bitmaps(f, ps)
                for f, ps in zip(fulls, probes)]

    def compact_masks(self, masks: Sequence[np.ndarray]) -> List[np.ndarray]:
        """Per-shard positions of True entries, one wave."""
        return [self.compact_mask(m) for m in masks]

    def segment_aggregate_batched(
            self, codes: Sequence[np.ndarray], values: Sequence[np.ndarray],
            num_groups: Sequence[int]
            ) -> List[Tuple[np.ndarray, np.ndarray, np.ndarray]]:
        """Per-shard (count, sum, sumsq) over shard-local group codes."""
        return [self.segment_aggregate(c, v, g)
                for c, v, g in zip(codes, values, num_groups)]

    # ------------------------------------------------------- track refine
    def refine_tracks(self, batch, path: str, constraints,
                      candidates: Optional[np.ndarray] = None,
                      edges=(), with_first_hits: bool = False,
                      min_counts=None, dwells=None,
                      with_analytics: bool = False):
        """Exact Tesseract refine over the ragged track at ``path``:
        per-doc bool mask [batch.n], True iff for *every* ``(region, t0,
        t1)`` constraint some track point lies inside the region's cover
        during the window.  ``candidates`` (bool mask) restricts the docs
        considered — the result equals ``full_refine & candidates`` bit
        for bit, and feeds ``compact_masks`` directly.

        ``edges`` is the ordering DAG over the constraint list: edge
        ``(i, j)`` additionally requires the doc's **first hit** of
        constraint ``i`` (min packed timestamp among its satisfying
        points) to be strictly before its first hit of ``j`` — equal
        first hits do not count as before.  ``with_first_hits`` returns
        ``(mask, table)`` with ``table`` the uint64 [batch.n, C]
        first-hit table (``exec.refine.FIRST_HIT_NONE`` where a
        constraint never hits) — parity-checked byte-for-byte across
        backends.

        ``min_counts``/``dwells`` generalize the per-constraint verdict
        (≥ k hits; last − first ≥ d seconds — see
        ``exec.refine.refine_tracks_host``); ``with_analytics`` returns
        ``(mask, first, last, count)`` — the full reduction-table family,
        parity-checked across backends.  Host reference: vectorized
        numpy over the shard's CSR columns."""
        lat = batch[path + ".lat"]
        lng = batch[path + ".lng"]
        tt = batch[path + ".t"]
        return refine_tracks_host(lat.values, lng.values, tt.values,
                                  lat.row_splits, batch.n,
                                  list(constraints), candidates,
                                  edges=tuple(edges),
                                  with_first_hits=with_first_hits,
                                  min_counts=min_counts, dwells=dwells,
                                  with_analytics=with_analytics)

    def refine_tracks_batched(self, batches, path: str, constraints,
                              candidates_list=None, edges=(),
                              with_first_hits: bool = False,
                              min_counts=None, dwells=None,
                              with_analytics: bool = False):
        """Per-shard refine masks for one wave — the loop-over-shards
        oracle the batched overrides must match byte-for-byte.  Returns
        the mask list, ``(masks, tables)`` under ``with_first_hits``, or
        ``(masks, firsts, lasts, counts)`` under ``with_analytics``."""
        batches = list(batches)
        if candidates_list is None:
            candidates_list = [None] * len(batches)
        outs = [self.refine_tracks(b, path, constraints, cand, edges=edges,
                                   with_first_hits=with_first_hits,
                                   min_counts=min_counts, dwells=dwells,
                                   with_analytics=with_analytics)
                for b, cand in zip(batches, candidates_list)]
        if with_analytics:
            return ([o[0] for o in outs], [o[1] for o in outs],
                    [o[2] for o in outs], [o[3] for o in outs])
        if with_first_hits:
            return [m for m, _ in outs], [t for _, t in outs]
        return outs

    # -------------------------------------------- multi-query (coalesced)
    # The query-serving layer coalesces Q compatible in-flight queries
    # against ONE resident wave of shards.  Base-class implementations
    # loop query-by-query over the single-query ops — the oracle the
    # stacked overrides must match byte-for-byte per query.

    def probe_shards_multi(self, fulls: Sequence[np.ndarray],
                           probes_multi) -> List[List[np.ndarray]]:
        """Per-query wave probes: ``probes_multi[q][s]`` is query q's
        probe-bitmap list for shard s.  Returns one ``probe_shards``
        result list per query."""
        return [self.probe_shards(fulls, probes) for probes in probes_multi]

    def refine_tracks_multi(self, batches, path: str, constraints_list,
                            candidates_lists=None, edges_list=None,
                            with_first_hits: bool = False,
                            min_counts_list=None, dwells_list=None):
        """Per-query wave refine: Q queries' constraint lists against one
        wave's shared tracks.  Returns one ``refine_tracks_batched``
        result per query (mask list, or ``(masks, tables)`` under
        ``with_first_hits``).  ``min_counts_list``/``dwells_list`` carry
        each query's per-constraint reductions (or ``None``)."""
        batches = list(batches)
        n_q = len(constraints_list)
        if candidates_lists is None:
            candidates_lists = [None] * n_q
        if edges_list is None:
            edges_list = [()] * n_q
        if min_counts_list is None:
            min_counts_list = [None] * n_q
        if dwells_list is None:
            dwells_list = [None] * n_q
        return [self.refine_tracks_batched(batches, path, cons, cands,
                                           edges=edges,
                                           with_first_hits=with_first_hits,
                                           min_counts=mc, dwells=dw)
                for cons, cands, edges, mc, dw in zip(
                    constraints_list, candidates_lists, edges_list,
                    min_counts_list, dwells_list)]

    def run_wave_fused_multi(self, shards, probes_multi, refines,
                             prefetch_shards=None):
        """Q coalesced *selection* queries (no aggregation tail) through
        one wave: returns a per-query list of ``(n_cands, ids_list)``
        pairs, or ``None`` to decline (the server then runs each query
        through the single-query path).  Base implementation is the
        loop-over-queries oracle the stacked override must match
        byte-for-byte per query."""
        out = []
        for probes, rf in zip(probes_multi, refines):
            r = self.run_wave_fused(shards, probes, refine=rf, agg=None)
            if r is None:
                return None
            n_cands, ids_list, _seg = r
            out.append((n_cands, ids_list))
        return out

    # -------------------------------------------------- sketch aggregation
    def segment_hll(self, codes: np.ndarray, reg_idx: np.ndarray,
                    ranks: np.ndarray, num_groups: int,
                    num_regs: int) -> np.ndarray:
        """Grouped HyperLogLog register build: per-row ``(group code,
        register index, rank)`` triples → uint8 ``[num_groups, num_regs]``
        per-group register planes.  The reduce is a plain max with
        identity 0 (= empty register) — commutative and idempotent, so
        the result is independent of row order and of how rows are split
        across shards or partitions (the ``merge_partials`` contract for
        sketches).  Rows with negative codes are ignored.  Host
        reference: one ``np.maximum.at`` scatter."""
        regs = np.zeros((num_groups, num_regs), dtype=np.uint8)
        codes = np.asarray(codes, dtype=np.int64)
        keep = codes >= 0
        if not keep.all():
            codes = codes[keep]
            reg_idx = np.asarray(reg_idx, dtype=np.int64)[keep]
            ranks = np.asarray(ranks, dtype=np.uint8)[keep]
        np.maximum.at(regs, (codes, np.asarray(reg_idx, dtype=np.int64)),
                      np.asarray(ranks, dtype=np.uint8))
        return regs

    # -------------------------------------------------- fused wave pipeline
    def postings_bitmap(self, ids: np.ndarray, t_min: np.ndarray,
                        t_max: np.ndarray, t0: float, t1: float,
                        n_docs: int) -> np.ndarray:
        """OR doc ``ids`` into a word bitmap and prune docs whose track
        span ``[t_min, t_max]`` misses ``[t0, t1]`` — the tail of
        ``SpaceTimeIndex.lookup`` behind the seam (host reference)."""
        bm = bitmap_from_ids(np.asarray(ids, dtype=np.int64), n_docs)
        overlap = (t_min <= t1) & (t_max >= t0)
        return bm & bitmap_from_ids(
            np.nonzero(overlap)[0].astype(np.int64), n_docs)

    def run_wave_fused(self, shards, probes, refine=None, agg=None,
                       prefetch_shards=None, query_id=None):
        """Whole-wave probe → refine → compact → (segment-agg) as one
        logical dispatch.  Returns ``(n_cands, ids_list, seg)``: per-shard
        pre-refine candidate counts, selected doc ids, and — when ``agg``
        (an ``exec.batched.FusedAggPlan``) is given — per-shard
        ``(group_keys, [(count, sum, sumsq) per value slot])`` partials
        over each shard's full group space.  May return ``None`` to
        decline, in which case the engine runs the per-primitive path.

        This base implementation is the loop-over-stages oracle the fused
        overrides must match byte-for-byte; ``prefetch_shards`` is a hint
        only (no-op on host backends), and ``query_id`` the number an
        override's spans carry (``repro.spans``)."""
        del query_id
        shards = list(shards)
        if not shards:
            return [], [], ([] if agg is not None else None)
        bms = self.probe_shards([sh.all_bitmap() for sh in shards], probes)
        masks = [mask_from_bitmap(bm, sh.n) for bm, sh in zip(bms, shards)]
        n_cands = [int(m.sum()) for m in masks]
        if refine is not None:
            masks = self.refine_tracks_batched(
                [sh.batch for sh in shards], refine.path,
                refine.constraints, masks, edges=refine.edges,
                min_counts=getattr(refine, "min_counts", None),
                dwells=getattr(refine, "dwells", None))
        ids_list = self.compact_masks(masks)
        seg = None
        if agg is not None:
            mm = tuple(getattr(agg, "minmax", ()) or ())
            seg = []
            for sh, ids in zip(shards, ids_list):
                uniq, codes, g = agg.factorize(sh, backend=self)
                if g == 0:
                    seg.append((uniq, []))
                    continue
                csel = codes[ids]
                slots = []
                for k, vp in enumerate(agg.value_paths or [None]):
                    vals = (sh.batch[vp].values[ids] if vp is not None
                            else np.zeros(ids.size))
                    slot = self.segment_aggregate(csel, vals, g)
                    if k < len(mm) and mm[k]:
                        slot = (*slot,
                                *_segment_minmax_host(csel, vals, g))
                    slots.append(slot)
                seg.append((uniq, slots))
        return n_cands, ids_list, seg

    # ------------------------------------------------------ partition layer
    def partition_context(self, part: int, num_parts: int):
        """Context manager the wave scheduler enters around one
        partition's dispatches.  Host backends have nothing to place —
        the partition layer degenerates to running the partitions'
        waves one after another on the same loop."""
        del part, num_parts
        return contextlib.nullcontext()

    def merge_partials(self, states, minmax=(), parts=None):
        """Combine per-shard segment-aggregate states across partitions
        — the partitioned Mixer combine, and the loop-over-partitions
        **oracle** mesh backends must match.

        ``states`` is a flat list of ``(uniq_keys, slots)`` pairs in
        global shard order (partitions are contiguous slices, so
        flattening per-partition results in partition order *is* shard
        order); each slot is ``(count, sum, sum_sq[, min, max])`` vectors
        over that state's own key space.  Returns ``(union_keys,
        merged_slots)`` over the sorted union key space: counts, sums and
        sums-of-squares accumulate **sequentially in states order** with
        absent groups contributing the additive identity 0 (bit-equal to
        the P=1 sequential merge), min/max planes reduce element-wise
        against ±inf, and the per-group presence masks OR (a group is
        live iff some state selected a row for it, which is exactly
        ``merged count > 0`` — counts are non-negative).

        ``minmax`` flags which value slots carry min/max planes;
        ``parts`` (per-partition state counts) is layout metadata for
        mesh-sharding backends — the host oracle just loops in order.
        """
        del parts
        live = [(np.asarray(k), list(slots)) for k, slots in states
                if len(k) and slots]
        if not live:
            return np.zeros(0, np.int64), []
        union = np.unique(np.concatenate([k for k, _ in live]))
        n_slots = max(len(slots) for _, slots in live)
        mm = tuple(minmax)
        mm = mm + (False,) * (n_slots - len(mm))
        g = union.size
        cnt = [np.zeros(g, np.int64) for _ in range(n_slots)]
        s = [np.zeros(g, np.float64) for _ in range(n_slots)]
        s2 = [np.zeros(g, np.float64) for _ in range(n_slots)]
        mn = [np.full(g, np.inf) for _ in range(n_slots)]
        mx = [np.full(g, -np.inf) for _ in range(n_slots)]
        mask = np.zeros(g, bool)
        for keys, slots in live:               # in order over states
            idx = np.searchsorted(union, keys)
            for k, st in enumerate(slots):
                # densify onto the union space, then accumulate — the
                # identical arithmetic a stacked device combine performs
                row_c = np.zeros(g, np.int64)
                row_s = np.zeros(g, np.float64)
                row_s2 = np.zeros(g, np.float64)
                row_c[idx] = np.asarray(st[0], np.int64)
                row_s[idx] = np.asarray(st[1], np.float64)
                row_s2[idx] = np.asarray(st[2], np.float64)
                cnt[k] = cnt[k] + row_c
                s[k] = s[k] + row_s
                s2[k] = s2[k] + row_s2
                if len(st) >= 5:
                    row_mn = np.full(g, np.inf)
                    row_mx = np.full(g, -np.inf)
                    row_mn[idx] = np.asarray(st[3], np.float64)
                    row_mx[idx] = np.asarray(st[4], np.float64)
                    mn[k] = np.minimum(mn[k], row_mn)
                    mx[k] = np.maximum(mx[k], row_mx)
            present = np.zeros(g, bool)
            present[idx] = np.asarray(slots[0][0]) > 0
            mask |= present
        merged = []
        for k in range(n_slots):
            slot = (cnt[k], s[k], s2[k])
            if mm[k]:
                slot = (*slot, mn[k], mx[k])
            merged.append(slot)
        return union, merged

    def prefetch_wave(self, shards, refine=None, agg=None) -> None:
        """Stage a wave's stacked buffers ahead of compute (no-op on host
        backends — there is nothing to upload)."""

    def gather_columns(self, batch, paths: Sequence[str],
                       ids: np.ndarray):
        """Selective column read of ``ids`` rows (host reference)."""
        return batch.select_paths(list(paths)).gather(ids)

    def prime_fdb(self, db) -> int:
        """Make ``db``'s stable buffers backend-resident (no-op on host)."""
        return 0

    def __repr__(self):
        return f"<ExecBackend {self.name}>"


# --------------------------------------------------------------------------
# numpy — host reference implementation (the oracle)
# --------------------------------------------------------------------------

class NumpyBackend(ExecBackend):
    name = "numpy"

    def intersect_bitmaps(self, full, bitmaps):
        bm = full
        for b in bitmaps:
            bm = bm & b
        return bm

    def select_ids(self, bitmap, n):
        return ids_from_bitmap(bitmap, n)

    def compact_mask(self, mask):
        return np.nonzero(mask)[0].astype(np.int64)

    def segment_aggregate(self, codes, values, num_groups):
        codes = np.asarray(codes, dtype=np.int64)
        keep = codes >= 0
        if not keep.all():
            codes, values = codes[keep], np.asarray(values)[keep]
        v = np.asarray(values, dtype=np.float64)
        cnt = np.bincount(codes, minlength=num_groups)[:num_groups]
        s = np.bincount(codes, weights=v, minlength=num_groups)[:num_groups]
        s2 = np.bincount(codes, weights=v * v,
                         minlength=num_groups)[:num_groups]
        return cnt.astype(np.int64), s, s2


# --------------------------------------------------------------------------
# jax — kernels.ops dispatch (pallas on TPU, interpret/reference elsewhere)
# --------------------------------------------------------------------------

class JaxBackend(ExecBackend):
    """Routes the hot loop through :mod:`repro.kernels.ops`.

    ``impl`` pins the kernel implementation (``pallas`` / ``interpret`` /
    ``reference``); default defers to ``ops.default_impl()`` per call, so
    ``REPRO_KERNEL_IMPL`` keeps working.
    """

    name = "jax"
    batched_dispatch = True

    def __init__(self, impl: Optional[str] = None):
        import jax  # container ships the jax_pallas toolchain
        import jax.numpy as jnp
        from ..kernels import ops
        from .device_cache import DeviceCache
        self._jax, self._jnp, self._ops = jax, jnp, ops
        self.impl = impl
        self.device_cache = DeviceCache(jax)
        # weak: a collected FDb drops out, so a new FDb reusing the same
        # address still primes, and a finalizer evicts its buffers.
        # Buffers are refcounted across FDbs — StreamingFDb snapshots
        # share flushed Shards (hence arrays), so an id is only evicted
        # once every FDb that primed it is gone.
        self._primed_fdbs: weakref.WeakSet = weakref.WeakSet()
        self._primed_refs: Dict[int, int] = {}
        # per-FDb primed key sets (shared with that FDb's finalizer, so
        # eager retirement can shrink them) + the latest primed snapshot
        # per source name for streaming generation turnover
        self._primed_keysets: "weakref.WeakKeyDictionary" = \
            weakref.WeakKeyDictionary()
        self._latest_primed: Dict[str, "weakref.ref"] = {}
        # id(track lat values) → (lat values pin, pts [4, P], rows [P]):
        # the packed integer form the refine kernel consumes, computed
        # once per shard at prime time (see exec.refine.pack_track_points)
        self._track_packs: Dict[int, Tuple[np.ndarray, np.ndarray,
                                           np.ndarray]] = {}
        # The query server opens/closes FDbs from many threads at once:
        # priming, finalizer release, and the pack cache share one lock so
        # refcounts stay consistent and eviction can never interleave with
        # a prime of the same buffers.  Reentrant because prime_fdb calls
        # _track_pack while holding it.
        self._prime_lock = threading.RLock()

    def _impl(self) -> str:
        return self.impl or self._ops.default_impl()

    def intersect_bitmaps(self, full, bitmaps):
        if not bitmaps:
            return full
        stack = bitmap_stack([full, *bitmaps])
        bm, _count = self._ops.bitmap_intersect(self._jnp.asarray(stack),
                                                impl=self._impl())
        return np.asarray(bm, dtype=np.uint32)

    def select_ids(self, bitmap, n):
        return self.compact_mask(mask_from_bitmap(bitmap, n))

    def compact_mask(self, mask):
        mask = np.asarray(mask, dtype=bool)
        idx, count = self._ops.compact(self._jnp.asarray(mask),
                                       impl=self._impl())
        return np.asarray(idx[: int(count)], dtype=np.int64)

    def _segment_dispatch(self, codes32: np.ndarray, values: np.ndarray,
                          num_groups: int):
        """One segment_agg launch → host (count int64, sum f64, sumsq f64)."""
        impl = self._impl()
        if impl == "reference":
            # float64 + row-order accumulation: bit-equal to the numpy
            # oracle, and the same segment math the kernel implements.
            with self._jax.enable_x64(True):
                cnt, s, s2 = self._ops.segment_agg(
                    self._jnp.asarray(codes32),
                    self._jnp.asarray(np.asarray(values, dtype=np.float64)),
                    num_groups, impl=impl)
                cnt, s, s2 = (np.asarray(cnt), np.asarray(s, np.float64),
                              np.asarray(s2, np.float64))
        else:
            cnt, s, s2 = self._ops.segment_agg(
                self._jnp.asarray(codes32),
                self._jnp.asarray(np.asarray(values, dtype=np.float32)),
                num_groups, impl=impl)
            cnt, s, s2 = (np.asarray(cnt), np.asarray(s, np.float64),
                          np.asarray(s2, np.float64))
        return np.rint(cnt).astype(np.int64), s, s2

    def segment_aggregate(self, codes, values, num_groups):
        codes32 = np.ascontiguousarray(codes, dtype=np.int32)
        return self._segment_dispatch(codes32, values, num_groups)

    # ------------------------------------------------------------- batched
    def probe_shards(self, fulls, probes):
        """One ``bitmap_intersect_batched`` launch for the whole wave.

        Ragged per-shard word counts are zero-padded to the wave max —
        sound because row 0 of every stack is the shard's valid-doc mask,
        which is zero in the pad region.  Shards with fewer probes than
        the wave max are padded with copies of their valid-doc mask (an
        AND no-op).
        """
        fulls = list(fulls)
        probes = [list(ps) for ps in probes]
        n_shards = len(fulls)
        if n_shards == 0:
            return []
        w = max(f.size for f in fulls)
        if w == 0:                       # a wave of entirely empty shards
            return [f.copy() for f in fulls]
        k = 1 + max(len(ps) for ps in probes)
        stack = np.zeros((n_shards, k, w), dtype=np.uint32)
        for i, (f, ps) in enumerate(zip(fulls, probes)):
            stack[i, 0, :f.size] = f
            for j, b in enumerate(ps):
                stack[i, j + 1, :b.size] = b
            for j in range(len(ps) + 1, k):
                stack[i, j, :f.size] = f
        bms, _counts = self._ops.bitmap_intersect_batched(
            self._jnp.asarray(stack), impl=self._impl())
        bms = np.asarray(bms, dtype=np.uint32)
        return [bms[i, :fulls[i].size].copy() for i in range(n_shards)]

    def probe_shards_multi(self, fulls, probes_multi):
        """Q queries' wave probes in ONE ``bitmap_intersect_batched``
        launch: the query axis is folded into the stacked shard axis
        ([Q·S, K, W]) — the AND-reduce is row-independent, so per-query
        slices are byte-equal to the loop-over-queries oracle."""
        fulls = list(fulls)
        probes_multi = [[list(ps) for ps in probes]
                        for probes in probes_multi]
        n_q, n_shards = len(probes_multi), len(fulls)
        if n_q == 0:
            return []
        if n_shards == 0:
            return [[] for _ in range(n_q)]
        w = max(f.size for f in fulls)
        if w == 0:
            return [[f.copy() for f in fulls] for _ in range(n_q)]
        k = 1 + max(len(ps) for probes in probes_multi for ps in probes)
        stack = np.zeros((n_q * n_shards, k, w), dtype=np.uint32)
        for q, probes in enumerate(probes_multi):
            for i, (f, ps) in enumerate(zip(fulls, probes)):
                row = q * n_shards + i
                stack[row, 0, :f.size] = f
                for j, b in enumerate(ps):
                    stack[row, j + 1, :b.size] = b
                for j in range(len(ps) + 1, k):
                    stack[row, j, :f.size] = f
        bms, _counts = self._ops.bitmap_intersect_batched(
            self._jnp.asarray(stack), impl=self._impl())
        bms = np.asarray(bms, dtype=np.uint32)
        return [[bms[q * n_shards + i, :fulls[i].size].copy()
                 for i in range(n_shards)] for q in range(n_q)]

    def compact_masks(self, masks):
        """One ``compact_batched`` launch for the whole wave (False-pad)."""
        masks = [np.asarray(m, dtype=bool) for m in masks]
        n_shards = len(masks)
        if n_shards == 0:
            return []
        n = max(m.size for m in masks)
        if n == 0:
            return [np.zeros(0, dtype=np.int64) for _ in masks]
        stack = np.zeros((n_shards, n), dtype=bool)
        for i, m in enumerate(masks):
            stack[i, :m.size] = m
        idx, counts = self._ops.compact_batched(self._jnp.asarray(stack),
                                                impl=self._impl())
        idx = np.asarray(idx)
        counts = np.asarray(counts)
        return [idx[i, :int(counts[i])].astype(np.int64)
                for i in range(n_shards)]

    def segment_aggregate_batched(self, codes, values, num_groups):
        """One segment launch per wave: shard-local group codes are offset
        into a disjoint global code space, aggregated together, and split
        back per shard.  Groups stay disjoint and rows keep their order,
        so every per-group accumulation sums the same values in the same
        order as the loop-over-shards oracle — bit-equal results.
        """
        num_groups = [int(g) for g in num_groups]
        total_groups = sum(num_groups)
        if total_groups == 0 or not codes:
            return [(np.zeros(0, np.int64), np.zeros(0), np.zeros(0))
                    for _ in codes]
        offsets = np.concatenate([[0], np.cumsum(num_groups)])
        shifted = []
        for c, off in zip(codes, offsets[:-1]):
            c32 = np.ascontiguousarray(c, dtype=np.int32)
            shifted.append(np.where(c32 >= 0, c32 + np.int32(off),
                                    np.int32(-1)).astype(np.int32))
        codes_cat = np.concatenate(shifted) if shifted else \
            np.zeros(0, np.int32)
        vals_cat = np.concatenate([np.asarray(v) for v in values]) if values \
            else np.zeros(0)
        cnt, s, s2 = self._segment_dispatch(codes_cat, vals_cat,
                                            total_groups)
        out = []
        for g, off in zip(num_groups, offsets[:-1]):
            off = int(off)
            out.append((cnt[off:off + g], s[off:off + g], s2[off:off + g]))
        return out

    # ---------------------------------------------------- device residence
    def _release_primed(self, keys, retire: bool = False) -> None:
        """Drop an FDb's buffer refs; evict at zero refcount.  Runs as
        the per-FDb GC finalizer and, with ``retire=True``, as the eager
        snapshot-turnover path (evictions then count on
        ``device_cache.retired_buffers``)."""
        with self._prime_lock:
            gone = []
            for key in list(keys):
                n = self._primed_refs.get(key, 0) - 1
                if n <= 0:
                    self._primed_refs.pop(key, None)
                    gone.append(key)
                    self._track_packs.pop(key, None)
                else:
                    self._primed_refs[key] = n
            if gone:
                self.device_cache.drop(gone, retired=retire)

    def prime_fdb(self, db) -> int:
        """Put ``db``'s stable buffers on device once (idempotent per FDb):
        column values/row_splits, valid-doc bitmaps, spacetime postings.
        Returns the number of buffers *newly* uploaded by this call.

        Priming is **incremental across streaming generations**: the
        device cache keys buffers by host-array identity, and successive
        ``StreamingFDb`` snapshots share their sealed/delta ``Shard``
        objects — so priming generation N+1 uploads only the new delta
        (and memtable-tail) buffers; everything already resident is a
        dict hit, not a host→device copy.  Refcounts still track every
        shared buffer per FDb, so eviction waits for the *last* snapshot
        using a buffer to be collected.

        A finalizer releases the buffers when the FDb is collected; shared
        buffers (snapshots sharing Shards) survive until their last FDb.
        Thread-safe: concurrent primes/releases of the same FDb (the query
        server's many sessions) serialize on the prime lock, so refcounts
        balance and eviction never fires mid-prime."""
        with self._prime_lock:
            if db in self._primed_fdbs:
                return 0
            before = len(self.device_cache)
            primed: List[np.ndarray] = []
            for shard in db.shards:
                primed.append(shard.all_bitmap())
                for col in shard.batch.columns.values():
                    primed.append(col.values)
                    if col.row_splits is not None:
                        primed.append(col.row_splits)
                for (path, kind), idx in shard.indexes.items():
                    if kind == "spacetime":
                        primed.extend((idx.keys, idx.splits, idx.doc_ids,
                                       idx.t_min, idx.t_max))
                        # packed refine-kernel form of the ragged track —
                        # stable per shard, so pack once and keep resident
                        pts, rows = self._track_pack(shard.batch, path,
                                                     pin=True)
                        if pts is not None:
                            primed.extend((pts, rows))
            keys = set()
            for arr in primed:
                self.device_cache.put(arr)
                keys.add(id(arr))
            for key in keys:
                self._primed_refs[key] = self._primed_refs.get(key, 0) + 1
            self._primed_fdbs.add(db)
            # the finalizer shares this (mutable) key set: eager
            # retirement below removes keys it already released, so the
            # finalizer can never double-decrement them
            self._primed_keysets[db] = keys
            weakref.finalize(db, self._release_primed, keys)
            uploaded = len(self.device_cache) - before
            # eager snapshot turnover: priming a newer snapshot of the
            # same source retires the replaced generation's *exclusive*
            # buffers (its memtable-tail shard — sealed/delta shards are
            # shared by identity and stay resident) right now, instead
            # of waiting for the old snapshot's GC finalizer
            prev_ref = self._latest_primed.get(db.name)
            prev = prev_ref() if prev_ref is not None else None
            self._latest_primed[db.name] = weakref.ref(db)
            if prev is not None and prev is not db:
                prev_keys = self._primed_keysets.get(prev)
                if prev_keys:
                    stale = prev_keys - keys
                    if stale:
                        prev_keys -= stale
                        self._release_primed(stale, retire=True)
            return uploaded

    # --------------------------------------------------------- track refine
    def _track_pack(self, batch, path: str, pin: bool = False):
        """(pts, rows) packed refine form for ``batch``'s track at
        ``path`` — cached per shard by the lat buffer's identity.

        Caching pins the source array, so entries are only inserted when
        their release is guaranteed: at ``prime_fdb`` time (``pin=True``)
        or when the buffer already belongs to a primed FDb — both paths
        are dropped by the per-FDb finalizer.  Packs for never-primed
        batches are computed per call instead of leaking forever."""
        lat_path = path + ".lat"
        if lat_path not in batch.columns:
            return None, None
        lat = batch[lat_path]
        hit = self._track_packs.get(id(lat.values))
        if hit is not None:
            return hit[1], hit[2]
        pts, rows = pack_track_points(lat.values, batch[path + ".lng"].values,
                                      batch[path + ".t"].values,
                                      lat.row_splits)
        with self._prime_lock:
            if pin or id(lat.values) in self._primed_refs:
                self._track_packs[id(lat.values)] = (lat.values, pts, rows)
        return pts, rows

    def _dev(self, arr: np.ndarray):
        """Device buffer for ``arr`` (resident when primed, else upload),
        in the cache's :func:`~repro.exec.device_cache.device_form`."""
        dev = self.device_cache.get(arr)
        return dev if dev is not None else self._jnp.asarray(
            device_form(arr))

    def _order_ok(self, fh_hi, fh_lo, i: int, j: int):
        """Device-side strict first-hit compare for ordering edge (i, j):
        (hi, lo) uint32 word pairs, 64-bit lexicographic — True where the
        first hit of constraint i is strictly before constraint j's.
        ``fh_*`` index constraints on axis -2 (works for [C, D] and
        [S, C, D])."""
        a_hi, a_lo = fh_hi[..., i, :], fh_lo[..., i, :]
        b_hi, b_lo = fh_hi[..., j, :], fh_lo[..., j, :]
        return (a_hi < b_hi) | ((a_hi == b_hi) & (a_lo < b_lo))

    @staticmethod
    def _fh_table(fh_hi: np.ndarray, fh_lo: np.ndarray,
                  candidates: Optional[np.ndarray]) -> np.ndarray:
        """Kernel (hi, lo) word pair [C, n] → host uint64 table [n, C],
        masked to the sentinel outside ``candidates`` (byte parity with
        the restricted host oracle, which never evaluates those docs)."""
        table = ((fh_hi.astype(np.uint64) << np.uint64(32))
                 | fh_lo.astype(np.uint64)).T.copy()
        if candidates is not None:
            table[~np.asarray(candidates, dtype=bool), :] = FIRST_HIT_NONE
        return table

    @staticmethod
    def _an_tables(lh_hi: np.ndarray, lh_lo: np.ndarray, cnt: np.ndarray,
                   candidates: Optional[np.ndarray]
                   ) -> Tuple[np.ndarray, np.ndarray]:
        """Kernel last-hit word pair + count plane [C, n] → host uint64
        last-hit table [n, C] and int64 count table, masked to the no-hit
        identities outside ``candidates`` (byte parity with the restricted
        oracle, which never evaluates those docs)."""
        last = ((lh_hi.astype(np.uint64) << np.uint64(32))
                | lh_lo.astype(np.uint64)).T.copy()
        count = cnt.T.astype(np.int64)
        if candidates is not None:
            off = ~np.asarray(candidates, dtype=bool)
            last[off, :] = LAST_HIT_NONE
            count[off, :] = 0
        return last, count

    def refine_tracks(self, batch, path, constraints,
                      candidates=None, edges=(),
                      with_first_hits: bool = False,
                      min_counts=None, dwells=None,
                      with_analytics: bool = False):
        """One ``refine_tracks`` kernel launch over the full shard track
        (device-resident when primed), AND-combined with ``candidates`` on
        the host — byte-equal to the restricted numpy oracle because the
        per-doc verdict is independent of other docs.  Ordering ``edges``
        are a pure device-side compare over the first-hit table the same
        launch produces (no extra dispatch).  Count/dwell reductions (or
        an explicit ``with_analytics``) pull the full reduction tables
        from the same launch and recompute the verdict host-side from the
        count table (``exec.refine.reduction_verdict`` — the kernel's
        all-hit mask can't express vacuous k=0 constraints)."""
        constraints = list(constraints)
        edges = list(edges)
        if not constraints or len(constraints) > 30 or batch.n == 0:
            # >30 constraints would overflow the kernel's int32 bitset
            return super().refine_tracks(batch, path, constraints,
                                         candidates, edges=edges,
                                         with_first_hits=with_first_hits,
                                         min_counts=min_counts,
                                         dwells=dwells,
                                         with_analytics=with_analytics)
        pts, rows = self._track_pack(batch, path)
        if pts is None:
            return super().refine_tracks(batch, path, constraints,
                                         candidates, edges=edges,
                                         with_first_hits=with_first_hits,
                                         min_counts=min_counts,
                                         dwells=dwells,
                                         with_analytics=with_analytics)
        cov = pack_constraints(constraints)
        if with_analytics or _has_red(min_counts, dwells):
            _, fh_hi, fh_lo, lh_hi, lh_lo, cnt = self._ops.refine_tracks(
                self._dev(pts), self._dev(rows), self._jnp.asarray(cov),
                batch.n, impl=self._impl(), with_analytics=True)
            first = self._fh_table(np.asarray(fh_hi), np.asarray(fh_lo),
                                   candidates)
            last, count = self._an_tables(np.asarray(lh_hi),
                                          np.asarray(lh_lo),
                                          np.asarray(cnt), candidates)
            mask = reduction_verdict(first, last, count, edges,
                                     min_counts, dwells)
            if candidates is not None:
                mask &= np.asarray(candidates, dtype=bool)
            if with_analytics:
                return mask, first, last, count
            return (mask, first) if with_first_hits else mask
        need_fh = bool(edges) or with_first_hits
        if need_fh:
            mask_d, fh_hi, fh_lo = self._ops.refine_tracks(
                self._dev(pts), self._dev(rows), self._jnp.asarray(cov),
                batch.n, impl=self._impl(), with_first_hits=True)
            for i, j in edges:
                mask_d = mask_d & self._order_ok(fh_hi, fh_lo, i, j)
            mask = np.array(mask_d, dtype=bool)
        else:
            mask = np.array(self._ops.refine_tracks(
                self._dev(pts), self._dev(rows), self._jnp.asarray(cov),
                batch.n, impl=self._impl()), dtype=bool)
        if candidates is not None:
            mask &= np.asarray(candidates, dtype=bool)
        if with_first_hits:
            return mask, self._fh_table(np.asarray(fh_hi),
                                        np.asarray(fh_lo), candidates)
        return mask

    def refine_tracks_batched(self, batches, path, constraints,
                              candidates_list=None, edges=(),
                              with_first_hits: bool = False,
                              min_counts=None, dwells=None,
                              with_analytics: bool = False):
        """One ``refine_tracks_batched`` launch for the whole wave: the
        shards' packed point buffers are stacked (device-side when
        resident) and every shard shares the query's constraint table.
        Ragged point/doc counts are padded with never-matching rows.
        Ordering ``edges`` stay on device: the strict first-hit compare
        runs over the launch's stacked (hi, lo) tables before the masks
        come back to feed ``compact_masks``.  Count/dwell reductions (or
        ``with_analytics``) pull the stacked reduction tables from the
        same launch and recompute each shard's verdict host-side via
        ``exec.refine.reduction_verdict``."""
        batches = list(batches)
        constraints = list(constraints)
        edges = list(edges)
        if candidates_list is None:
            candidates_list = [None] * len(batches)
        need_an = with_analytics or _has_red(min_counts, dwells)
        if not batches:
            if with_analytics:
                return [], [], [], []
            return ([], []) if with_first_hits else []
        if not constraints or len(constraints) > 30:
            return super().refine_tracks_batched(batches, path, constraints,
                                                 candidates_list,
                                                 edges=edges,
                                                 with_first_hits=with_first_hits,
                                                 min_counts=min_counts,
                                                 dwells=dwells,
                                                 with_analytics=with_analytics)
        packs = [self._track_pack(b, path) for b in batches]
        if any(pts is None for pts, _ in packs):
            return super().refine_tracks_batched(batches, path, constraints,
                                                 candidates_list,
                                                 edges=edges,
                                                 with_first_hits=with_first_hits,
                                                 min_counts=min_counts,
                                                 dwells=dwells,
                                                 with_analytics=with_analytics)
        need_fh = bool(edges) or with_first_hits
        ns = [b.n for b in batches]
        n_max = max(ns)
        p_max = max(pts.shape[1] for pts, _ in packs)
        tables: List[np.ndarray] = []
        lasts: List[np.ndarray] = []
        counts: List[np.ndarray] = []
        if n_max == 0 or p_max == 0:
            n_c = len(constraints)
            tables = [np.full((n, n_c), FIRST_HIT_NONE,
                              dtype=np.uint64) for n in ns]
            lasts = [np.full((n, n_c), LAST_HIT_NONE, dtype=np.uint64)
                     for n in ns]
            counts = [np.zeros((n, n_c), dtype=np.int64) for n in ns]
            if need_an:
                # an all-empty-track wave is not automatically all-False:
                # vacuous (k <= 0) constraints still pass un-hit docs
                masks = [reduction_verdict(f, l, c, edges, min_counts,
                                           dwells)
                         for f, l, c in zip(tables, lasts, counts)]
            else:
                masks = [np.zeros(n, dtype=bool) for n in ns]
        elif need_an:
            jnp = self._jnp
            pts_pad, rows_pad = [], []
            for pts, rows in packs:
                p = pts.shape[1]
                dp, dr = self._dev(pts), self._dev(rows)
                if p < p_max:
                    dp = jnp.zeros((4, p_max), jnp.uint32).at[:, :p].set(dp)
                    dr = jnp.full((p_max,), -1, jnp.int32).at[:p].set(dr)
                pts_pad.append(dp)
                rows_pad.append(dr)
            _, fh_hi, fh_lo, lh_hi, lh_lo, cnt = \
                self._ops.refine_tracks_batched(
                    jnp.stack(pts_pad), jnp.stack(rows_pad),
                    jnp.asarray(pack_constraints(constraints)), n_max,
                    impl=self._impl(), with_analytics=True)
            hi_h, lo_h = np.asarray(fh_hi), np.asarray(fh_lo)
            lhi_h, llo_h = np.asarray(lh_hi), np.asarray(lh_lo)
            cnt_h = np.asarray(cnt)
            masks = []
            for i, (n, cand) in enumerate(zip(ns, candidates_list)):
                first = self._fh_table(hi_h[i, :, :n], lo_h[i, :, :n],
                                       cand)
                last, count = self._an_tables(lhi_h[i, :, :n],
                                              llo_h[i, :, :n],
                                              cnt_h[i, :, :n], cand)
                masks.append(reduction_verdict(first, last, count, edges,
                                               min_counts, dwells))
                tables.append(first)
                lasts.append(last)
                counts.append(count)
        else:
            jnp = self._jnp
            # pad each shard's resident buffers to the wave max, then one
            # stack — O(S·P_max) total copy (no per-shard full-stack copy)
            pts_pad, rows_pad = [], []
            for pts, rows in packs:
                p = pts.shape[1]
                dp, dr = self._dev(pts), self._dev(rows)
                if p < p_max:
                    dp = jnp.zeros((4, p_max), jnp.uint32).at[:, :p].set(dp)
                    dr = jnp.full((p_max,), -1, jnp.int32).at[:p].set(dr)
                pts_pad.append(dp)
                rows_pad.append(dr)
            pts_stack = jnp.stack(pts_pad)
            rows_stack = jnp.stack(rows_pad)
            cov = pack_constraints(constraints)
            if need_fh:
                out_d, fh_hi, fh_lo = self._ops.refine_tracks_batched(
                    pts_stack, rows_stack, self._jnp.asarray(cov), n_max,
                    impl=self._impl(), with_first_hits=True)
                for i, j in edges:
                    out_d = out_d & self._order_ok(fh_hi, fh_lo, i, j)
                out = np.asarray(out_d, dtype=bool)
            else:
                out = np.asarray(self._ops.refine_tracks_batched(
                    pts_stack, rows_stack, self._jnp.asarray(cov), n_max,
                    impl=self._impl()), dtype=bool)
            masks = [out[i, :n].copy() for i, n in enumerate(ns)]
            if with_first_hits:
                hi_h, lo_h = np.asarray(fh_hi), np.asarray(fh_lo)
                tables = [self._fh_table(hi_h[i, :, :n], lo_h[i, :, :n],
                                         cand)
                          for i, (n, cand) in enumerate(
                              zip(ns, candidates_list))]
        for m, cand in zip(masks, candidates_list):
            if cand is not None:
                m &= np.asarray(cand, dtype=bool)
        if with_analytics:
            return masks, tables, lasts, counts
        return (masks, tables) if with_first_hits else masks

    def refine_tracks_multi(self, batches, path, constraints_list,
                            candidates_lists=None, edges_list=None,
                            with_first_hits: bool = False,
                            min_counts_list=None, dwells_list=None):
        """Q coalesced queries' refine in ONE ``refine_tracks_multi``
        launch: the wave's track buffers are stacked once and shared, the
        per-query constraint tables ride a leading query axis (padded to
        common C/R — see ``exec.refine.pack_constraints_multi``).  Falls
        back to the loop-over-queries oracle when any query has 0/>30
        constraints or a shard lacks a packed track."""
        batches = list(batches)
        constraints_list = [list(c) for c in constraints_list]
        n_q = len(constraints_list)
        if candidates_lists is None:
            candidates_lists = [None] * n_q
        if edges_list is None:
            edges_list = [()] * n_q
        edges_list = [tuple(tuple(e) for e in es) for es in edges_list]
        if min_counts_list is None:
            min_counts_list = [None] * n_q
        if dwells_list is None:
            dwells_list = [None] * n_q
        need_an = any(_has_red(mc, dw)
                      for mc, dw in zip(min_counts_list, dwells_list))

        def fallback():
            return super(JaxBackend, self).refine_tracks_multi(
                batches, path, constraints_list, candidates_lists,
                edges_list, with_first_hits=with_first_hits,
                min_counts_list=min_counts_list, dwells_list=dwells_list)

        if n_q == 0 or not batches:
            return fallback()
        if any(not c or len(c) > 30 for c in constraints_list):
            return fallback()
        packs = [self._track_pack(b, path) for b in batches]
        if any(pts is None for pts, _ in packs):
            return fallback()
        ns = [b.n for b in batches]
        n_max = max(ns)
        p_max = max(pts.shape[1] for pts, _ in packs)
        if n_max == 0 or p_max == 0:
            return fallback()
        jnp = self._jnp
        pts_pad, rows_pad = [], []
        for pts, rows in packs:
            p = pts.shape[1]
            dp, dr = self._dev(pts), self._dev(rows)
            if p < p_max:
                dp = jnp.zeros((4, p_max), jnp.uint32).at[:, :p].set(dp)
                dr = jnp.full((p_max,), -1, jnp.int32).at[:p].set(dr)
            pts_pad.append(dp)
            rows_pad.append(dr)
        pts_stack = jnp.stack(pts_pad)
        rows_stack = jnp.stack(rows_pad)
        cov = pack_constraints_multi(constraints_list)
        if need_an:
            # one analytics launch; every query's verdict is recomputed
            # host-side from its slice of the reduction tables (pad
            # constraints sliced off — vacuous k=0 stays vacuous)
            _, fh_hi, fh_lo, lh_hi, lh_lo, cnt = \
                self._ops.refine_tracks_multi(
                    pts_stack, rows_stack, jnp.asarray(cov), n_max,
                    impl=self._impl(), with_analytics=True)
            hi_h, lo_h = np.asarray(fh_hi), np.asarray(fh_lo)
            lhi_h, llo_h = np.asarray(lh_hi), np.asarray(lh_lo)
            cnt_h = np.asarray(cnt)
            results = []
            for q in range(n_q):
                cands = candidates_lists[q]
                if cands is None:
                    cands = [None] * len(batches)
                c_q = len(constraints_list[q])
                mc, dw = min_counts_list[q], dwells_list[q]
                masks, tables = [], []
                for i, (n, cand) in enumerate(zip(ns, cands)):
                    first = self._fh_table(hi_h[q, i, :c_q, :n],
                                           lo_h[q, i, :c_q, :n], cand)
                    last, count = self._an_tables(lhi_h[q, i, :c_q, :n],
                                                  llo_h[q, i, :c_q, :n],
                                                  cnt_h[q, i, :c_q, :n],
                                                  cand)
                    m = reduction_verdict(first, last, count,
                                          edges_list[q], mc, dw)
                    if cand is not None:
                        m &= np.asarray(cand, dtype=bool)
                    masks.append(m)
                    tables.append(first)
                results.append((masks, tables) if with_first_hits
                               else masks)
            return results
        need_fh = with_first_hits or any(edges_list)
        if need_fh:
            out_d, fh_hi, fh_lo = self._ops.refine_tracks_multi(
                pts_stack, rows_stack, jnp.asarray(cov), n_max,
                impl=self._impl(), with_first_hits=True)
            masked = []
            for q, edges in enumerate(edges_list):
                m = out_d[q]
                for i, j in edges:
                    m = m & self._order_ok(fh_hi[q], fh_lo[q], i, j)
                masked.append(m)
            out = np.asarray(jnp.stack(masked), dtype=bool)
        else:
            out = np.asarray(self._ops.refine_tracks_multi(
                pts_stack, rows_stack, jnp.asarray(cov), n_max,
                impl=self._impl()), dtype=bool)
        if with_first_hits:
            hi_h, lo_h = np.asarray(fh_hi), np.asarray(fh_lo)
        results = []
        for q in range(n_q):
            cands = candidates_lists[q]
            if cands is None:
                cands = [None] * len(batches)
            masks = [out[q, i, :n].copy() for i, n in enumerate(ns)]
            for m, cand in zip(masks, cands):
                if cand is not None:
                    m &= np.asarray(cand, dtype=bool)
            if with_first_hits:
                # only the query's real constraints (pad rows sliced off)
                c_q = len(constraints_list[q])
                tables = [self._fh_table(hi_h[q, i, :c_q, :n],
                                         lo_h[q, i, :c_q, :n], cand)
                          for i, (n, cand) in enumerate(zip(ns, cands))]
                results.append((masks, tables))
            else:
                results.append(masks)
        return results

    def gather_columns(self, batch, paths, ids):
        """Selective read from device-resident buffers when primed: dense
        columns gather directly; repeated columns run the device-side
        ragged gather (CSR spans-concatenate over the resident value
        buffer, new row_splits built host-side from the shard's splits).
        Unprimed columns fall back to the host gather — identical values
        either way."""
        from ..fdb.columnar import Column, ColumnBatch
        sub = batch.select_paths(list(paths))
        ids = np.asarray(ids, dtype=np.int64)
        cols = {}
        dev_ids = None
        for p, c in sub.columns.items():
            dev = self.device_cache.get(c.values)
            if dev is None:
                cols[p] = c.gather(ids)
                continue
            if c.row_splits is None:
                if dev_ids is None:
                    dev_ids = self._jnp.asarray(ids.astype(np.int32))
                vals = host_form(dev[dev_ids], c.values.dtype)
                cols[p] = Column(vals, None, c.vocab)
                continue
            # device-side ragged gather: only the per-doc spans (one
            # entry per selected doc) go host→device; the O(points)
            # spans-concatenate index build and value gather run on
            # device against the resident CSR value buffer (int32
            # positions: a shard holds fewer than 2**31 points)
            starts = c.row_splits[ids]
            ends = c.row_splits[ids + 1]
            new_splits = np.zeros(ids.size + 1, dtype=np.int64)
            np.cumsum(ends - starts, out=new_splits[1:])
            total = int(new_splits[-1])
            if total == 0:
                vals = c.values[:0].copy()
            else:
                jnp = self._jnp
                splits_d = jnp.asarray(new_splits.astype(np.int32))
                pos = jnp.arange(total, dtype=jnp.int32)
                row = jnp.searchsorted(splits_d, pos, side="right") - 1
                flat = jnp.asarray(starts.astype(np.int32))[row] + pos \
                    - splits_d[row]
                vals = host_form(dev[flat], c.values.dtype)
            cols[p] = Column(vals, new_splits, c.vocab)
        return ColumnBatch(sub.schema, cols, ids.size)

    # ----------------------------------------------------- fused wave path
    def postings_bitmap(self, ids, t_min, t_max, t0, t1, n_docs):
        """Postings OR + span prune as one device pass over the resident
        ``t_min``/``t_max`` buffers (see ``kernels.fused``)."""
        bm = self._ops.postings_bitmap(np.asarray(ids, dtype=np.int64),
                                       self._dev(t_min), self._dev(t_max),
                                       float(t0), float(t1), n_docs,
                                       impl=self._impl())
        return np.asarray(bm, dtype=np.uint32)

    def segment_hll(self, codes, reg_idx, ranks, num_groups: int,
                    num_regs: int) -> np.ndarray:
        """One ``segment_hll`` launch: the (group, register) pair folds
        into a composite segment id and the rank plane max-reduces on
        device (``jax.ops.segment_max`` — exact uint8 integer max, so the
        result is byte-equal to the host scatter oracle)."""
        codes = np.asarray(codes, dtype=np.int64)
        reg_idx = np.asarray(reg_idx, dtype=np.int64)
        composite = np.where(codes >= 0, codes * num_regs + reg_idx, -1)
        out = self._ops.segment_hll(
            self._jnp.asarray(composite),
            self._jnp.asarray(np.asarray(ranks, dtype=np.uint8)[:, None]),
            num_groups * num_regs, impl=self._impl())
        return np.asarray(out)[:, 0].reshape(num_groups, num_regs)

    def _refine_stack(self, shards, packs, path: str):
        """Wave-stacked (pts [S, 4, P], rows [S, P]) device buffers for
        the fused refine stage, keyed in the DeviceCache per wave
        partition — resident per-shard packs are stacked once per
        partition instead of re-stacked every query.  Only cached when
        every source buffer is primed (the per-FDb finalizer then owns
        eviction); padding matches ``refine_tracks_batched``."""
        jnp = self._jnp
        p_max = max(p.shape[1] for p, _ in packs)
        src = tuple(id(sh.batch[path + ".lat"].values) for sh in shards)
        keyed_ok = all(k in self._primed_refs for k in src)
        key = ("refine_stack",) + src
        if keyed_ok:
            hit = self.device_cache.get_keyed(key)
            if hit is not None:
                return hit
        pts_pad, rows_pad = [], []
        for pts, rows in packs:
            p = pts.shape[1]
            dp, dr = self._dev(pts), self._dev(rows)
            if p < p_max:
                dp = jnp.zeros((4, p_max), jnp.uint32).at[:, :p].set(dp)
                dr = jnp.full((p_max,), -1, jnp.int32).at[:p].set(dr)
            pts_pad.append(dp)
            rows_pad.append(dr)
        out = (jnp.stack(pts_pad), jnp.stack(rows_pad))
        if keyed_ok:
            self.device_cache.put_keyed(key, out)
        return out

    def _agg_stacks(self, shards, agg, impl: str, n_max: int):
        """Offset-coded group-code stack [S, n_max] (−1 pad) plus one
        value stack per aggregated column for the fused segment stage,
        keyed in the DeviceCache per wave partition.  Value stacks are
        float64 under ``reference`` (bit-parity accumulation) and float32
        otherwise — the same cast ``_segment_dispatch`` applies."""
        jnp = self._jnp
        facts = [agg.factorize(sh, backend=self) for sh in shards]
        offsets = np.concatenate(
            [[0], np.cumsum([g for _, _, g in facts])]).astype(np.int64)
        total = int(offsets[-1])
        if total == 0:
            return facts, offsets, None, (), 0
        src = tuple(id(sh.batch[agg.key_path].values) for sh in shards)
        keyed_ok = all(k in self._primed_refs for k in src)
        ckey = ("agg_codes", n_max) + src
        codes_dev = self.device_cache.get_keyed(ckey) if keyed_ok else None
        if codes_dev is None:
            codes = np.full((len(shards), n_max), -1, dtype=np.int32)
            for i, (sh, (_, c, g)) in enumerate(zip(shards, facts)):
                if g:
                    codes[i, :sh.n] = c + np.int32(offsets[i])
            codes_dev = jnp.asarray(codes)
            if keyed_ok:
                self.device_cache.put_keyed(ckey, codes_dev)
        ftag = "f64" if impl == "reference" else "f32"
        dt = np.float64 if impl == "reference" else np.float32
        vals_dev = []
        for vp in (agg.value_paths or [None]):
            if vp is None:
                # count-only plan: a zeros stack so the segment stage
                # still returns per-group row counts
                with self._jax.enable_x64(True):
                    vals_dev.append(jnp.zeros((len(shards), n_max), dt))
                continue
            vsrc = tuple(id(sh.batch[vp].values) for sh in shards)
            vok = keyed_ok and all(k in self._primed_refs for k in vsrc)
            vkey = ("agg_vals", ftag, n_max) + vsrc
            dv = self.device_cache.get_keyed(vkey) if vok else None
            if dv is None:
                stack = np.zeros((len(shards), n_max), dtype=dt)
                for i, sh in enumerate(shards):
                    if sh.n:
                        stack[i, :sh.n] = np.asarray(sh.batch[vp].values,
                                                     dt)
                with self._jax.enable_x64(True):
                    dv = jnp.asarray(stack)
                if vok:
                    self.device_cache.put_keyed(vkey, dv)
            vals_dev.append(dv)
        return facts, offsets, codes_dev, tuple(vals_dev), total

    def run_wave_fused(self, shards, probes, refine=None, agg=None,
                       prefetch_shards=None, query_id=None):
        """One fused dispatch for the whole wave (``kernels.fused``), or
        ``None`` to decline to the per-primitive path: a refine spec with
        zero or >30 constraints, a shard without a packed track, or a
        wave whose tracks are all empty (the legacy path's host shortcut
        already covers that case).  ``prefetch_shards`` — the next wave's
        shards — are staged *before* this wave's outputs sync back to the
        host, overlapping upload with compute."""
        shards = list(shards)
        probes = [list(ps) for ps in probes]
        if not shards:
            return [], [], ([] if agg is not None else None)
        packs = None
        edges: Tuple = ()
        mcs: Tuple = ()
        dws: Tuple = ()
        if refine is not None:
            cons = list(refine.constraints)
            edges = tuple(tuple(e) for e in refine.edges)
            mcs = tuple(int(k) for k in
                        (getattr(refine, "min_counts", None) or ()))
            dws = tuple(None if d is None else float(d) for d in
                        (getattr(refine, "dwells", None) or ()))
            if not _has_red(mcs, dws):
                # default reductions: keep the legacy jit-cache key
                mcs, dws = (), ()
            if not cons or len(cons) > 30:
                return None
            packs = [self._track_pack(sh.batch, refine.path)
                     for sh in shards]
            if any(p is None for p, _ in packs):
                return None
        ns = [sh.n for sh in shards]
        n_max = max(ns)
        fulls = [sh.all_bitmap() for sh in shards]
        w = max(f.size for f in fulls)
        if n_max == 0 or w == 0:
            # all-empty wave: nothing to compute, but it still counts one
            # fused dispatch so the ⌈shards/wave⌉ total-launch contract
            # stays exact
            self._ops.record_launch("run_wave_fused")
            if prefetch_shards:
                self.prefetch_wave(prefetch_shards, refine, agg)
            seg = ([(np.zeros(0, dtype=np.int64), []) for _ in shards]
                   if agg is not None else None)
            return ([0] * len(shards),
                    [np.zeros(0, dtype=np.int64) for _ in shards], seg)
        if refine is not None and max(p.shape[1] for p, _ in packs) == 0:
            return None
        impl = self._impl()
        with span("stack", query=query_id):
            k = 1 + max((len(ps) for ps in probes), default=0)
            stack = np.zeros((len(shards), k, w), dtype=np.uint32)
            for i, (f, ps) in enumerate(zip(fulls, probes)):
                stack[i, 0, :f.size] = f
                for j, b in enumerate(ps):
                    stack[i, j + 1, :b.size] = b
                for j in range(len(ps) + 1, k):
                    stack[i, j, :f.size] = f
            probe_dev = self._jnp.asarray(stack)
            ns_dev = self._jnp.asarray(np.asarray(ns, dtype=np.int32))
            pts_stack = rows_stack = cov_dev = None
            if refine is not None:
                pts_stack, rows_stack = self._refine_stack(shards, packs,
                                                           refine.path)
                cov_dev = self._jnp.asarray(pack_constraints(cons))
            codes_dev, vals_dev, total = None, (), 0
            facts, offsets = [], None
            if agg is not None:
                facts, offsets, codes_dev, vals_dev, total = \
                    self._agg_stacks(shards, agg, impl, n_max)
        minmax = tuple(getattr(agg, "minmax", ()) or ()) \
            if agg is not None else ()
        with span("dispatch", query=query_id,
                  **_refine_grid_meta(1, pts_stack, n_max)):
            cand, sel_idx, sel_counts, segs = self._ops.run_wave_fused(
                probe_dev, ns_dev, pts_stack, rows_stack, cov_dev,
                codes_dev, vals_dev, num_docs=n_max, edges=edges,
                min_counts=mcs, dwells=dws, total_groups=total, impl=impl,
                minmax=minmax)
        # stage wave k+1's buffers before wave k's outputs sync to host
        if prefetch_shards:
            self.prefetch_wave(prefetch_shards, refine, agg)
        with span("sync", query=query_id):
            idx_h = np.asarray(sel_idx)
            counts_h = np.asarray(sel_counts)
            cand_h = np.asarray(cand)
            segs_h = [[np.asarray(a) for a in st] for st in (segs or [])]
        n_cands = [int(c) for c in cand_h]
        ids_list = [idx_h[i, :int(counts_h[i])].astype(np.int64)
                    for i in range(len(shards))]
        if agg is None:
            return n_cands, ids_list, None
        with span("finalize", query=query_id):
            # slots are (count, sum, sumsq) triples, or 5-tuples with the
            # per-group min/max planes appended for flagged value slots
            slot_host = []
            for st in segs_h:
                slot = (np.rint(st[0]).astype(np.int64),
                        np.asarray(st[1], dtype=np.float64),
                        np.asarray(st[2], dtype=np.float64))
                if len(st) == 5:
                    slot = (*slot, np.asarray(st[3], dtype=np.float64),
                            np.asarray(st[4], dtype=np.float64))
                slot_host.append(slot)
            seg = []
            for i, (uniq, _c, g) in enumerate(facts):
                off = int(offsets[i])
                # g == 0 → (uniq, []) exactly like the base-class oracle
                seg.append((uniq,
                            [tuple(a[off:off + g] for a in slot)
                             for slot in slot_host] if g else []))
        return n_cands, ids_list, seg

    def run_wave_fused_multi(self, shards, probes_multi, refines,
                             prefetch_shards=None):
        """Q coalesced selection queries through one wave in ONE
        ``run_wave_fused_multi`` dispatch: per-query probe stacks ride a
        leading query axis folded into the stacked probe/compact kernels,
        the per-query constraint tables a leading axis on the multi refine
        kernel, and the wave's track buffers are shared.  Declines
        (``None``) on the same conditions as the single-query fused path —
        the server then falls back to per-query execution."""
        shards = list(shards)
        probes_multi = [[list(ps) for ps in probes]
                        for probes in probes_multi]
        n_q = len(probes_multi)
        if n_q == 0:
            return []
        if not shards:
            return [([], []) for _ in range(n_q)]
        refines = list(refines)
        has_refine = any(r is not None for r in refines)
        path = None
        packs = None
        mcs_multi: Tuple = ()
        dws_multi: Tuple = ()
        if has_refine:
            if not all(r is not None for r in refines):
                return None              # mixed refine/no-refine group
            if len({r.path for r in refines}) != 1:
                return None
            path = refines[0].path
            cons_list = [list(r.constraints) for r in refines]
            if any(not c or len(c) > 30 for c in cons_list):
                return None
            mcs_multi = tuple(
                tuple(int(k) for k in
                      (getattr(r, "min_counts", None) or ()))
                for r in refines)
            dws_multi = tuple(
                tuple(None if d is None else float(d) for d in
                      (getattr(r, "dwells", None) or ()))
                for r in refines)
            if not any(_has_red(mc, dw)
                       for mc, dw in zip(mcs_multi, dws_multi)):
                # default reductions: keep the legacy jit-cache key
                mcs_multi = tuple(() for _ in refines)
                dws_multi = tuple(() for _ in refines)
            for mc, dw in zip(mcs_multi, dws_multi):
                if mc and all(int(k) <= 0 for k in mc) \
                        and not any(d is not None for d in dw):
                    # an all-vacuous query passes docs with zero points;
                    # the multi kernel's always-hit pad constraints can't
                    # express that — decline to the per-query path
                    return None
            packs = [self._track_pack(sh.batch, path) for sh in shards]
            if any(p is None for p, _ in packs):
                return None
        ns = [sh.n for sh in shards]
        n_max = max(ns)
        fulls = [sh.all_bitmap() for sh in shards]
        w = max(f.size for f in fulls)
        if n_max == 0 or w == 0:
            # all-empty wave: still one fused dispatch so the coalesced
            # ⌈shards/wave⌉ total-launch contract stays exact
            self._ops.record_launch("run_wave_fused_multi")
            if prefetch_shards:
                self.prefetch_wave(prefetch_shards,
                                   refines[0] if has_refine else None)
            return [([0] * len(shards),
                     [np.zeros(0, dtype=np.int64) for _ in shards])
                    for _ in range(n_q)]
        if has_refine and max(p.shape[1] for p, _ in packs) == 0:
            return None
        with span("stack", n=n_q):
            k = 1 + max((len(ps) for probes in probes_multi for ps in probes),
                        default=0)
            stack = np.zeros((n_q, len(shards), k, w), dtype=np.uint32)
            for q, probes in enumerate(probes_multi):
                for i, (f, ps) in enumerate(zip(fulls, probes)):
                    stack[q, i, 0, :f.size] = f
                    for j, b in enumerate(ps):
                        stack[q, i, j + 1, :b.size] = b
                    for j in range(len(ps) + 1, k):
                        stack[q, i, j, :f.size] = f
            probe_dev = self._jnp.asarray(stack)
            ns_dev = self._jnp.asarray(np.asarray(ns, dtype=np.int32))
            pts_stack = rows_stack = cov_dev = None
            edges_multi = tuple(() for _ in range(n_q))
            if has_refine:
                pts_stack, rows_stack = self._refine_stack(shards, packs, path)
                cov_dev = self._jnp.asarray(pack_constraints_multi(cons_list))
                edges_multi = tuple(tuple(tuple(e) for e in r.edges)
                                    for r in refines)
        with span("dispatch", n=n_q,
                  **_refine_grid_meta(n_q, pts_stack, n_max)):
            cand, sel_idx, sel_counts = self._ops.run_wave_fused_multi(
                probe_dev, ns_dev, pts_stack, rows_stack, cov_dev,
                num_docs=n_max, edges_multi=edges_multi,
                min_counts_multi=mcs_multi, dwells_multi=dws_multi,
                impl=self._impl())
        if prefetch_shards:
            self.prefetch_wave(prefetch_shards,
                               refines[0] if has_refine else None)
        with span("sync", n=n_q):
            cand_h = np.asarray(cand)
            idx_h = np.asarray(sel_idx)
            counts_h = np.asarray(sel_counts)
        out = []
        for q in range(n_q):
            n_cands = [int(c) for c in cand_h[q]]
            ids_list = [idx_h[q, i, :int(counts_h[q, i])].astype(np.int64)
                        for i in range(len(shards))]
            out.append((n_cands, ids_list))
        return out

    def prefetch_wave(self, shards, refine=None, agg=None) -> None:
        """Double-buffered async prefetch: build (or re-find) the next
        wave's keyed stacked buffers — refine point stacks, offset group
        codes, value stacks — so its fused dispatch starts from resident
        device memory.  Device puts are non-blocking; nothing here syncs."""
        shards = list(shards)
        if not shards:
            return
        with span("prefetch"):
            n_max = max(sh.n for sh in shards)
            if n_max == 0:
                return
            if refine is not None:
                cons = list(refine.constraints)
                if cons and len(cons) <= 30:
                    packs = [self._track_pack(sh.batch, refine.path)
                             for sh in shards]
                    if all(p is not None for p, _ in packs) and \
                            max(p.shape[1] for p, _ in packs) > 0:
                        self._refine_stack(shards, packs, refine.path)
            if agg is not None:
                self._agg_stacks(shards, agg, self._impl(), n_max)

    # ---------------------------------------------------- partition layer
    def partition_context(self, part: int, num_parts: int):
        """Run one partition's dispatches device-local: partition p of P
        pins its waves to exec-mesh device p mod D.  On a one-device host
        (CPU CI's emulated mesh) there is nothing to pin — the no-op
        keeps emulated P>1 runs byte-identical by construction."""
        if num_parts <= 1:
            return contextlib.nullcontext()
        devs = self._jax.devices()
        if len(devs) <= 1:
            return contextlib.nullcontext()
        return self._jax.default_device(devs[part % len(devs)])

    def merge_partials(self, states, minmax=(), parts=None):
        """One-launch device combine of the per-shard segment states:
        align every state to the sorted union key space host-side, stack
        ``[S, K, G]`` float64 planes (identity fill: 0 for
        count/sum/sum_sq, ±inf for min/max, False for presence), then
        dispatch ``ops.merge_partials`` under ``shard_map`` over the
        ``"part"`` axis of ``launch.mesh.make_exec_mesh``.  The in-order
        accumulation matches the numpy oracle bit for bit on the
        emulated (size-1 axis) mesh — see ``kernels/merge.py`` for the
        multi-device subtotal caveat — and the whole merge costs exactly
        one recorded launch per query."""
        from ..launch.mesh import make_exec_mesh

        states = [(np.asarray(k), list(slots)) for k, slots in states]
        live = [st for st in states if len(st[0]) and st[1]]
        mesh = make_exec_mesh(len(parts) if parts else 0)
        with self._jax.enable_x64(True):
            if not live:
                # nothing selected anywhere — still one combine launch,
                # keeping the launch contract exact (cf. all-empty waves)
                zero = np.zeros((1, 1, 0))
                self._ops.merge_partials(
                    zero.astype(np.int64), zero, zero, zero, zero,
                    np.zeros((1, 0), bool), mesh=mesh, impl=self.impl)
                return np.zeros(0, np.int64), []
            union = np.unique(np.concatenate([k for k, _ in live]))
            n_states = len(live)
            n_slots = max(len(slots) for _, slots in live)
            mm = tuple(minmax)
            mm = mm + (False,) * (n_slots - len(mm))
            g = union.size
            cnt = np.zeros((n_states, n_slots, g), np.int64)
            s = np.zeros((n_states, n_slots, g), np.float64)
            s2 = np.zeros((n_states, n_slots, g), np.float64)
            mn = np.full((n_states, n_slots, g), np.inf)
            mx = np.full((n_states, n_slots, g), -np.inf)
            msk = np.zeros((n_states, g), bool)
            for si, (keys, slots) in enumerate(live):
                idx = np.searchsorted(union, keys)
                for k, st in enumerate(slots):
                    cnt[si, k, idx] = np.asarray(st[0], np.int64)
                    s[si, k, idx] = np.asarray(st[1], np.float64)
                    s2[si, k, idx] = np.asarray(st[2], np.float64)
                    if len(st) >= 5:
                        mn[si, k, idx] = np.asarray(st[3], np.float64)
                        mx[si, k, idx] = np.asarray(st[4], np.float64)
                msk[si, idx] = np.asarray(slots[0][0]) > 0
            out = self._ops.merge_partials(cnt, s, s2, mn, mx, msk,
                                           mesh=mesh, impl=self.impl)
            o_cnt, o_s, o_s2, o_mn, o_mx = \
                [np.asarray(x) for x in out[:5]]
        merged = []
        for k in range(n_slots):
            slot = (o_cnt[k].astype(np.int64), o_s[k], o_s2[k])
            if mm[k]:
                slot = (*slot, o_mn[k], o_mx[k])
            merged.append(slot)
        return union, merged


# --------------------------------------------------------------------------
# Registry
# --------------------------------------------------------------------------

_FACTORIES: Dict[str, Callable[[], ExecBackend]] = {}
_INSTANCES: Dict[str, ExecBackend] = {}


def register_backend(name: str, factory: Callable[[], ExecBackend]) -> None:
    """Register (or replace) a backend under ``name``."""
    _FACTORIES[name] = factory
    _INSTANCES.pop(name, None)


def backend_names() -> List[str]:
    return sorted(_FACTORIES)


register_backend("numpy", NumpyBackend)
register_backend("jax", JaxBackend)


def get_backend(spec: Optional[str] = None) -> ExecBackend:
    """Resolve a backend name (default: ``$REPRO_EXEC_BACKEND`` or numpy)."""
    name = spec or os.environ.get("REPRO_EXEC_BACKEND") or "numpy"
    if name not in _FACTORIES:
        raise ValueError(f"unknown exec backend {name!r}; "
                         f"registered: {backend_names()}")
    if name not in _INSTANCES:
        _INSTANCES[name] = _FACTORIES[name]()
    return _INSTANCES[name]


def as_backend(spec: Union[None, str, ExecBackend]) -> ExecBackend:
    """Accept None (env default), a registered name, or an instance."""
    if isinstance(spec, ExecBackend):
        return spec
    return get_backend(spec)
