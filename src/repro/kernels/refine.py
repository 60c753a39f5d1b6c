"""Pallas ragged track-refine kernel (the Tesseract exact pass, paper §2).

After the conservative ``spacetime`` index probe, every candidate trip must
be checked *exactly*: does some track point fall inside the query region's
Morton-range cover during the time window — for **every** constraint of the
query?  Host-side this is the `eval_expr(InSpaceTime)` loop; here it is one
fused device pass over the shard's CSR track buffers.

Input packing (all integer words, so the pass is exact on any impl):

  * ``pts`` — uint32 ``[4, P]`` per-point words: Morton key split into
    (hi, lo) 32-bit halves, and the float64 timestamp mapped through the
    order-preserving IEEE-754 trick (flip sign bit for positives, all bits
    for negatives) and split the same way.  Point-in-range and in-window
    become 64-bit *lexicographic* integer compares — byte-identical to the
    host's uint64 searchsorted + float64 compares, with no f64 on device.
  * ``rows`` — int32 ``[P]`` doc id per point (CSR ``row_splits`` expanded;
    ``-1`` marks padding and never matches a doc).
  * ``cov`` — uint32 ``[C, 8, R]`` per-constraint range table: each of the
    R slots holds (key_lo, key_hi) cover-range bounds and the constraint's
    (win_lo, win_hi) window, all as (hi, lo) word pairs.  Padding slots use
    an empty range (lo = 2^64−1, hi = 0) and never hit.

The kernel walks a ``(query, shard, step)`` grid over a **banded**
schedule: doc block g (docs [g·128, (g+1)·128)) only meets the point
blocks that hold its own points, so each shard's steps are the
(doc block, point block) pairs whose bands overlap, listed with doc blocks
nondecreasing — the merge path of the CSR layout.  Per step it evaluates
all C constraints against the R ranges on the VPU for one point block,
reduces hits per doc through the one-hot ``rows == doc_iota`` compare,
and OR-accumulates a **per-doc constraint bitset** (bit c set ⇔ some point
satisfied constraint c).  A doc passes iff its bitset is full — computed in
the jit epilogue.  A wave of shards (ragged P and doc counts zero-padded)
rides the shard axis and Q coalesced queries the query axis, so a wave
costs **one** launch, mirroring ``compact_batched``; the single-query and
single-shard entry points are the Q=1 / S=1 cases of the same call.

Precondition of the banded walk: each shard's ``rows`` is nondecreasing
over its points and followed only by a ``-1`` tail pad — the layout
``exec.refine.pack_track_points`` (``row_splits`` expanded) and the wave
stacks padded with ``-1`` give.  The schedule is derived inside the jit
from ``rows`` (:func:`_band_schedule`) and reaches the index maps as
scalar prefetch; its length per shard is the static bound
:func:`grid_steps` counts, so every world of one shape compiles the same
program.  Every doc block, empty or padding, has at least one step, the
first of its run, where its outputs are initialised; the bound's spare
steps at the tail repeat the last block and compute nothing.

On the device the point words and doc ids travel as one int32 ``[8, T]``
tile per point block (rows: key hi/lo, time hi/lo, doc id, 3 zero rows),
transposed in the kernel so points run down the sublanes and docs and
ranges along the lanes.  Words are mapped to int32 by flipping the sign
bit, which keeps their unsigned order: the TPU reduces int32 but not
uint32.  The epilogue flips the hit tables back.

Under ``with_first_hits`` the same grid walk also min-reduces a
per-(doc × constraint) **first-hit** timestamp — the lexicographic
(t_hi, t_lo) minimum over the doc's satisfying points, kept as two uint32
word planes with a (0xFFFFFFFF, 0xFFFFFFFF) "never hit" sentinel (only
NaN timestamps could collide with it, and NaN never passes a window
compare).  Ordered Tesseract queries (A before B) compare that table
edge-wise on device; the ordering adds outputs, not launches.

``with_analytics`` generalizes that min-reduce into the whole reduction
family, still in the same one-hot compare pass: alongside the first-hit
planes it max-reduces a **last-hit** (t_hi, t_lo) pair per
(doc × constraint) — dual sentinel (0, 0); packed key 0 only encodes −NaN,
which never passes a window compare — and sum-accumulates an int32
**hit count** across the sequential point-grid axis.  Count thresholds
(``at_least(k)``) and dwell verdicts (``last − first >= n`` seconds) are
pure epilogue compares over these tables; the reductions add outputs to
the existing ⌈shards/wave⌉ dispatches, never launches.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["refine_tracks", "refine_tracks_batched", "refine_tracks_multi",
           "grid_steps", "DEFAULT_POINT_BLOCK", "DEFAULT_DOC_BLOCK"]

DEFAULT_POINT_BLOCK = 512
DEFAULT_DOC_BLOCK = 128
_RANGE_PAD = 128               # cover-range slots padded to the lane width


def _ge(a_hi, a_lo, b_hi, b_lo):
    """a >= b over (hi, lo) word pairs (64-bit lexicographic)."""
    return (a_hi > b_hi) | ((a_hi == b_hi) & (a_lo >= b_lo))


def _lt(a_hi, a_lo, b_hi, b_lo):
    return (a_hi < b_hi) | ((a_hi == b_hi) & (a_lo < b_lo))


def _le(a_hi, a_lo, b_hi, b_lo):
    return (a_hi < b_hi) | ((a_hi == b_hi) & (a_lo <= b_lo))


_FH_SENT = 0xFFFFFFFF          # first-hit "no hit" sentinel word
_SIGN = 0x80000000
_I32_MAX = 0x7FFFFFFF          # _FH_SENT in signed order
_I32_MIN = -0x80000000         # last-hit sentinel word 0 in signed order


def _signed(x):
    """uint32 words → int32 with the same order (sign bit flipped)."""
    return jax.lax.bitcast_convert_type(x ^ jnp.uint32(_SIGN), jnp.int32)


def _unsigned(x):
    """Inverse of :func:`_signed`."""
    return jax.lax.bitcast_convert_type(x, jnp.uint32) ^ jnp.uint32(_SIGN)


# A schedule step is one int32 word in SMEM: flags in bits 0–1, the point
# block from bit 2, the doc block above it (``_step_shift``).
_INIT, _COMPUTE = 1, 2           # first step of a doc block's run; work
_FLAG_BITS = 2


def grid_steps(q: int, s: int, p: int, num_docs: int,
               point_block: int = DEFAULT_POINT_BLOCK,
               doc_block: int = DEFAULT_DOC_BLOCK) -> Tuple[int, int]:
    """(banded, dense) grid steps of one :func:`refine_tracks_multi`
    launch over Q queries × S shards of P points and ``num_docs`` docs:
    the banded schedule's static length against the full doc-block ×
    point-block product it replaces.  Bands split the points in doc
    order, so consecutive bands share at most one point block and an
    empty band takes one step: a shard needs at most ⌈P/T⌉ + ⌈D/B⌉ − 1."""
    if min(q, s, p, num_docs) <= 0:
        return 0, 0
    n_pb, n_db = pl.cdiv(p, point_block), pl.cdiv(num_docs, doc_block)
    return q * s * (n_pb + n_db - 1), q * s * n_pb * n_db


def _step_shift(n_pb: int, n_db: int) -> int:
    """Bit offset of the doc block in a step word."""
    shift = _FLAG_BITS + max(1, (n_pb - 1).bit_length())
    if shift + max(1, (n_db - 1).bit_length()) > 31:
        raise ValueError(f"{n_pb} point blocks × {n_db} doc blocks do not "
                         "fit a refine schedule step word")
    return shift


def _band_schedule(rows, n_steps: int, point_block: int, doc_block: int,
                   n_doc_blocks: int, shift: int):
    """rows [S, P_pad] int32 (CSR order, ``-1`` tail) → the banded walk as
    int32 step words [S · n_steps]: doc block, point block and flags
    (``_INIT`` on the first step of a doc block's run, ``_COMPUTE`` where
    the step's point block holds some of its points)."""
    s, p_pad = rows.shape
    n_pb = p_pad // point_block
    keyed = jnp.where(rows < 0, n_doc_blocks * doc_block, rows)
    bounds = jnp.arange(n_doc_blocks + 1, dtype=jnp.int32) * doc_block
    starts = jax.vmap(lambda r: jnp.searchsorted(r, bounds))(keyed)
    lo, hi = starts[:, :-1], starts[:, 1:]         # each doc block's band
    busy = hi > lo
    first = jnp.minimum(lo // point_block, n_pb - 1)
    n = jnp.where(busy, (hi - 1) // point_block - first + 1, 1)
    ends = jnp.cumsum(n, axis=1)
    step = jnp.arange(n_steps, dtype=jnp.int32)
    g = jax.vmap(lambda e: jnp.searchsorted(e, step, side="right"))(ends)
    live = g < n_doc_blocks
    g = jnp.minimum(g, n_doc_blocks - 1)           # spare tail: last block

    def at(a):
        return jnp.take_along_axis(a, g, axis=1)

    n_g = at(n)
    local = step - (at(ends) - n_g)
    pb = at(first) + jnp.minimum(local, n_g - 1)
    flags = (jnp.where(live & (local == 0), _INIT, 0)
             | jnp.where(live & at(busy), _COMPUTE, 0))
    words = (g << shift) | (pb << _FLAG_BITS) | flags
    return words.astype(jnp.int32).reshape(s * n_steps)


def _refine_kernel(steps_ref, words_ref, cov_ref, out_ref, *aux_refs,
                   doc_block: int, n_constraints: int, n_steps: int,
                   shift: int):
    """One (query, shard, step) grid step of the banded schedule
    (``steps_ref``, scalar prefetch).  ``aux_refs`` are the first-hit
    (hi, lo) planes, then last-hit (hi, lo) and the hit count under
    analytics; all words are in signed order."""
    step = steps_ref[pl.program_id(1) * n_steps + pl.program_id(2)]
    top = jnp.int32(_I32_MAX)
    bottom = jnp.int32(_I32_MIN)

    @pl.when((step & _INIT) != 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)
        for fh in aux_refs[:2]:                    # first-hit planes → sent
            fh[...] = jnp.full_like(fh, top)
        for lh in aux_refs[2:4]:                   # last-hit planes → sent
            lh[...] = jnp.full_like(lh, bottom)
        for cnt in aux_refs[4:]:
            cnt[...] = jnp.zeros_like(cnt)

    @pl.when((step & _COMPUTE) != 0)
    def _fold():
        _refine_step(words_ref, cov_ref, out_ref, aux_refs, step >> shift,
                     doc_block=doc_block, n_constraints=n_constraints)


def _refine_step(words_ref, cov_ref, out_ref, aux_refs, g, *,
                 doc_block: int, n_constraints: int):
    """Fold one point block into doc block ``g``'s outputs."""
    top = jnp.int32(_I32_MAX)
    bottom = jnp.int32(_I32_MIN)
    w = words_ref[0].T                             # (T, 8) int32
    k_hi, k_lo, t_hi, t_lo, rows = (w[:, i:i + 1] for i in range(5))
    docs = g * doc_block + jax.lax.broadcasted_iota(
        jnp.int32, (1, doc_block), 1)              # (1, D)
    onehot = rows == docs                          # (T, D) bool
    acc = jnp.zeros((1, doc_block), jnp.int32)
    for c in range(n_constraints):
        cv = cov_ref[0, c]                         # (8, R)
        lo_hi, lo_lo, hi_hi, hi_lo, w0_hi, w0_lo, w1_hi, w1_lo = (
            cv[i:i + 1, :] for i in range(8))      # (1, R) each
        hit = (_ge(k_hi, k_lo, lo_hi, lo_lo)       # key in [lo, hi)
               & _lt(k_hi, k_lo, hi_hi, hi_lo)
               & _ge(t_hi, t_lo, w0_hi, w0_lo)     # t in [w0, w1]
               & _le(t_hi, t_lo, w1_hi, w1_lo))
        hit_pt = jnp.any(hit, axis=1, keepdims=True)          # (T, 1)
        hit2d = onehot & hit_pt                               # (T, D)
        contrib = jnp.any(hit2d, axis=0, keepdims=True)       # (1, D)
        acc = acc | jnp.left_shift(contrib.astype(jnp.int32), c)
        if aux_refs:
            # per-doc lexicographic (t_hi, t_lo) min over this point
            # block, two passes: min hi, then min lo among points whose
            # hi equals that min (exact — the second pass only sees the
            # argmin-hi candidates; no-hit docs stay at the sentinel)
            fh_hi_ref, fh_lo_ref = aux_refs[0], aux_refs[1]
            blk_hi = jnp.min(jnp.where(hit2d, t_hi, top), axis=0,
                             keepdims=True)
            at_min = hit2d & (t_hi == blk_hi)
            blk_lo = jnp.min(jnp.where(at_min, t_lo, top), axis=0,
                             keepdims=True)
            acc_hi = fh_hi_ref[0, 0, c:c + 1, :]
            acc_lo = fh_lo_ref[0, 0, c:c + 1, :]
            take = _lt(blk_hi, blk_lo, acc_hi, acc_lo)
            fh_hi_ref[0, 0, c:c + 1, :] = jnp.where(take, blk_hi, acc_hi)
            fh_lo_ref[0, 0, c:c + 1, :] = jnp.where(take, blk_lo, acc_lo)
        if len(aux_refs) > 2:
            # last-hit dual: lexicographic max with a (0, 0) unsigned init
            # — safe as a sentinel because packed key 0 only encodes −NaN,
            # which never passes a window compare; count sums hits across
            # the sequential point-grid axis
            lh_hi_ref, lh_lo_ref, cnt_ref = aux_refs[2:]
            lblk_hi = jnp.max(jnp.where(hit2d, t_hi, bottom), axis=0,
                              keepdims=True)
            at_max = hit2d & (t_hi == lblk_hi)
            lblk_lo = jnp.max(jnp.where(at_max, t_lo, bottom), axis=0,
                              keepdims=True)
            lacc_hi = lh_hi_ref[0, 0, c:c + 1, :]
            lacc_lo = lh_lo_ref[0, 0, c:c + 1, :]
            ltake = _lt(lacc_hi, lacc_lo, lblk_hi, lblk_lo)
            lh_hi_ref[0, 0, c:c + 1, :] = jnp.where(ltake, lblk_hi, lacc_hi)
            lh_lo_ref[0, 0, c:c + 1, :] = jnp.where(ltake, lblk_lo, lacc_lo)
            cnt_ref[0, 0, c:c + 1, :] = cnt_ref[0, 0, c:c + 1, :] \
                + jnp.sum(hit2d, axis=0, keepdims=True, dtype=jnp.int32)
    out_ref[0, 0] = out_ref[0, 0] | acc


def _pad_cov(cov: jnp.ndarray) -> jnp.ndarray:
    """Pad the range axis to the lane width with never-hit slots."""
    c, _, r = cov.shape
    padded_r = max(_RANGE_PAD, pl.cdiv(max(r, 1), _RANGE_PAD) * _RANGE_PAD)
    if r == padded_r:
        return cov
    pad = jnp.zeros((c, 8, padded_r), jnp.uint32)
    # empty range: key >= 0xFFFF…FFFF is unsatisfiable for 60-bit keys and
    # key < 0 is always false — either kills the slot
    pad = pad.at[:, 0, :].set(jnp.uint32(0xFFFFFFFF))
    pad = pad.at[:, 1, :].set(jnp.uint32(0xFFFFFFFF))
    return pad.at[:, :, :r].set(cov)


@functools.partial(jax.jit, static_argnames=("num_docs", "point_block",
                                             "doc_block", "interpret",
                                             "with_first_hits",
                                             "with_analytics"))
def refine_tracks_multi(pts: jnp.ndarray, rows: jnp.ndarray,
                        cov: jnp.ndarray, num_docs: int,
                        point_block: int = DEFAULT_POINT_BLOCK,
                        doc_block: int = DEFAULT_DOC_BLOCK,
                        interpret: bool = False,
                        with_first_hits: bool = False,
                        with_analytics: bool = False):
    """Multi-query wave refine: Q coalesced queries' constraint tables
    against ONE wave of shards' track buffers in a single launch.

    pts [S, 4, P] uint32 and rows [S, P] int32 are shared across queries
    (the wave's resident track buffers, uploaded once); cov [Q, C, 8, R]
    uint32 carries each query's packed cover-range × window table with a
    leading query axis (constraint / range counts padded across queries by
    the caller: never-hit slots on the range axis, always-hit constraints
    on the C axis).  Returns hit masks [Q, S, num_docs] bool, plus uint32
    first-hit word tables [Q, S, C, num_docs] × 2 under
    ``with_first_hits``; ``with_analytics`` adds last-hit word tables
    (0-sentinel) and an int32 hit-count table, same launch.
    """
    s, _, p = pts.shape
    n_queries = int(cov.shape[0])
    n_constraints = int(cov.shape[1])
    full = jnp.int32((1 << n_constraints) - 1)
    sent = jnp.uint32(_FH_SENT)
    n_tables = 5 if with_analytics else (2 if with_first_hits else 0)

    def table(fill, dtype=jnp.uint32):
        return jnp.full((n_queries, s, n_constraints, num_docs), fill,
                        dtype)

    def empty(out):
        if with_analytics:
            return (out, table(sent), table(sent), table(0), table(0),
                    table(0, jnp.int32))
        return (out, table(sent), table(sent)) if with_first_hits else out

    if n_queries == 0 or s == 0 or num_docs == 0:
        return empty(jnp.zeros((n_queries, s, num_docs), jnp.bool_))
    if p == 0 or n_constraints == 0:
        # no points → no constraint can hit; no constraints → vacuous truth
        return empty(jnp.full((n_queries, s, num_docs), n_constraints == 0))
    cov = _signed(jnp.stack([_pad_cov(cov[q]) for q in range(n_queries)]))
    r_pad = cov.shape[3]
    n_pb = pl.cdiv(p, point_block)
    n_db = pl.cdiv(num_docs, doc_block)
    n_steps = grid_steps(1, 1, p, num_docs, point_block, doc_block)[0]
    shift = _step_shift(n_pb, n_db)
    padded_p = n_pb * point_block
    padded_d = n_db * doc_block
    words = jnp.zeros((s, 8, padded_p), jnp.int32)
    words = words.at[:, :4, :p].set(_signed(pts))
    words = words.at[:, 4, :].set(-1).at[:, 4, :p].set(rows)
    steps = _band_schedule(words[:, 4, :], n_steps, point_block, doc_block,
                           n_db, shift)

    def doc_map(q, i, w, steps_ref):
        return q, i, 0, steps_ref[i * n_steps + w] >> shift

    def point_map(q, i, w, steps_ref):
        pb = steps_ref[i * n_steps + w] >> _FLAG_BITS
        return i, 0, pb & ((1 << (shift - _FLAG_BITS)) - 1)

    tbl_shape = jax.ShapeDtypeStruct(
        (n_queries, s, n_constraints, padded_d), jnp.int32)
    tbl_spec = pl.BlockSpec((1, 1, n_constraints, doc_block), doc_map)
    outs = pl.pallas_call(
        functools.partial(_refine_kernel, doc_block=doc_block,
                          n_constraints=n_constraints, n_steps=n_steps,
                          shift=shift),
        name="refine_tracks_multi",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n_queries, s, n_steps),
            in_specs=[
                pl.BlockSpec((1, 8, point_block), point_map),
                pl.BlockSpec((1, n_constraints, 8, r_pad),
                             lambda q, *_: (q, 0, 0, 0)),
            ],
            out_specs=[pl.BlockSpec((1, 1, 1, doc_block), doc_map)]
            + [tbl_spec] * n_tables,
        ),
        out_shape=[jax.ShapeDtypeStruct((n_queries, s, 1, padded_d),
                                        jnp.int32)]
        + [tbl_shape] * n_tables,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(steps, words, cov)
    mask = outs[0][:, :, 0, :num_docs] == full
    if not n_tables:
        return mask
    tables = [o[..., :num_docs] for o in outs[1:]]
    words_out = tuple(_unsigned(o) for o in tables[:4])
    return (mask, *words_out, *tables[4:])


@functools.partial(jax.jit, static_argnames=("num_docs", "point_block",
                                             "doc_block", "interpret",
                                             "with_first_hits",
                                             "with_analytics"))
def refine_tracks_batched(pts: jnp.ndarray, rows: jnp.ndarray,
                          cov: jnp.ndarray, num_docs: int,
                          point_block: int = DEFAULT_POINT_BLOCK,
                          doc_block: int = DEFAULT_DOC_BLOCK,
                          interpret: bool = False,
                          with_first_hits: bool = False,
                          with_analytics: bool = False):
    """pts [S, 4, P] uint32, rows [S, P] int32 (−1 pad), cov [C, 8, R]
    uint32 → per-doc hit mask [S, num_docs] bool (wave-ragged doc counts
    zero-padded to ``num_docs`` by the caller; slice per shard): the
    one-query case of :func:`refine_tracks_multi`.

    ``with_first_hits`` grows the same fused pass with a per-(doc ×
    constraint) **first-hit** min-reduce and returns
    ``(mask, first_hi, first_lo)`` — uint32 ``[S, C, num_docs]`` word
    pairs, the lexicographic minimum (t_hi, t_lo) over each doc's points
    satisfying constraint c, (0xFFFFFFFF, 0xFFFFFFFF) when none.  Ordered
    (A-before-B) queries compare this table edge-wise; still one launch
    per wave.

    ``with_analytics`` (implies first hits) returns the full reduction
    family ``(mask, fh_hi, fh_lo, lh_hi, lh_lo, cnt)``: **last-hit**
    lexicographic max word pairs with a (0, 0) no-hit sentinel, and an
    int32 ``[S, C, num_docs]`` **hit-count** table — count/dwell verdicts
    are epilogue compares at the caller, same single launch per wave.
    """
    out = refine_tracks_multi(pts, rows, cov[None], num_docs,
                              point_block=point_block, doc_block=doc_block,
                              interpret=interpret,
                              with_first_hits=with_first_hits,
                              with_analytics=with_analytics)
    if with_analytics or with_first_hits:
        return tuple(o[0] for o in out)
    return out[0]


@functools.partial(jax.jit, static_argnames=("num_docs", "point_block",
                                             "doc_block", "interpret",
                                             "with_first_hits",
                                             "with_analytics"))
def refine_tracks(pts: jnp.ndarray, rows: jnp.ndarray, cov: jnp.ndarray,
                  num_docs: int, point_block: int = DEFAULT_POINT_BLOCK,
                  doc_block: int = DEFAULT_DOC_BLOCK,
                  interpret: bool = False, with_first_hits: bool = False,
                  with_analytics: bool = False):
    """Single-shard refine: pts [4, P], rows [P], cov [C, 8, R] →
    hit mask [num_docs] bool (+ uint32 first-hit word tables
    [C, num_docs] × 2 under ``with_first_hits``; the full
    (mask, fh, lh, cnt) reduction family under ``with_analytics``)."""
    out = refine_tracks_batched(pts[None], rows[None], cov, num_docs,
                                point_block=point_block,
                                doc_block=doc_block,
                                interpret=interpret,
                                with_first_hits=with_first_hits,
                                with_analytics=with_analytics)
    if with_analytics or with_first_hits:
        return tuple(o[0] for o in out)
    return out[0]
