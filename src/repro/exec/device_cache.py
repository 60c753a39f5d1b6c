"""Device-resident column buffers for the jax execution backend.

The per-shard hot loop used to ship every operand host→device on each
query.  The stable operands — a shard's :class:`~repro.fdb.columnar.Column`
value buffers, its valid-doc bitmap, and the ``spacetime`` index postings
arrays — never change after an FDb is built, so the jax backend puts them
on device **once per FDb open** (:meth:`JaxBackend.prime_fdb`) and reuses
the buffers across queries: the selective column read after filter→compact
gathers from the resident buffers instead of re-uploading the columns.

The cache is keyed by host-array identity.  A cached entry pins the host
array (so its ``id`` cannot be recycled), which is why only *priming*
inserts: transient arrays (probe bitmaps, residual masks, derived value
columns) pass through untouched.

Identity keying is also what makes priming **incremental for streaming
ingestion**: successive :meth:`~repro.fdb.streaming.StreamingFDb.snapshot`
generations share their sealed/delta ``Shard`` objects, so re-priming a
new generation re-uploads nothing that is already resident — only the
fresh delta buffers cost a host→device copy (``put`` on a known id is a
dict hit).  ``stats()["buffers"]`` therefore grows by exactly the delta
between generations, which the streaming tests assert.

64-bit buffers (int64/float64/uint64) go to the device as their uint32
bit words, shape ``[..., 2]`` in (lo, hi) order (:func:`device_form`).  The
parity contract is byte-identical results against the numpy oracle: a
TPU emulates float64 with two float32s, which would round the values, and
a put without x64 would truncate them to 32 bits.  Word buffers move
every bit unchanged, and readers view them back on the host.

On top of the identity-keyed buffers the cache holds **keyed derived
entries** (:meth:`put_keyed` / :meth:`get_keyed`): wave-stacked buffers the
fused pipeline derives from several primed arrays at once — stacked refine
track words per (FDb, wave partition), offset-coded group-code stacks,
value stacks, factorize results.  Keys are flat tuples whose int elements
are the ``id``s of the primed source arrays, so :meth:`drop` evicts every
derived entry alongside its sources when an FDb is collected.  Keyed
entries do not count toward ``len()`` / ``stats()["buffers"]`` — those
remain the primed-buffer census the priming tests assert.
"""
from __future__ import annotations

import threading
from typing import Dict, Optional, Tuple

import numpy as np

__all__ = ["DeviceCache", "device_form", "host_form"]


def device_form(arr: np.ndarray) -> np.ndarray:
    """The array a buffer is kept as on device: 64-bit dtypes as their
    uint32 words ``[..., 2]`` (a view, no copy), everything else as is."""
    if arr.dtype.itemsize != 8:
        return arr
    arr = np.ascontiguousarray(arr)
    return arr.view(np.uint32).reshape(arr.shape + (2,))


def host_form(words, dtype) -> np.ndarray:
    """Inverse of :func:`device_form` for a buffer read back from device."""
    words = np.asarray(words)
    dtype = np.dtype(dtype)
    if dtype.itemsize != 8:
        return words.astype(dtype, copy=False)
    words = np.ascontiguousarray(words, dtype=np.uint32)
    return words.view(dtype).reshape(words.shape[:-1])


class DeviceCache:
    """Identity-keyed host→device buffer cache (insert via :meth:`put`).

    All mutations run under one RLock: the serve layer opens and closes
    FDbs from worker threads while the scheduler primes waves, so put /
    drop / clear race without it.  The device put itself (host→device
    copy) stays outside the lock — only dict bookkeeping is guarded, and
    a duplicate concurrent put of the same array is harmless (last write
    wins; both device buffers alias the same bytes).
    """

    def __init__(self, jax_module):
        self._jax = jax_module
        self._jnp = jax_module.numpy
        self._lock = threading.RLock()
        # id(host array) → (host array pin, device buffer)
        self._buffers: Dict[int, Tuple[np.ndarray, object]] = {}
        # flat tuple key (tag, *source ids, ...) → derived stacked value
        self._keyed: Dict[tuple, object] = {}
        self.hits = 0
        self.misses = 0
        self.keyed_hits = 0
        #: buffers *eagerly* evicted on streaming snapshot turnover — a
        #: replaced generation's exclusive buffers (its memtable-tail
        #: shard) retired at re-prime time instead of waiting for the old
        #: snapshot's GC finalizer (see ``JaxBackend.prime_fdb``)
        self.retired_buffers = 0

    def __len__(self) -> int:
        with self._lock:
            return len(self._buffers)

    def nbytes(self) -> int:
        """Host-side bytes of everything resident (device mirror is 1:1)."""
        with self._lock:
            return sum(a.nbytes for a, _ in self._buffers.values())

    def put(self, arr: Optional[np.ndarray]):
        """Make ``arr`` device-resident; returns the device buffer."""
        if arr is None:
            return None
        key = id(arr)
        with self._lock:
            hit = self._buffers.get(key)
        if hit is not None:
            return hit[1]
        dev = self._jnp.asarray(device_form(arr))
        with self._lock:
            self._buffers[key] = (arr, dev)
        return dev

    def get(self, arr: np.ndarray):
        """Device buffer for ``arr`` if primed, else None (and count it)."""
        with self._lock:
            hit = self._buffers.get(id(arr))
            if hit is not None:
                self.hits += 1
                return hit[1]
            self.misses += 1
            return None

    def put_keyed(self, key: tuple, value) -> None:
        """Store a derived wave-stacked entry under a flat tuple key whose
        int elements are primed-source ``id``s (see module docstring)."""
        with self._lock:
            self._keyed[key] = value

    def get_keyed(self, key: tuple):
        """Derived entry for ``key`` if staged, else None (hits counted —
        the prefetch tests read ``keyed_hits``)."""
        with self._lock:
            hit = self._keyed.get(key)
            if hit is not None:
                self.keyed_hits += 1
            return hit

    def drop(self, keys, retired: bool = False) -> int:
        """Evict entries by key id (used by per-FDb finalizers so buffers
        of a collected FDb do not stay pinned forever).  Derived keyed
        entries referencing a dropped source id go with it.  Returns the
        number of buffers actually evicted; ``retired=True`` counts them
        on ``retired_buffers`` (the eager snapshot-turnover path)."""
        dropped = set(keys)
        evicted = 0
        with self._lock:
            for key in keys:
                if self._buffers.pop(key, None) is not None:
                    evicted += 1
            if self._keyed:
                self._keyed = {
                    k: v for k, v in self._keyed.items()
                    if not any(isinstance(e, int) and e in dropped for e in k)}
            if retired:
                self.retired_buffers += evicted
        return evicted

    def clear(self) -> None:
        with self._lock:
            self._buffers.clear()
            self._keyed.clear()
            self.hits = 0
            self.misses = 0
            self.keyed_hits = 0
            self.retired_buffers = 0

    def stats(self) -> Dict[str, int]:
        with self._lock:
            return {"buffers": len(self._buffers),
                    "nbytes": sum(a.nbytes
                                  for a, _ in self._buffers.values()),
                    "keyed": len(self._keyed), "hits": self.hits,
                    "misses": self.misses, "keyed_hits": self.keyed_hits,
                    "retired_buffers": self.retired_buffers}
