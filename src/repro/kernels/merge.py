"""Cross-partition merge of segment-aggregate states (the Mixer combine).

One query executed over P partitions produces per-shard segment states —
for each value slot a ``(count, sum, sum_sq[, min, max])`` vector over
that shard's group key space.  This module combines the states, aligned
to the union key space by the host, in a single device dispatch:

* counts / sums / sums-of-squares accumulate **sequentially in states
  order** (an in-order ``fori_loop``, not a tree reduce) so the float64
  result is bit-equal to the numpy loop-over-partitions oracle and to
  the P=1 sequential reference — absent groups contribute the additive
  identity 0, which changes no bits;
* min / max planes reduce element-wise against ±inf identities;
* per-group presence masks OR.

Under a multi-device ``"part"`` mesh the leading states axis is sharded
with ``shard_map`` and the per-device partial accumulations combine via
``psum`` (min / max gather the per-device planes and reduce them
locally).  On a one-device host the mesh axis has
size 1, so the shard_map path is still exercised while the arithmetic
stays the exact sequential order — CPU CI emulates P>1 partitions
without changing a single result bit.  Precision note: like the Mixer's
host merge, the combine always accumulates float64 regardless of
``REPRO_KERNEL_IMPL`` (the per-shard *aggregation* is where the
float32-on-MXU trade lives, not the merge).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

__all__ = ["merge_partials"]


def _combine_local(cnt, s, s2, mn, mx, msk):
    """Sequential in-order accumulation over the leading states axis."""
    n_states = cnt.shape[0]

    def body(i, acc):
        c, a, a2, lo, hi, m = acc
        return (c + cnt[i], a + s[i], a2 + s2[i],
                jnp.minimum(lo, mn[i]), jnp.maximum(hi, mx[i]),
                m | msk[i])

    init = (jnp.zeros_like(cnt[0]), jnp.zeros_like(s[0]),
            jnp.zeros_like(s2[0]),
            jnp.full_like(mn[0], jnp.inf),
            jnp.full_like(mx[0], -jnp.inf),
            jnp.zeros_like(msk[0]))
    return jax.lax.fori_loop(0, n_states, body, init)


@functools.lru_cache(maxsize=None)
def _sharded_combine(mesh):
    spec = P("part")

    @jax.jit
    # check_vma=False: the gathered min/max planes are replicated, which
    # shard_map cannot infer through all_gather
    @functools.partial(shard_map, mesh=mesh,
                       in_specs=(spec,) * 6,
                       out_specs=(P(),) * 6, check_vma=False)
    def run(cnt, s, s2, mn, mx, msk):
        c, a, a2, lo, hi, m = _combine_local(cnt, s, s2, mn, mx, msk)
        # in-order within a device, then a cross-device combine.  With a
        # size-1 axis (CPU CI's emulated mesh) this is exactly the
        # sequential oracle order; counts (ints) and min/max/OR are exact
        # at any axis size, float sums become per-device subtotals on a
        # real multi-device mesh (the usual tree-reduce trade)
        # min/max gather the per-device planes and reduce locally: a TPU
        # all-reduce lowers only sums for 64-bit floats
        return (jax.lax.psum(c, "part"), jax.lax.psum(a, "part"),
                jax.lax.psum(a2, "part"),
                jax.lax.all_gather(lo, "part").min(axis=0),
                jax.lax.all_gather(hi, "part").max(axis=0),
                jax.lax.psum(m.astype(jnp.int32), "part") > 0)

    return run


def merge_partials(cnt, s, s2, mn, mx, msk, mesh=None):
    """Combine aligned segment-state stacks.

    ``cnt/s/s2/mn/mx`` are ``[S, K, G]`` (states x value slots x union
    groups), ``msk`` is ``[S, G]`` bool.  Returns the same tuple with the
    leading axis reduced.  ``mesh`` is a 1-D ``"part"`` mesh (see
    ``launch.mesh.make_exec_mesh``); S is zero-padded to a multiple of
    the axis size (identity states: zeros / +-inf / False).
    """
    cnt = jnp.asarray(cnt)
    s = jnp.asarray(s, jnp.float64)
    s2 = jnp.asarray(s2, jnp.float64)
    mn = jnp.asarray(mn, jnp.float64)
    mx = jnp.asarray(mx, jnp.float64)
    msk = jnp.asarray(msk, bool)
    if mesh is None:
        return _combine_local(cnt, s, s2, mn, mx, msk)
    axis = mesh.shape["part"]
    pad = (-cnt.shape[0]) % axis
    if pad:
        def _pad(x, fill):
            width = ((0, pad),) + ((0, 0),) * (x.ndim - 1)
            return jnp.pad(x, width, constant_values=fill)

        cnt, s, s2 = _pad(cnt, 0), _pad(s, 0.0), _pad(s2, 0.0)
        mn, mx = _pad(mn, jnp.inf), _pad(mx, -jnp.inf)
        msk = _pad(msk, False)
    return _sharded_combine(mesh)(cnt, s, s2, mn, mx, msk)
