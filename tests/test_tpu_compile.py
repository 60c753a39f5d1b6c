"""Ahead-of-time compiles of the main path for a TPU v5e.

The TPU compiler ships with jax and compiles for a *described* chip where
none is attached, so a kernel the chip would refuse — a block not aligned
to the (8, 128) tiling, a reduction Mosaic cannot lower, a 64-bit type —
fails here at no chip time.  Shapes are the ones ``chip_smoke.py`` drives
at ``--scale 50``.  Nothing runs: the interpret-mode tests check results.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every test worker imports this file.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (AxisType, Mesh, NamedSharding, PartitionSpec,
                          SingleDeviceSharding)

from repro.kernels import bitset, compact, fused, merge, refine, segment_agg

# chip_smoke.py at --scale 50: SpeedObservations shards hold 50 k rows with
# ~24.5 k distinct roads each, 8 shards a wave (196 k offset-coded groups);
# Trips shards hold 6 k trips with ~115 k track points; queries carry 2
# constraints, cover tables 256 range slots; the server coalesces 8.
WAVE, PROBES = 8, 4
ROWS, GROUPS = 50_000, 196_000
TRIPS, POINTS, CONS, RANGES, QUERIES = 6_000, 115_000, 2, 256, 8


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip cannot be read back from the
        # persistent cache without that chip: keep the cache out of it
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        try:
            yield desc
        finally:
            jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def shape(topo):
    one = SingleDeviceSharding(topo.devices[0])
    return lambda dims, dtype: jax.ShapeDtypeStruct(dims, dtype,
                                                    sharding=one)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


def _kernel_cases():
    u32, i32 = jnp.uint32, jnp.int32
    words = (ROWS + 31) // 32
    tracks = [((WAVE, 4, POINTS), u32), ((WAVE, POINTS), i32),
              ((CONS, 8, RANGES), u32)]
    return {
        "bitmap_intersect": (bitset.bitmap_intersect,
                             [((PROBES, words), u32)]),
        "bitmap_intersect_batched": (bitset.bitmap_intersect_batched,
                                     [((WAVE, PROBES, words), u32)]),
        "compact": (compact.compact, [((ROWS,), jnp.bool_)]),
        "compact_batched": (compact.compact_batched,
                            [((WAVE, ROWS), jnp.bool_)]),
        "segment_agg": (lambda g, v: segment_agg.segment_agg(g, v, GROUPS),
                        [((WAVE * ROWS,), i32), ((WAVE * ROWS,), jnp.float32)]),
        "refine_tracks_batched": (
            lambda p, r, c: refine.refine_tracks_batched(p, r, c, TRIPS),
            tracks),
        "refine_tracks_batched_first_hits": (
            lambda p, r, c: refine.refine_tracks_batched(
                p, r, c, TRIPS, with_first_hits=True), tracks),
        "refine_tracks_batched_analytics": (
            lambda p, r, c: refine.refine_tracks_batched(
                p, r, c, TRIPS, with_analytics=True), tracks),
        "refine_tracks_multi": (
            lambda p, r, c: refine.refine_tracks_multi(
                p, r, c, TRIPS, with_analytics=True),
            tracks[:2] + [((QUERIES, CONS, 8, RANGES), u32)]),
        # a served batch of eight plain Q6/Q7 queries
        "refine_tracks_multi_q8": (
            lambda p, r, c: refine.refine_tracks_multi(p, r, c, TRIPS),
            tracks[:2] + [((QUERIES, CONS, 8, RANGES), u32)]),
    }


@pytest.mark.parametrize("name", sorted(_kernel_cases()))
def test_kernel_compiles_for_v5e(name, shape):
    fn, args = _kernel_cases()[name]
    hlo = _compile(fn, *[shape(d, t) for d, t in args])
    assert "tpu_custom_call" in hlo          # the Pallas kernel, lowered
    if name.startswith("refine"):
        # the banded walk: its step list leads the kernel's operands as
        # scalar prefetch, one int32 word per (shard, step)
        steps = WAVE * refine.grid_steps(1, 1, POINTS, TRIPS)[0]
        assert re.search(r"%refine_tracks_multi(\.\d+)? = .*"
                         rf"operand_layout_constraints={{s32\[{steps}\]",
                         hlo), steps


def _fused_cases():
    """name → (factory(shape) → (jitted program, args))."""
    u32, i32 = jnp.uint32, jnp.int32

    def agg(shape):
        words = (ROWS + 31) // 32
        return (fused._fused_fn("pallas", ROWS, (), GROUPS, False),
                [shape((WAVE, PROBES, words), u32), shape((WAVE,), i32),
                 None, None, None, shape((WAVE, ROWS), i32),
                 (shape((WAVE, ROWS), jnp.float32),)])

    def trips(edges=(), min_counts=(), dwells=()):
        def make(shape):
            words = (TRIPS + 31) // 32
            fn = fused._fused_fn("pallas", TRIPS, edges, 0, True, (),
                                 min_counts, dwells)
            return fn, [shape((WAVE, 3, words), u32), shape((WAVE,), i32),
                        shape((WAVE, 4, POINTS), u32),
                        shape((WAVE, POINTS), i32),
                        shape((CONS, 8, RANGES), u32), None, ()]
        return make

    def multi(shape):
        words = (TRIPS + 31) // 32
        fn = fused._fused_multi_fn("pallas", TRIPS,
                                   tuple(() for _ in range(QUERIES)), True)
        return fn, [shape((QUERIES, WAVE, 3, words), u32),
                    shape((WAVE,), i32), shape((WAVE, 4, POINTS), u32),
                    shape((WAVE, POINTS), i32),
                    shape((QUERIES, CONS, 8, RANGES), u32)]

    def postings(shape):
        return (fused._postings_bitmap,
                [shape((5_000,), i32), shape((TRIPS, 2), u32),
                 shape((TRIPS, 2), u32), shape((2,), u32), shape((2,), u32),
                 TRIPS])

    return {"fused_agg": agg, "fused_refine": trips(),
            "fused_ordered": trips(edges=((0, 1),)),
            "fused_at_least": trips(min_counts=(2, 1)),
            "fused_dwell": trips(min_counts=(1, 1), dwells=(600.0, None)),
            "fused_multi": multi, "postings_bitmap": postings}


#: the ``pallas_call`` names each fused program holds: the device trace
#: knows a stage by them (``chipbench/roofline/`` reads ``segment_agg``
#: and ``refine_tracks_multi``)
_SELECT = ("bitmap_intersect_batched", "mask_prefix_sum_batched")
_KERNELS = {"fused_agg": _SELECT + ("segment_agg",),
            "fused_multi": _SELECT + ("refine_tracks_multi",),
            "postings_bitmap": ()}


@pytest.mark.parametrize("name", sorted(_fused_cases()))
def test_fused_program_compiles_for_v5e(name, shape):
    fn, args = _fused_cases()[name](shape)
    hlo = fn.lower(*args).compile().as_text()
    if name != "postings_bitmap":             # pure XLA, no kernel
        assert "tpu_custom_call" in hlo
    for kernel in _KERNELS.get(name, _SELECT + ("refine_tracks_multi",)):
        assert re.search(rf"%{kernel}(\.\d+)? = ", hlo), kernel


def test_merge_compiles_on_four_chips(topo):
    """The partition merge's shard_map over a 4-device mesh: float64
    planes (the merge always accumulates float64) and the cross-device
    combine."""
    mesh = Mesh(np.array(topo.devices).reshape(4), ("part",),
                axis_types=(AxisType.Auto,))
    part = NamedSharding(mesh, PartitionSpec("part"))
    states, slots, groups = 20, 1, 30_000

    def plane(dtype, dims=(states, slots, groups)):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=part)

    with jax.enable_x64(True):
        hlo = merge._sharded_combine(mesh).lower(
            plane(jnp.int64), plane(jnp.float64), plane(jnp.float64),
            plane(jnp.float64), plane(jnp.float64),
            plane(jnp.bool_, (states, groups))).compile().as_text()
    assert "all-reduce" in hlo and "all-gather" in hlo
