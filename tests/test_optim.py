"""Optimizer stack: AdamW math, clipping, schedules, EF compression."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.ml.optim import (adamw_init, adamw_update, clip_by_global_norm,
                            compress_ef, cosine_schedule, ef_init)


def test_adamw_matches_reference_math():
    params = {"w": jnp.asarray([[1.0, -2.0]]), "b": jnp.asarray([0.5])}
    grads = {"w": jnp.asarray([[0.1, 0.2]]), "b": jnp.asarray([-0.3])}
    st = adamw_init(params)
    lr, b1, b2, eps, wd = 0.1, 0.9, 0.95, 1e-8, 0.1
    new_p, new_st = adamw_update(params, grads, st, lr, b1=b1, b2=b2,
                                 eps=eps, weight_decay=wd)
    # manual step 1
    for k in ("w", "b"):
        g = np.asarray(grads[k], np.float64)
        m = (1 - b1) * g
        v = (1 - b2) * g * g
        upd = (m / (1 - b1)) / (np.sqrt(v / (1 - b2)) + eps)
        if np.asarray(params[k]).ndim >= 2:
            upd = upd + wd * np.asarray(params[k])
        want = np.asarray(params[k]) - lr * upd
        np.testing.assert_allclose(np.asarray(new_p[k]), want, rtol=1e-5)
    assert int(new_st["step"]) == 1


def test_clip_by_global_norm():
    grads = {"a": jnp.full((4,), 3.0), "b": jnp.full((4,), 4.0)}
    clipped, gn = clip_by_global_norm(grads, 1.0)
    assert float(gn) == pytest.approx(10.0)
    total = np.sqrt(sum(float(jnp.sum(g ** 2))
                        for g in jax.tree_util.tree_leaves(clipped)))
    assert total == pytest.approx(1.0, rel=1e-5)


def test_cosine_schedule_shape():
    lr = cosine_schedule(1e-3, warmup=10, total=100, min_ratio=0.1)
    assert float(lr(0)) == 0.0
    assert float(lr(10)) == pytest.approx(1e-3, rel=1e-5)
    assert float(lr(100)) == pytest.approx(1e-4, rel=1e-3)
    assert float(lr(55)) < float(lr(20))


def test_ef_compression_error_feedback():
    """Quantization error must be carried, not lost (EF21 property)."""
    rng = np.random.default_rng(0)
    g = {"w": jnp.asarray(rng.normal(size=(64, 64)).astype(np.float32))}
    err = ef_init(g)
    # accumulate K compressed steps; sum of dequantized ≈ sum of true
    total_true = np.zeros((64, 64), np.float32)
    total_deq = np.zeros((64, 64), np.float32)
    for k in range(20):
        gk = {"w": g["w"] * (1.0 + 0.01 * k)}
        deq, err = compress_ef(gk, err)
        total_true += np.asarray(gk["w"])
        total_deq += np.asarray(deq["w"])
    # residual bounded by one quantization step, NOT accumulating
    resid = np.abs(total_true - total_deq).max()
    scale = np.abs(g["w"]).max() / 127.0
    assert resid < 3 * scale
    # int8 payload: 4× smaller on the wire
    q_bytes = g["w"].size * 1
    f_bytes = g["w"].size * 4
    assert f_bytes / q_bytes == 4


def test_compressed_psum_shard_map():
    """int8 all-gather + local reduce ≈ fp32 psum (within quant error)."""
    import jax
    from jax import shard_map
    from jax.sharding import PartitionSpec as P
    from repro.ml.optim import compressed_psum

    devs = jax.devices()
    if len(devs) < 1:
        pytest.skip("no devices")
    mesh = jax.make_mesh((1,), ("data",))
    x = jnp.asarray(np.random.default_rng(0).normal(
        size=(8,)).astype(np.float32))

    f = shard_map(lambda v: compressed_psum(v, "data"), mesh=mesh,
                  in_specs=P(), out_specs=P(), check_vma=False)
    got = f(x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(x), atol=2e-2,
                               rtol=2e-2)
