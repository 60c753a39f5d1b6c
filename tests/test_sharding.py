"""Sharding rules: path matching, divisibility fallbacks, ZeRO-1/FSDP."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs.base import get_config
from repro.ml import sharding as sh
from repro.ml.model import ModelBundle, TrainConfig, _cache_spec_leaf
from repro.ml.transformer import LM


@pytest.fixture(scope="module")
def mesh16():
    # Shape-rule checks don't need real devices — abstract mesh suffices.
    from jax.sharding import AbstractMesh
    return AbstractMesh((16, 16), ("data", "model"))


def _specs_for(arch, mesh):
    cfg = get_config(arch).reduced()
    lm = LM(cfg)
    shape = jax.eval_shape(lm.init, jax.random.key(0))
    return sh.param_specs(shape, mesh), shape


def test_attention_tp_rules(mesh16):
    specs, shape = _specs_for("command_r_35b", mesh16)
    blk = specs["blocks"]["slot0"]
    # wq [G, D, H*hd] → out-dim on model; wo [G, H*hd, D] → in-dim
    assert blk["attn"]["wq"][-1] == "model"
    assert blk["attn"]["wo"][-2] == "model"
    assert blk["mlp"]["w_up"][-1] == "model"
    assert blk["mlp"]["w_down"][-2] == "model"
    # norms replicated
    assert specs["final_norm"]["scale"] == P()


def test_divisibility_fallback(mesh16):
    """Dims that don't divide the axis fall back or replicate (pjit
    rejects uneven shards)."""
    cfg = get_config("mixtral_8x7b")      # 8 experts on a 16-way axis
    lm = LM(cfg)
    shape = jax.eval_shape(lm.init, jax.random.key(0))
    specs = sh.param_specs(shape, mesh16)
    w_gate = specs["blocks"]["slot0"]["moe"]["experts"]["w_gate"]
    # E=8 can't shard 16 ways → the FFN dim (14336) takes the axis
    sizes = jax.tree_util.tree_leaves(
        shape)[0]  # just ensure no exception; check spec directly
    assert "model" in tuple(w_gate)
    assert w_gate[1] != "model"           # E dim NOT sharded


def test_ep_when_divisible(mesh16):
    cfg = get_config("jamba_v0_1_52b")    # 16 experts on 16-way axis
    lm = LM(cfg)
    shape = jax.eval_shape(lm.init, jax.random.key(0))
    specs = sh.param_specs(shape, mesh16)
    # find a moe slot
    for s in range(8):
        blk = specs["blocks"][f"slot{s}"]
        if "moe" in blk:
            assert blk["moe"]["experts"]["w_gate"][1] == "model"
            return
    raise AssertionError("no moe slot found")


def test_zero1_and_fsdp_extend(mesh16):
    specs, shape = _specs_for("qwen1_5_0_5b", mesh16)
    z = sh.extend_specs(specs, mesh16, shape, "data")
    w = z["blocks"]["slot0"]["attn"]["wq"]
    assert "data" in tuple(w) and "model" in tuple(w)


def test_cache_specs_head_vs_seq(mesh16):
    # qwen kv=16 divides → heads on model
    leaf = jax.ShapeDtypeStruct((24, 128, 16, 1024, 64), jnp.bfloat16)
    path = (jax.tree_util.DictKey("k"),)
    spec = _cache_spec_leaf(path, leaf, mesh16)
    assert spec[2] == "model"
    # command-r kv=8 does not divide 16 → cache length takes the axis
    leaf = jax.ShapeDtypeStruct((40, 128, 8, 32768, 128), jnp.bfloat16)
    spec = _cache_spec_leaf(path, leaf, mesh16)
    assert spec[2] is None and spec[3] == "model"
    # long-context B=1 → sequence-parallel over the batch axes too
    leaf = jax.ShapeDtypeStruct((40, 1, 8, 524288, 128), jnp.bfloat16)
    spec = _cache_spec_leaf(path, leaf, mesh16)
    assert spec[1] is None
    flat = []
    for ax in spec:
        if isinstance(ax, tuple):
            flat.extend(ax)
        elif ax:
            flat.append(ax)
    assert "data" in flat                 # context parallelism engaged


def test_constrain_noop_without_mesh():
    sh.set_active_mesh(None)
    x = jnp.ones((4, 4))
    y = sh.constrain(x, ("batch", "model"))
    assert y is x
