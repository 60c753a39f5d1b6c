"""Structural dry-run check on a tiny forced-device mesh.

The full 512-device sweep runs via ``python -m repro.launch.dryrun`` (see
EXPERIMENTS §Dry-run).  This test proves the machinery — forced host
devices, mesh build, pjit lowering with our shardings, HLO analysis — in a
*subprocess* (the device count must be set before jax initializes, which
pytest's process already did)."""
import json
import os
import subprocess
import sys

import pytest

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax
from dataclasses import replace
from repro.configs.base import SHAPES, ShapeConfig, get_config
from repro.ml.model import ModelBundle, TrainConfig
from repro.launch.hlo_analysis import analyze_hlo
from repro.launch.mesh import make_local_mesh

mesh = make_local_mesh(2, 4)
assert len(jax.devices()) == 8

cfg = get_config("qwen1_5_0_5b").reduced()
shape = ShapeConfig("tiny_train", 64, 8, "train")
mb = ModelBundle(cfg, mesh, impl="reference",
                 train_cfg=TrainConfig(remat="full", loss_chunk=32,
                                       zero1=True))
lowered = mb.lower_train(shape)
compiled = lowered.compile()
mem = compiled.memory_analysis()
res = analyze_hlo(compiled.as_text())
print(json.dumps({
    "temp_bytes": mem.temp_size_in_bytes,
    "flops": res["flops_per_device"],
    "coll": res["collective_bytes"],
    "warnings": len(res["warnings"]),
}))

# decode path too
shape_d = ShapeConfig("tiny_decode", 64, 8, "decode")
mb.lower_decode(shape_d).compile()
print("DECODE_OK")
"""


@pytest.mark.slow
def test_dryrun_machinery_on_8_devices():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    out = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                         capture_output=True, text=True, timeout=500,
                         cwd=os.path.dirname(os.path.dirname(
                             os.path.abspath(__file__))))
    assert out.returncode == 0, out.stderr[-3000:]
    line = out.stdout.strip().splitlines()
    stats = json.loads(line[0])
    assert stats["flops"] > 0
    assert stats["coll"] > 0          # model-axis TP must communicate
    assert "DECODE_OK" in out.stdout
