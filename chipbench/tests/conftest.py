"""Small copies of the benchmark's configurations, for CPU tests."""
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

#: sizes a test run can hold; every other key is the configuration's own
SMALL = {
    "sec6_speedobs": {"rows": 8000, "shards": 4},
    "sec6_trips": {"trips": 1200, "shards": 4},
}


def small_config(name: str) -> dict:
    with open(ROOT / "chipbench" / "configs" / f"{name}.json") as fh:
        cfg = json.load(fh)
    cfg.update(SMALL[name])
    cfg["roads"] = dict(cfg["roads"], count=1500)
    return cfg


def traffic(name: str) -> dict:
    with open(ROOT / "chipbench" / "traffic" / f"{name}.json") as fh:
        return json.load(fh)


@pytest.fixture
def small_run(tmp_path, monkeypatch):
    """``run(cell, seed, seconds=1, trace=0)`` drives chipbench.run at the
    small sizes on the CPU, skipping the look for a chip; returns the
    result dict."""
    import jax
    import chipbench.run as R
    orig = R.resolve

    def resolve(name):
        bench, cell, cfg, tr = orig(name)
        cfg = dict(cfg, **SMALL[cfg["name"]])
        cfg["roads"] = dict(cfg["roads"], count=1500)
        return bench, cell, cfg, tr

    monkeypatch.setattr(R, "resolve", resolve)
    monkeypatch.setattr(R, "DATA_DIR", tmp_path / "data")
    monkeypatch.setattr("repro.compile_cache.enable_compile_cache",
                        lambda: "off")
    monkeypatch.setattr(R, "CACHE_DIR", tmp_path / "jax_cache")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jc"))

    def run(cell, seed, seconds=1.0, trace=0):
        args = R.argparse.Namespace(workload=cell, seed=seed,
                                    seconds=seconds, trace=trace)
        return R.run(args, require=lambda n: jax.devices()[:n])

    # run() points JAX's persistent cache at the checkout: undo it for the
    # tests that follow in this process
    saved = (jax.config.jax_compilation_cache_dir,
             jax.config.jax_persistent_cache_min_compile_time_secs)
    yield run
    from jax.experimental.compilation_cache import compilation_cache
    jax.config.update("jax_compilation_cache_dir", saved[0])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", saved[1])
    compilation_cache.reset_cache()
