"""The harness end to end on the CPU: it refuses to run without a TPU,
finds a new traffic mix by name alone, and with the look for a chip
skipped it judges answers: sound ones correct, broken ones not."""
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from .conftest import ROOT

#: every cell of the benchmark, driven at the small sizes
CELLS = [w["name"] for w in
         json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"]]
A, C = "speedobs.cov.analyst", "trips.tess.analyst"


def test_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(ROOT / "chipbench" / "run.py"), "--workload",
         A, "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert not any(line.startswith("{") for line in proc.stdout.splitlines())
    assert "TPU" in proc.stderr


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(small_run, cell):
    res = small_run(cell, 2**33 + 1)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    assert set(res["metrics"]) == {
        m["name"] for m in bench["end_to_end"]
        if cell in m.get("workloads", [cell])}
    assert list(res)[-1] == "checks"


def test_traced_run_reports_per_layer_metrics(small_run):
    res = small_run(C, 7, trace=1)
    assert res["correct"]
    assert "dispatches_per_query" in res["metrics"]
    assert "p50_ms" not in res["metrics"]
    assert res["device"]["window_s"] > 0
    # no device plane on the CPU: nothing to read for rooflines
    assert "refine_roofline" not in res["metrics"]


def test_new_traffic_file_is_found_by_name(tmp_path, monkeypatch, small_run):
    """A cell added by data alone: a traffic file and a BENCHMARK.json
    entry, nothing in the harness edited."""
    import chipbench.run as R
    root = tmp_path / "checkout"
    (root / "chipbench" / "traffic").mkdir(parents=True)
    shutil.copytree(ROOT / "chipbench" / "configs",
                    root / "chipbench" / "configs")
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    mix = json.loads((ROOT / "chipbench" / "traffic"
                      / "tess_analyst.json").read_text())
    mix["queries"] = mix["queries"][:1]
    (root / "chipbench" / "traffic" / "q6_only.json").write_text(
        json.dumps(mix))
    bench["workloads"].append({"name": "trips.q6", "config": "sec6_trips",
                               "traffic": "q6_only", "chips": 1,
                               "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    monkeypatch.setattr(R, "ROOT", root)
    res = small_run("trips.q6", 3)
    assert res["correct"] and res["attempted"] > 0


def _break_half_of_each_wave(monkeypatch):
    """Half of every wave's shards left out where the fused dispatch
    returns: their selections come back empty, their partial sums zero."""
    from repro.exec.backend import JaxBackend
    fused, multi = JaxBackend.run_wave_fused, JaxBackend.run_wave_fused_multi

    def drop(n_cands, ids_list):
        keep = (len(ids_list) + 1) // 2
        return (list(n_cands), [ids if i < keep else ids[:0]
                                for i, ids in enumerate(ids_list)])

    def run_wave_fused(self, shards, *a, **kw):
        out = fused(self, shards, *a, **kw)
        if out is None:
            return out
        n_cands, ids_list, seg = out
        n_cands, ids_list = drop(n_cands, ids_list)
        if seg is not None:
            keep = (len(seg) + 1) // 2
            seg = [s if i < keep else (s[0], [tuple(np.zeros_like(x)
                                                    for x in slot)
                                              for slot in s[1]])
                   for i, s in enumerate(seg)]
        return n_cands, ids_list, seg

    def run_wave_fused_multi(self, *a, **kw):
        out = multi(self, *a, **kw)
        return out if out is None else [drop(*q) for q in out]

    monkeypatch.setattr(JaxBackend, "run_wave_fused", run_wave_fused)
    monkeypatch.setattr(JaxBackend, "run_wave_fused_multi",
                        run_wave_fused_multi)


@pytest.mark.parametrize("cell", CELLS)
def test_half_the_batch_left_out_is_not_correct(small_run, monkeypatch,
                                                cell):
    _break_half_of_each_wave(monkeypatch)
    res = small_run(cell, 11)
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("cell", CELLS)
def test_answer_altered_where_produced_is_not_correct(small_run,
                                                      monkeypatch, cell):
    """One value of one answer changed as the program hands it over."""
    from chipbench.kinds import tesseract, variability
    for kind, col in ((variability, "cov"), (tesseract, "duration_s")):
        orig = kind.answer

        def altered(result, orig=orig, col=col):
            out = orig(result)
            if out[col].size:
                out[col] = out[col].copy()
                out[col][0] += 0.5
            return out
        monkeypatch.setattr(kind, "answer", altered)
    res = small_run(cell, 13)
    assert not res["correct"], res["checks"]
