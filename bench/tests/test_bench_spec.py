"""Cells, configurations and per-layer metrics are found by name; adding
one is adding files and ``BENCHMARK.json`` entries."""
import json
import os
import shutil
import subprocess
import sys
import time

import pytest

from bench import harness, spec
from bench_tiny import ROOT

NEW_READER = '''
def read(ctx):
    red = ctx["reduced"]
    return None if red is None else float(red.steps)
'''


def test_benchmark_json_keeps_to_its_contract():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    names = {c["name"] for c in bench["configs"]}
    assert names == {w["config"] for w in bench["workloads"]}
    for w in bench["workloads"]:
        cell = spec.load_cell(w["name"], ROOT)
        assert cell.config["name"] == w["config"]
        numbers = {"loss", "loss_first", "first_grad", "first_grad_median",
                   "update", "update_median"}
        assert cell.limits["batch"] == 0
        assert set(cell.limits) - {"batch"} <= numbers
        assert {m["name"] for m in cell.end_to_end} == {
            "train_tokens_per_s", "setup_s"}
        for m in cell.per_layer:
            assert cell.reader(m["name"]).read
        assert len(w["why"]) <= 200
    for c in bench["configs"]:
        f = json.loads((ROOT / c["file"]).read_text())
        assert f["source"] == c["source"]
        assert f["reduced"] == c["reduced"]
        for key in c["reduced"]:
            assert not key.endswith(("_dim", "_rank"))


def test_new_cell_config_and_metric_are_new_files(tiny_root):
    """The tiny cells are new files and entries in a copy of the benchmark;
    here a per-layer metric is added the same way, and a traced run reports
    it without any edit to an existing file."""
    bench = json.loads((tiny_root / "BENCHMARK.json").read_text())
    (tiny_root / "bench/metrics/traced_steps.py").write_text(NEW_READER)
    bench["per_layer"].append({
        "name": "traced_steps", "unit": "steps", "better": "higher",
        "source": "device_trace", "layer": "trainer loop",
        "moves": "train_tokens_per_s", "workloads": ["smollm-tiny.tiny"]})
    (tiny_root / "BENCHMARK.json").write_text(json.dumps(bench))
    res = harness.run("smollm-tiny.tiny", 99, 0.5, True, time.perf_counter(),
                      root=tiny_root, allow_cpu=True)
    assert res["correct"]
    assert res["metrics"]["traced_steps"]["value"] == harness.TRACE_STEPS
    other = spec.load_cell("mamba2-tiny.tiny", tiny_root)
    assert "traced_steps" not in {m["name"] for m in other.per_layer}


def test_unknown_names_and_devices_are_errors(tiny_root):
    with pytest.raises(spec.SpecError):
        spec.load_cell("no-such-cell", tiny_root)
    with pytest.raises(spec.SpecError):
        spec.peaks("TPU v99", tiny_root)
    assert spec.peaks("TPU v5 lite", ROOT)["bf16_flops_per_s"] == 197e12


def test_program_that_differs_from_its_file_is_refused(tiny_root):
    path = tiny_root / "bench/configs/smollm-tiny.json"
    f = json.loads(path.read_text())
    f["hidden_size"] += 1
    path.write_text(json.dumps(f))
    with pytest.raises(spec.SpecError):
        harness.run("smollm-tiny.tiny", 1, 0.5, False, time.perf_counter(),
                    root=tiny_root, allow_cpu=True)


def _command(cwd, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "smollm-360m.train.seq2k", "--seed", "3", "--seconds", "1",
         "--trace", "0", *extra], cwd=cwd, env=env, capture_output=True,
        text=True, timeout=300)


def test_the_command_refuses_the_cpu():
    p = _command(ROOT)
    assert p.returncode == 3 and p.stdout == ""
    assert "needs a TPU" in p.stderr


def test_the_command_fails_with_only_the_benchmark(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    p = _command(tmp_path)
    assert p.returncode != 0 and p.stdout == ""
