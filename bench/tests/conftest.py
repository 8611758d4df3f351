"""A tiny copy of the benchmark that runs on the CPU in seconds.

``tiny_root`` copies ``bench/`` into a temporary checkout, adds a cell per
family at the program's reduced sizes (new files and ``BENCHMARK.json``
entries, nothing edited), a CPU line in its peak table, and registers the
reduced architectures under their own names in the program's registry.
"""
import json
import shutil

import pytest

from bench_tiny import ROOT, TINY_LIMITS, TINY_TRAFFIC, tiny_config


@pytest.fixture
def tiny_root(tmp_path, monkeypatch):
    from repro.configs import registry

    root = tmp_path / "checkout"
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    peaks = json.loads((root / "bench/peaks.json").read_text())
    peaks["devices"]["cpu"] = {"bf16_flops_per_s": 1e12,
                               "hbm_bytes_per_s": 1e11, "source": "test"}
    (root / "bench/peaks.json").write_text(json.dumps(peaks))
    (root / "bench/traffic/tiny.json").write_text(json.dumps(TINY_TRAFFIC))
    for family in ("dense", "ssm"):
        arch, f = tiny_config(family)
        monkeypatch.setitem(registry.ARCHS, arch.name, arch)
        (root / f"bench/configs/{arch.name}.json").write_text(json.dumps(f))
        cell = f"{arch.name}.tiny"
        (root / f"bench/limits/{cell}.json").write_text(
            json.dumps({"limits": TINY_LIMITS}))
        bench["configs"].append({"name": arch.name, "source": f["source"],
                                 "file": f"bench/configs/{arch.name}.json",
                                 "reduced": [], "why": "test"})
        bench["workloads"].append({"name": cell, "config": arch.name,
                                   "traffic": "tiny", "chips": 1,
                                   "why": "test"})
        for m in bench["per_layer"]:
            m["workloads"].append(cell)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                       str(tmp_path / "jax-cache"))
    import jax
    before = jax.config.jax_persistent_cache_min_compile_time_secs
    yield root
    jax.config.update("jax_persistent_cache_min_compile_time_secs", before)
