"""The harness end to end at the program's reduced sizes on the CPU."""
import time

import pytest

from bench import harness, tracing
from repro.obs import scopes

#: per cell: the per-layer metrics a traced run reports, and the scope
#: that its family's mixer runs under
TRACED = {
    "smollm-tiny.tiny": ({"train_mfu", "device_idle_share.train",
                          "attn_roofline.train", "host_stall_ms.train"},
                         scopes.ATTENTION_CORE),
    "mamba2-tiny.tiny": ({"train_mfu", "device_idle_share.train",
                          "host_stall_ms.train"}, scopes.SSD_SCAN),
}


@pytest.mark.parametrize("cell", ["smollm-tiny.tiny", "mamba2-tiny.tiny"])
@pytest.mark.parametrize("trace", [False, True])
def test_tiny_cell_runs_and_is_correct(tiny_root, cell, trace):
    res = harness.run(cell, 2 ** 31 + 12345, 1.0, trace, time.perf_counter(),
                      root=tiny_root, allow_cpu=True)
    assert res["correct"], res["checks"]
    assert list(res)[-1] == "checks"
    assert res["attempted"] >= 1 and res["failed"] == 0
    if trace:
        want, mixer = TRACED[cell]
        metrics = {n: m["value"] for n, m in res["metrics"].items()}
        assert set(metrics) == want
        assert 0 < metrics["train_mfu"]
        assert metrics["host_stall_ms.train"] >= 0
        if "attn_roofline.train" in want:
            assert 0 < metrics["attn_roofline.train"] <= 100
        assert 0 < res["device"]["busy_s"] <= res["device"]["window_s"]
        assert set(res["device"]["step_memory_bytes"]) == {
            "arguments", "outputs", "temporaries", "aliased"}
        assert res["device"]["step_memory_bytes"]["arguments"] > 0
        breakdown = res["breakdown"]
        assert breakdown["device_ops"]
        assert dict(breakdown["scopes"]).get(mixer, 0) > 0
        spans = dict(breakdown["idle_by_span"])
        assert 0 < len(spans) <= tracing.TOP
        assert any(n.startswith("train.") for n in spans)
        assert res["attempted"] == harness.TRACE_STEPS
    else:
        assert set(res["metrics"]) == {"train_tokens_per_s", "setup_s"}
        assert res["metrics"]["setup_s"]["value"] > 0
        assert "breakdown" not in res
        assert set(res["device"]) == {"platform", "kind", "count",
                                      "memory_peak_bytes"}
