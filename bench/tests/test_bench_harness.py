"""The harness end to end at the program's reduced sizes on the CPU."""
import time

import pytest

from bench import harness


@pytest.mark.parametrize("cell", ["smollm-tiny.tiny", "mamba2-tiny.tiny"])
@pytest.mark.parametrize("trace", [False, True])
def test_tiny_cell_runs_and_is_correct(tiny_root, cell, trace):
    res = harness.run(cell, 2 ** 31 + 12345, 1.0, trace, time.perf_counter(),
                      root=tiny_root, allow_cpu=True)
    assert res["correct"], res["checks"]
    assert list(res)[-1] == "checks"
    assert res["attempted"] >= 1 and res["failed"] == 0
    if trace:
        assert set(res["metrics"]) == {"train_mfu", "device_idle_share.train"}
        assert 0 < res["metrics"]["train_mfu"]["value"]
        assert 0 < res["device"]["busy_s"] <= res["device"]["window_s"]
        assert res["breakdown"]["device_ops"]
        assert res["attempted"] == harness.TRACE_STEPS
    else:
        assert set(res["metrics"]) == {"train_tokens_per_s", "setup_s"}
        assert res["metrics"]["setup_s"]["value"] > 0
