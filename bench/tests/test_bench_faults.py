"""A run with its timed path broken underneath reads ``correct`` false:
once for each fault a one-chip training cell can have."""
import time

import pytest

from bench import harness


def _broken_step(monkeypatch, fault):
    from repro.runtime import steps

    real = steps.make_train_step

    def make(*a, **k):
        step = real(*a, **k)

        def broken(state, batch):
            if fault == "half_batch":  # the mean over half the rows
                batch = {x: v[: v.shape[0] // 2] for x, v in batch.items()}
            new, metrics = step(state, batch)
            return (state if fault == "unchanged_state" else new), metrics
        return broken

    monkeypatch.setattr(steps, "make_train_step", make)


def _altered_token(monkeypatch):
    from repro.data.pipeline import PackedLoader

    real = PackedLoader.batch

    def batch(self, step, rank=0, n_ranks=1):
        out = real(self, step, rank, n_ranks)
        out["tokens"] = out["tokens"].copy()
        out["tokens"][0, 5] = (out["tokens"][0, 5] + 1) % self.cfg.vocab_size
        return out

    monkeypatch.setattr(PackedLoader, "batch", batch)


@pytest.mark.parametrize("cell", ["smollm-tiny.tiny", "mamba2-tiny.tiny"])
@pytest.mark.parametrize("fault", ["unchanged_state", "half_batch",
                                   "altered_token"])
def test_fault_makes_the_run_incorrect(tiny_root, monkeypatch, cell, fault):
    if fault == "altered_token":
        _altered_token(monkeypatch)
    else:
        _broken_step(monkeypatch, fault)
    res = harness.run(cell, 123456789012, 0.5, False, time.perf_counter(),
                      root=tiny_root, allow_cpu=True)
    assert res["correct"] is False
    failing = {k for k, c in res["checks"].items()
               if not c["value"] <= c["limit"]}
    assert failing, res["checks"]
    if fault == "unchanged_state":
        assert res["checks"]["update"]["value"] == pytest.approx(1.0)
    if fault == "altered_token":
        assert "batch" in failing
