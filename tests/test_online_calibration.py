"""Online calibration & drift watch — the telemetry-driven test harness.

Covers the streaming subsystem end to end: RLS ≡ batch ``fit_relative``
(the exactness property), telemetry ring-buffer semantics, CUSUM drift
detection bounds (including the no-false-positive property), the full
drift-injection scenario (detect → refit → registry revision bump → cache
invalidation → fused ≡ loop coherence), the learned residual head, the
calibration CLI round-trip, and the inf-safe fit diagnostics.
"""
from __future__ import annotations

import json
import math
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(__file__))
from _hypothesis_compat import given, settings, st  # noqa: E402

from repro.calibration import registry
from repro.calibration.online import DriftMonitor, OnlineCalibrator
from repro.calibration.registry import register_revision
from repro.calibration.telemetry import (TelemetrySink, pv_fingerprint)
from repro.core import exprops, fit, predictor
from repro.core.model import (SCHEMA_VERSION, LinearCostModel, geomean)


@pytest.fixture(autouse=True)
def _isolated_registry(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_MODEL_REGISTRY", str(tmp_path / "ambient-reg"))


def _geo_rel_err(model, pvs, times):
    errs = fit.safe_relative_errors(model.predict_many(list(pvs)), times)
    finite = errs[np.isfinite(errs)]
    return geomean(finite) if len(finite) else float("inf")


# ---------------------------------------------------------------------------
# RLS ≡ batch fit_relative
# ---------------------------------------------------------------------------


def _synthetic_stream(rng, n, keys, w_true, noise=0.1):
    pvs, times = [], []
    for _ in range(n):
        pv = {k: float(v) for k, v in zip(keys, rng.uniform(0.1, 10.0,
                                                            len(keys)))}
        t = float(sum(w * pv[k] for w, k in zip(w_true, keys)))
        t *= float(np.exp(noise * rng.standard_normal()))
        pvs.append(pv)
        times.append(t)
    return pvs, times


def test_rls_forgetting_one_equals_batch_fit_seeded():
    rng = np.random.default_rng(7)
    keys = ["a", "b", "c", "d"]
    pvs, times = _synthetic_stream(rng, 64, keys,
                                   np.array([0.5, 2.0, 1.0, 3.0]))
    batch = fit.fit_relative(pvs, times, keys=keys)
    rls = fit.RLSState.init(keys, lam=1.0, delta=1e12)
    rls.observe_many(pvs, times)
    np.testing.assert_allclose(rls.w, batch.weights, rtol=1e-7, atol=1e-10)
    # and the materialized model predicts identically to its weights
    m = rls.model(device="rls-test")
    for pv in pvs[:5]:
        assert m.predict(pv) == pytest.approx(rls.predict(pv), rel=1e-12)
    assert m.meta["n_samples"] == 64 and m.meta["forgetting"] == 1.0


@given(st.integers(min_value=0, max_value=10_000),
       st.integers(min_value=8, max_value=40),
       st.integers(min_value=2, max_value=5))
@settings(max_examples=20, deadline=None)
def test_rls_forgetting_one_equals_batch_fit_property(seed, n, k):
    rng = np.random.default_rng(seed)
    keys = [f"p{i}" for i in range(k)]
    w_true = rng.uniform(0.5, 3.0, size=k)
    pvs, times = _synthetic_stream(rng, n, keys, w_true)
    batch = fit.fit_relative(pvs, times, keys=keys)
    rls = fit.RLSState.init(keys, lam=1.0, delta=1e12)
    rls.observe_many(pvs, times)
    np.testing.assert_allclose(rls.w, batch.weights, rtol=1e-7, atol=1e-10)


def test_rls_warm_start_anchors_unobserved_directions():
    # a rank-1 stream (one pv repeated) must leave the unexercised weights
    # at the prior instead of collapsing them to zero
    prior = LinearCostModel(keys=["x", "y"], weights=np.array([2.0, 5.0]),
                            device="warm")
    rls = fit.RLSState.from_model(prior, lam=1.0, delta=1e12)
    for _ in range(10):
        rls.observe({"x": 4.0}, 8.0)          # consistent with w_x = 2.0
    np.testing.assert_allclose(rls.w, [2.0, 5.0], rtol=1e-6)


def test_rls_forgetting_tracks_drift_better_than_batch(make_drift_stream):
    s = make_drift_stream(n_pre=150, n_post=150, shift=1.5, noise=0.02,
                          seed=3)
    flat = fit.RLSState.init(s.keys, lam=1.0)
    windowed = fit.RLSState.init(s.keys, lam=0.97)
    flat.observe_many(s.pvs, s.times)
    windowed.observe_many(s.pvs, s.times)
    post = slice(s.shift_index, None)
    err = lambda r: np.mean([abs(r.predict(pv) - t) / t for pv, t in
                             zip(s.pvs[post], s.times[post])])
    assert err(windowed) < err(flat)


def test_rls_validates_inputs():
    with pytest.raises(ValueError, match="forgetting"):
        fit.RLSState.init(["a"], lam=0.0)
    rls = fit.RLSState.init(["a"])
    # the strict row constructor still raises...
    with pytest.raises(ValueError, match="non-positive"):
        rls.row({"a": 1.0}, 0.0)
    # ...but the streaming path QUARANTINES a poisoned sample instead of
    # letting one clock glitch kill a live calibrator (tests/test_faults.py
    # covers the full quarantine contract)
    assert rls.observe({"a": 1.0}, 0.0) is False
    assert rls.n_quarantined == 1 and rls.n_samples == 0


def test_refit_strictly_reduces_windowed_error_on_drift(make_drift_stream):
    s = make_drift_stream(n_pre=100, n_post=60, shift=1.6, noise=0.03,
                          seed=11)
    pre = fit.fit_relative(s.pvs[:s.shift_index], s.times[:s.shift_index],
                           keys=s.keys)
    post_pvs = s.pvs[s.shift_index:]
    post_times = s.times[s.shift_index:]
    refit = fit.RLSState.from_model(pre, lam=1.0)
    refit.observe_many(post_pvs, post_times)
    old_err = _geo_rel_err(pre, post_pvs, post_times)
    new_err = _geo_rel_err(refit.model(), post_pvs, post_times)
    assert new_err < old_err          # strictly better on the drifted window
    assert old_err > 0.3              # the 1.6× drift really was visible
    assert new_err < 0.05


# ---------------------------------------------------------------------------
# telemetry sink
# ---------------------------------------------------------------------------


def test_pv_fingerprint_ignores_zero_entries():
    assert pv_fingerprint({"a": 1.0, "b": 0.0}) == pv_fingerprint({"a": 1.0})
    assert pv_fingerprint({"a": 1.0}) != pv_fingerprint({"a": 2.0})


def test_sink_dedups_vectors_and_evicts_with_gc():
    sink = TelemetrySink(capacity=4)
    pv_a, pv_b = {"x": 1.0}, {"x": 2.0}
    for i in range(3):
        sink.record(pv_a, 0.1, step=i, tag="train")
    assert sink.stats()["n_unique_pvs"] == 1
    for i in range(4):                 # evicts all pv_a samples
        sink.record(pv_b, 0.2, step=i)
    st_ = sink.stats()
    assert len(sink) == 4 and st_["n_recorded"] == 7
    assert st_["n_unique_pvs"] == 1    # pv_a garbage-collected
    with pytest.raises(KeyError):
        sink.pv(pv_fingerprint(pv_a))


def test_sink_drops_non_positive_timings():
    sink = TelemetrySink()
    assert sink.record({"x": 1.0}, 0.0) is None
    assert sink.record({"x": 1.0}, -1.0) is None
    assert sink.record({"x": 1.0}, 1e-9) == 0
    assert sink.stats()["n_dropped"] == 2


def test_sink_windows_filter_by_seq_and_tag():
    sink = TelemetrySink()
    for i in range(6):
        sink.record({"x": float(i + 1)}, float(i + 1),
                    tag="train" if i % 2 == 0 else "decode")
    pvs, times = sink.window(since_seq=3)
    assert times == [4.0, 5.0, 6.0]
    pvs, times = sink.window(tag="decode")
    assert times == [2.0, 4.0, 6.0]
    pvs, times = sink.window(n=2)
    assert times == [5.0, 6.0] and pvs[-1] == {"x": 6.0}


def test_sink_json_roundtrip(tmp_path):
    sink = TelemetrySink(capacity=8)
    for i in range(5):
        sink.record({"mxu:16": float(i + 1), "const1": 1.0},
                    0.01 * (i + 1), step=i, tag="train")
    sink.record({"x": 1.0}, -1.0)      # counted drop
    path = str(tmp_path / "telemetry.json")
    sink.save(path)
    back = TelemetrySink.load(path)
    assert back.stats() == sink.stats()
    assert back.window() == sink.window()
    assert [s.seq for s in back.samples()] == [s.seq for s in sink.samples()]
    with open(path) as f:
        d = json.load(f)
    assert d["kind"] == "telemetry" and d["schema"] == 2
    with pytest.raises(ValueError, match="not a telemetry record"):
        TelemetrySink.from_json_dict({"kind": "nope"})


# ---------------------------------------------------------------------------
# drift detection
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("shift", [1.2, 1.5, 2.0])
def test_drift_monitor_flags_within_bounded_samples(shift):
    mon = DriftMonitor(slack=0.1, threshold=3.0)
    resid = shift - 1.0
    bound = math.ceil(mon.threshold / (resid - mon.slack)) + 2
    ev = None
    for i in range(bound):
        ev = mon.observe(i, resid, step=i)
        if ev is not None:
            break
    assert ev is not None, f"{shift}x drift not flagged within {bound}"
    assert ev.direction == "slow" and ev.onset_seq == 0


def test_drift_monitor_onset_is_change_point_estimate():
    mon = DriftMonitor(slack=0.1, threshold=2.0)
    ev = None
    for i in range(200):
        ev = mon.observe(i, 0.0 if i < 50 else 0.6, step=i)
        if ev is not None:
            break
    assert ev is not None and ev.onset_seq == 50 and ev.step == ev.seq
    assert mon.evidence == 0.0         # state reset after the event


def test_drift_monitor_detects_speedups_too():
    mon = DriftMonitor(slack=0.1, threshold=2.0)
    ev = None
    for i in range(100):
        ev = mon.observe(i, -0.4)
        if ev is not None:
            break
    assert ev is not None and ev.direction == "fast"


def test_drift_monitor_quiet_under_pure_noise():
    rng = np.random.default_rng(0)
    mon = DriftMonitor()               # default slack 0.15
    for i in range(2000):
        assert mon.observe(i, float(0.05 * rng.standard_normal())) is None
    assert mon.status == "ok" and not mon.events


def test_calibrator_no_false_positive_under_noise(make_drift_stream):
    s = make_drift_stream(n_pre=400, n_post=0, shift=1.0, noise=0.05,
                          seed=21)
    truth = LinearCostModel(keys=s.keys, weights=s.weights, device="truth")
    cal = OnlineCalibrator(truth, device="noise-dev")
    for i, (pv, t) in enumerate(zip(s.pvs, s.times)):
        assert cal.observe(pv, t, step=i) is None
    assert cal.refits == 0 and cal.drift.status == "ok" and not cal.events


# ---------------------------------------------------------------------------
# end-to-end drift injection: detect -> refit -> registry -> caches coherent
# ---------------------------------------------------------------------------


def test_drift_injection_end_to_end(tmp_path, make_drift_stream):
    s = make_drift_stream(n_pre=120, n_post=80, shift=1.5, noise=0.02,
                          seed=5)
    truth = LinearCostModel(keys=s.keys, weights=s.weights, device="truth",
                            meta={"source": "synthetic"})
    cache = exprops.BasisCache(maxsize=256)
    cal = OnlineCalibrator(truth, device="drift-dev",
                           registry_dir=str(tmp_path), auto_register=True,
                           caches=[cache])
    events = []
    for i, (pv, t) in enumerate(zip(s.pvs, s.times)):
        ev = cal.observe(pv, t, step=i)
        if ev is not None:
            events.append(ev)

    # detected once, within a bounded window after the injected shift
    assert len(events) == 1 == len(cal.events) == cal.refits
    ev = events[0]
    assert ev.direction == "slow"
    assert s.shift_index <= ev.seq <= s.shift_index + 60
    # the CUSUM's change-point estimate lands on the injected shift
    assert abs(ev.onset_seq - s.shift_index) <= 3

    # registry revision bumped exactly once; the refit model round-trips
    assert cal.revision == 1
    loaded = registry.load_model("drift-dev", str(tmp_path))
    assert loaded.meta["revision"] == 1
    assert loaded.meta["refit_epoch"] == 1
    np.testing.assert_array_equal(loaded.weights, cal.model.weights)

    # refit swapped in a NEW model object (fold caches key on identity)
    # and its predictions track the 1.5x-shifted regime
    assert cal.model is not truth
    np.testing.assert_allclose(
        cal.model.predict_many(s.pvs[s.shift_index:]),
        np.asarray(s.times[s.shift_index:]), rtol=0.1)

    # stale basis-cache entries were invalidated
    assert cache.invalidations == 1

    # post-refit windowed error within 1.25x of the pre-drift error
    pre_err = _geo_rel_err(truth, s.pvs[:s.shift_index],
                           s.times[:s.shift_index])
    post_err = _geo_rel_err(cal.model, s.pvs[s.shift_index:],
                            s.times[s.shift_index:])
    assert post_err <= 1.25 * pre_err

    # observability: the report line carries the whole story
    line = cal.report_line()
    assert "drift=ok" in line and "refits=1" in line and "revision=1" in line
    assert f"samples={len(s.pvs)}" in line
    report = cal.final_report()
    assert "drift event:" in report and "direction=slow" in report


def test_refit_model_scores_fused_equals_loop(tmp_path, make_drift_stream):
    """All prediction paths stay coherent after a refit: the batched engine
    (through the cache the calibrator cleared) matches the per-plan oracle
    under the refit model."""
    from repro.configs.base import SHAPES
    from repro.configs.registry import ARCHS
    from repro.distributed.plan import plan_for
    s = make_drift_stream(n_pre=60, n_post=60, shift=1.5, noise=0.0, seed=9)
    truth = LinearCostModel(keys=s.keys, weights=s.weights, device="truth")
    cache = exprops.BasisCache(maxsize=256)
    cfg, shape = ARCHS["glm4-9b"], SHAPES["train_4k"]
    mesh = {"data": 16, "model": 16}
    base = plan_for(cfg, shape)
    plans = [base.with_(microbatches=m, fsdp=f)
             for m in (1, 4) for f in (True, False)]
    # warm the cache with the OLD model so stale columns exist to invalidate
    predictor.predict_plans(cfg, shape, plans, mesh, truth, cache=cache)

    cal = OnlineCalibrator(truth, device="fused-dev",
                           registry_dir=str(tmp_path), caches=[cache])
    for i, (pv, t) in enumerate(zip(s.pvs, s.times)):
        cal.observe(pv, t, step=i)
    assert cal.refits == 1 and cache.invalidations == 1

    fused = predictor.predict_plans(cfg, shape, plans, mesh, cal.model,
                                    cache=cache)
    loop = predictor.predict_plans_loop(cfg, shape, plans, mesh, cal.model)
    np.testing.assert_allclose(fused, loop, rtol=1e-9)


def test_register_revision_bumps_monotonically(tmp_path):
    m = LinearCostModel(keys=["const1"], weights=np.array([1.0]),
                        device="rev-dev")
    path1, r1 = register_revision(m, str(tmp_path))
    path2, r2 = register_revision(m, str(tmp_path))
    assert (r1, r2) == (1, 2) and path1 == path2
    assert registry.load_model("rev-dev", str(tmp_path)).meta["revision"] == 2


# ---------------------------------------------------------------------------
# learned residual head
# ---------------------------------------------------------------------------


def test_fit_residual_learns_systematic_correction():
    rng = np.random.default_rng(13)
    keys = ["a", "b"]
    base = LinearCostModel(keys=keys, weights=np.array([1.0, 2.0]),
                           device="res")
    pvs, times = [], []
    for _ in range(80):
        pv = {k: float(v) for k, v in zip(keys, rng.uniform(1.0, 50.0, 2))}
        # true time = base prediction x a feature-dependent factor the
        # linear basis cannot express
        factor = 1.0 + 0.3 * np.tanh(np.log1p(pv["a"]) - 2.5)
        pvs.append(pv)
        times.append(base.predict(pv) * factor)
    head = fit.fit_residual(pvs, times, base, ridge=1e-3)
    assert head is not None
    raw = fit.safe_relative_errors(base.predict_many(pvs), times)
    corr = fit.safe_relative_errors(
        [head.predict(base, pv) for pv in pvs], times)
    assert geomean(corr) < 0.5 * geomean(raw)
    # serialization round-trip
    back = fit.ResidualHead.from_json_dict(head.to_json_dict())
    for pv in pvs[:5]:
        assert back.predict(base, pv) == head.predict(base, pv)
    with pytest.raises(ValueError, match="not a residual_head"):
        fit.ResidualHead.from_json_dict({"kind": "nope"})


def test_fit_residual_degenerate_returns_none():
    m = LinearCostModel(keys=["a"], weights=np.array([1.0]), device="x")
    assert fit.fit_residual([{"a": 1.0}], [1.0], m) is None
    # rows with non-positive predictions/times are unusable
    neg = LinearCostModel(keys=["a"], weights=np.array([-1.0]), device="x")
    assert fit.fit_residual([{"a": 1.0}] * 4, [1.0] * 4, neg) is None


def test_residual_head_correction_is_clipped():
    head = fit.ResidualHead(keys=["a"], mean=np.zeros(1), scale=np.ones(1),
                            beta=np.array([100.0, 0.0]), clip=2.0)
    assert head.correction({"a": 1e9}) == pytest.approx(np.exp(2.0))
    assert head.correction({}) >= np.exp(-2.0)


def test_predict_step_applies_residual_head():
    from repro.configs.base import SHAPES
    from repro.configs.registry import ARCHS
    from repro.distributed.plan import plan_for
    cfg, shape = ARCHS["glm4-9b"], SHAPES["train_4k"]
    plan = plan_for(cfg, shape)
    mesh = {"data": 16, "model": 16}
    # bias-only head: exact x1.1 correction regardless of features
    head = fit.ResidualHead(keys=["const1"], mean=np.zeros(1),
                            scale=np.ones(1),
                            beta=np.array([0.0, np.log(1.1)]))
    base = predictor.predict_step(cfg, shape, plan, mesh)
    corr = predictor.predict_step(cfg, shape, plan, mesh, residual=head)
    assert corr.seconds == pytest.approx(1.1 * base.seconds, rel=1e-9)
    assert corr.terms["residual"] == pytest.approx(0.1 * base.seconds,
                                                  rel=1e-9)
    assert "residual" not in base.terms


def test_calibrator_fits_residual_head_on_refit(tmp_path, make_drift_stream):
    s = make_drift_stream(n_pre=60, n_post=60, shift=1.5, noise=0.02, seed=2)
    truth = LinearCostModel(keys=s.keys, weights=s.weights, device="truth")
    cal = OnlineCalibrator(truth, device="res-dev",
                           registry_dir=str(tmp_path), residual=True)
    for i, (pv, t) in enumerate(zip(s.pvs, s.times)):
        cal.observe(pv, t, step=i)
    assert cal.refits == 1 and cal.residual_head is not None
    assert "residual head:" in cal.final_report()


# ---------------------------------------------------------------------------
# calibration CLI round-trip regression
# ---------------------------------------------------------------------------


def test_cli_measure_fit_register_load_roundtrip(tmp_path, capsys,
                                                 monkeypatch):
    from repro.calibration.__main__ import main
    # a set JAX_COMPILATION_CACHE_DIR keeps the CLI off the checkout cache
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jax"))
    reg = str(tmp_path / "cli-reg")
    rc = main(["--device", "cli-dev", "--scale", "tiny", "--runs", "3",
               "--drop", "1", "--classes", "stride1_global", "--out", reg])
    assert rc == 0
    first = capsys.readouterr().out.splitlines()[0]
    assert first.startswith("# measuring on platform=cpu "), first
    m1 = registry.load_model("cli-dev", reg)
    assert m1.meta["source"] == "calibrated"
    # register -> load -> re-register -> load is bit-exact (no decimal decay)
    reg2 = str(tmp_path / "cli-reg-2")
    registry.save_model(m1, reg2)
    m2 = registry.load_model("cli-dev", reg2)
    np.testing.assert_array_equal(m1.weights, m2.weights)
    assert m1.keys == m2.keys
    # --show renders the registered model
    assert main(["--show", "cli-dev", "--out", reg]) == 0
    assert "cli-dev" in capsys.readouterr().out


def test_cli_show_unknown_device_is_clean_error(tmp_path, capsys):
    from repro.calibration.__main__ import main
    rc = main(["--show", "no-such-dev", "--out", str(tmp_path)])
    assert rc == 1
    err = capsys.readouterr().err
    assert "cannot load model 'no-such-dev'" in err
    assert "tpu-v5e" in err            # lists what IS available


def test_cli_show_rejects_future_schema(tmp_path, capsys):
    from repro.calibration.__main__ import main
    with open(tmp_path / "future-dev.json", "w") as f:
        json.dump({"schema": SCHEMA_VERSION + 1,
                   "kind": "linear_cost_model",
                   "keys": ["x"], "weights": [1.0]}, f)
    rc = main(["--show", "future-dev", "--out", str(tmp_path)])
    assert rc == 1
    assert "cannot load model 'future-dev'" in capsys.readouterr().err


# ---------------------------------------------------------------------------
# inf-safe fit diagnostics (previously ZeroDivisionError / LinAlgError)
# ---------------------------------------------------------------------------


def test_fit_report_zero_timing_rows_are_inf_not_crash():
    m = LinearCostModel(keys=["a"], weights=np.array([2.0]), device="x")
    pvs = [{"a": 1.0}, {"a": 2.0}, {"a": 3.0}]
    times = [2.0, 0.0, 6.0]            # would previously divide by zero
    rep = fit.fit_report(m, pvs, times)
    assert rep["n"] == 3 and rep["n_finite"] == 2
    assert rep["rows"][1]["rel_err"] == float("inf")
    assert rep["geomean_rel_err"] <= 2e-12  # the finite rows are exact
    assert np.isfinite(rep["max_rel_err"])


def test_fit_report_all_zero_timings():
    m = LinearCostModel(keys=["a"], weights=np.array([1.0]), device="x")
    rep = fit.fit_report(m, [{"a": 1.0}], [0.0])
    assert rep["n_finite"] == 0
    assert rep["geomean_rel_err"] == float("inf")
    assert rep["max_rel_err"] == float("inf")


def test_condition_report_drops_zero_timing_rows():
    pvs = [{"a": 1.0, "b": 2.0}, {"a": 3.0, "b": 1.0}, {"a": 2.0, "b": 2.0}]
    rep = fit.condition_report(pvs, [1.0, 0.0, 2.0])
    assert rep["n_rows"] == 2 and rep["n_dropped"] == 1
    assert np.isfinite(rep["cond"])
    all_zero = fit.condition_report(pvs, [0.0, 0.0, 0.0])
    assert all_zero["n_rows"] == 0 and all_zero["rank"] == 0
    assert all_zero["cond"] == float("inf") and all_zero["n_dropped"] == 3


def test_safe_relative_errors_basic():
    errs = fit.safe_relative_errors([1.0, 2.0, 3.0], [2.0, 0.0, 3.0])
    assert errs[0] == 0.5 and errs[1] == float("inf") and errs[2] == 0.0


# ---------------------------------------------------------------------------
# runtime integration: trainer + decode server feed the sink
# ---------------------------------------------------------------------------


def test_trainer_feeds_calibrator(tmp_path, capsys):
    from repro.configs.registry import ARCHS
    from repro.data.pipeline import DataConfig
    from repro.runtime.trainer import Trainer, TrainerConfig
    cfg = ARCHS["smollm-360m"].reduced()
    dc = DataConfig(vocab_size=cfg.vocab_size, seq_len=64, global_batch=4,
                    seed=5)
    tc = TrainerConfig(log_every=2, total_steps=6, online_calibrate=True,
                       calib_registry=str(tmp_path))
    t = Trainer(cfg, dc, tc)
    assert t.calibrator is not None
    t.train(6)
    assert t.calibrator.sink.stats()["n_recorded"] == 6
    assert t.calibrator.sink.samples(tag="train")
    assert t.calibrator.rls.n_samples == 6
    out = capsys.readouterr().out
    assert "[calib] samples=" in out and "drift=" in out


def test_decode_server_feeds_calibrator(tmp_path):
    import jax
    from repro.configs.registry import ARCHS
    from repro.models import transformer
    from repro.runtime.server import DecodeServer, Request
    cfg = ARCHS["smollm-360m"].reduced()
    params, _ = transformer.init_params(cfg, jax.random.PRNGKey(0))
    cal = OnlineCalibrator(None, device="decode-dev",
                           registry_dir=str(tmp_path))
    srv = DecodeServer(cfg, params, slots=2, max_len=64, seed=0,
                       calibrator=cal)
    rng = np.random.default_rng(0)
    srv.submit(Request(rid=0, prompt=rng.integers(2, 200, 4).astype(np.int32),
                       max_new=4))
    done = srv.run()
    assert len(done) == 1
    assert cal.sink.stats()["n_recorded"] >= 4
    assert all(sm.tag == "decode" for sm in cal.sink.samples())
