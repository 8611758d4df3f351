"""Calibration subsystem tests: schema-versioned model (de)serialization,
registry lookup/error paths, the batched plan-scoring hot path, and a
tiny-scale end-to-end calibrate -> register -> load -> predict loop."""
from __future__ import annotations

import json
import os

import numpy as np
import pytest

from repro.calibration import (ANALYTIC_SEEDS, UnknownDeviceError, calibrate,
                               list_models, load_model, resolve_model,
                               save_model, seeds)
from repro.core import predictor
from repro.core.model import (SCHEMA_VERSION, LinearCostModel,
                              ModelSchemaError)


@pytest.fixture(autouse=True)
def _isolated_registry(tmp_path, monkeypatch):
    """Pin the default registry to an empty tmp dir so fitted models a
    developer registered in ./experiments/registry can't shadow the
    analytic seeds these tests compare against."""
    monkeypatch.setenv("REPRO_MODEL_REGISTRY", str(tmp_path / "ambient-reg"))


def _awkward_model() -> LinearCostModel:
    # weights chosen so decimal shortening would be observable
    w = np.array([1.0 / 3.0 * 1e-9, np.pi * 1e-12, -7.3e-11, 2.0 ** -40])
    return LinearCostModel(keys=["a", "b", "c", "d"], weights=w,
                           device="rt-test", meta={"note": "round-trip"})


# ---------------------------------------------------------------------------
# serialization round-trip
# ---------------------------------------------------------------------------


def test_roundtrip_predictions_bitwise_identical(tmp_path):
    m = _awkward_model()
    p = str(tmp_path / "m.json")
    m.save(p)
    m2 = LinearCostModel.load(p)
    assert m2.keys == m.keys and m2.device == m.device and m2.meta == m.meta
    assert np.array_equal(m2.weights, m.weights)  # bitwise, not approx
    pvs = [{"a": 3.0, "b": 1e6, "c": 7.0, "d": 2.0},
           {"a": 1.0}, {"b": 123.456, "d": 1e-3}]
    for pv in pvs:
        assert m2.predict(pv) == m.predict(pv)
    assert np.array_equal(m2.predict_many(pvs), m.predict_many(pvs))


def test_serialized_file_carries_schema_version(tmp_path):
    p = str(tmp_path / "m.json")
    _awkward_model().save(p)
    with open(p) as f:
        d = json.load(f)
    assert d["schema"] == SCHEMA_VERSION
    assert d["kind"] == "linear_cost_model"


def test_legacy_v0_file_still_loads(tmp_path):
    # the pre-registry format: no schema/kind envelope
    p = str(tmp_path / "legacy.json")
    with open(p, "w") as f:
        json.dump({"device": "old", "keys": ["x"], "weights": [1e-9],
                   "meta": {}}, f)
    m = LinearCostModel.load(p)
    assert m.device == "old" and m.predict({"x": 2.0}) == 2e-9


def test_future_schema_rejected(tmp_path):
    p = str(tmp_path / "future.json")
    with open(p, "w") as f:
        json.dump({"schema": SCHEMA_VERSION + 1, "kind": "linear_cost_model",
                   "keys": ["x"], "weights": [1.0]}, f)
    with pytest.raises(ModelSchemaError):
        LinearCostModel.load(p)


def test_mismatched_lengths_rejected():
    with pytest.raises(ModelSchemaError):
        LinearCostModel.from_json_dict(
            {"schema": 1, "kind": "linear_cost_model",
             "keys": ["x", "y"], "weights": [1.0]})


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------


def test_registry_save_then_load(tmp_path):
    m = _awkward_model()
    path = save_model(m, str(tmp_path))
    assert os.path.exists(path)
    m2 = load_model("rt-test", str(tmp_path))
    assert np.array_equal(m2.weights, m.weights)
    assert list_models(str(tmp_path))["rt-test"] == "fitted"


def test_registry_unknown_device_error_lists_available(tmp_path):
    with pytest.raises(UnknownDeviceError) as ei:
        load_model("no-such-device", str(tmp_path))
    msg = str(ei.value)
    assert "no-such-device" in msg and "tpu-v5e" in msg
    assert isinstance(ei.value, KeyError)


def test_registry_analytic_seeds_cover_cross_vendor(tmp_path):
    names = set(list_models(str(tmp_path)))
    assert {"tpu-v5e", "gpu-a100", "gpu-h100", "gpu-mi300x"} <= names
    vendors = {load_model(n, str(tmp_path)).meta.get("vendor")
               for n in ("gpu-a100", "gpu-mi300x")}
    assert vendors == {"nvidia", "amd"}


def test_registry_v5e_seed_matches_predictor_seed(tmp_path):
    reg = load_model("tpu-v5e", str(tmp_path))
    ref = predictor.tpu_v5e_weights()
    assert reg.keys == ref.keys
    assert np.array_equal(reg.weights, ref.weights)


def test_registry_fitted_model_shadows_analytic_seed(tmp_path):
    custom = LinearCostModel(keys=["const1"], weights=np.array([1.0]),
                             device="gpu-a100")
    save_model(custom, str(tmp_path))
    assert load_model("gpu-a100", str(tmp_path)).keys == ["const1"]
    assert list_models(str(tmp_path))["gpu-a100"] == "fitted"


def test_analytic_seeds_price_full_taxonomy():
    from repro.core import properties as props
    for name, build in ANALYTIC_SEEDS.items():
        m = build()
        have = set(m.keys)
        assert props.CONST1 in have and props.BARRIER in have, name
        assert props.mxu_key(16) in have, name
        assert props.mem_key("load", 32, "s1") in have, name
        assert props.coll_key("all_reduce") in have, name


def test_resolve_model_forms(tmp_path):
    m = _awkward_model()
    assert resolve_model(m) is m
    by_name = resolve_model("gpu-h100", registry_dir=str(tmp_path))
    assert by_name.device == "gpu-h100"
    default = resolve_model(None, registry_dir=str(tmp_path))
    assert default.device == predictor.tpu_v5e_weights().device
    with pytest.raises(TypeError):
        resolve_model(42)


# ---------------------------------------------------------------------------
# batched plan-scoring hot path
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def plan_search_cell():
    from repro.configs.base import SHAPES
    from repro.configs.registry import ARCHS
    from repro.launch.autoshard import candidate_plans
    cfg = ARCHS["glm4-9b"]
    shape = SHAPES["train_4k"]
    plans = candidate_plans(cfg, shape)
    return cfg, shape, plans, {"data": 16, "model": 16}


def test_predict_plans_matches_per_plan_loop(plan_search_cell):
    cfg, shape, plans, mesh = plan_search_cell
    batched = predictor.predict_plans(cfg, shape, plans, mesh)
    assert batched.shape == (len(plans),)
    loop = [predictor.predict_step(cfg, shape, p, mesh).seconds
            for p in plans]
    np.testing.assert_allclose(batched, loop, rtol=1e-9)


def test_rank_plans_is_sorted_and_complete(plan_search_cell):
    cfg, shape, plans, mesh = plan_search_cell
    ranked = predictor.rank_plans(cfg, shape, plans, mesh)
    assert len(ranked) == len(plans)
    secs = [s for s, _ in ranked]
    assert secs == sorted(secs)


def test_predict_plans_accepts_registry_name(plan_search_cell):
    cfg, shape, plans, mesh = plan_search_cell
    by_name = predictor.predict_plans(cfg, shape, plans[:8], mesh, "gpu-a100")
    by_model = predictor.predict_plans(cfg, shape, plans[:8], mesh,
                                       ANALYTIC_SEEDS["gpu-a100"]())
    np.testing.assert_array_equal(by_name, by_model)


def test_predict_plans_empty():
    from repro.configs.base import SHAPES
    from repro.configs.registry import ARCHS
    out = predictor.predict_plans(ARCHS["glm4-9b"], SHAPES["train_4k"], [],
                                  {"data": 2})
    assert out.shape == (0,)


def test_straggler_monitor_from_model(plan_search_cell):
    from repro.runtime.straggler import StragglerMonitor
    cfg, shape, plans, mesh = plan_search_cell
    mon = StragglerMonitor.from_model(cfg, shape, plans[0], mesh,
                                      n_hosts=4, model="tpu-v5e", k=3.0)
    expect = predictor.predict_step(cfg, shape, plans[0], mesh).seconds
    assert mon.predicted_step_s == pytest.approx(expect)
    assert mon.k == 3.0 and mon.n_hosts == 4


def test_elastic_replan_accepts_registry_name(plan_search_cell):
    from repro.distributed import elastic
    cfg, shape, _, _ = plan_search_cell
    opts = elastic.replan(cfg, shape, 64, weights="gpu-h100")
    assert opts and opts[0].predicted_step_s <= opts[-1].predicted_step_s


# ---------------------------------------------------------------------------
# end-to-end: calibrate -> registry -> load -> identical predictions
# ---------------------------------------------------------------------------


def test_calibrate_tiny_end_to_end(tmp_path):
    res = calibrate("cpu-test", scale="tiny", runs=5, drop=1,
                    classes=("stride1_global",), registry_dir=str(tmp_path),
                    verbose=False)
    assert res.registry_path and os.path.exists(res.registry_path)
    assert res.model.meta["source"] == "calibrated"
    import jax
    assert res.model.meta["platform"] == jax.devices()[0].platform
    assert res.model.meta["device_kind"] == jax.devices()[0].device_kind
    assert res.model.meta["device_count"] == jax.device_count()
    assert res.report["n"] == len(res.labels) > 0

    loaded = load_model("cpu-test", str(tmp_path))
    assert np.array_equal(loaded.weights, res.model.weights)
    assert loaded.meta["platform"] == res.model.meta["platform"]
    pv = {k: float(i + 1) for i, k in enumerate(res.model.keys)}
    assert loaded.predict(pv) == res.model.predict(pv)


def test_calibrate_rejects_unknown_class(tmp_path):
    with pytest.raises(ValueError, match="unknown kernel classes"):
        calibrate("x", scale="tiny", classes=("not_a_class",),
                  registry_dir=str(tmp_path), verbose=False)
