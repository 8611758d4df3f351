"""``readings.py`` (the limits' readings) end to end at the reduced sizes."""
import json

import jax

from bench import harness
from bench import readings


def test_readings_of_program_control_and_half_batch(tiny_root, tmp_path,
                                                    monkeypatch):
    monkeypatch.setattr(readings, "ROOT", tiny_root)
    monkeypatch.setattr(harness, "_devices",
                        lambda chips, allow_cpu=False: jax.devices()[:chips])
    out = tmp_path / "r.json"
    assert readings.main(["--workload", "smollm-tiny.tiny", "--seeds", "2",
                          "--control", "1", "--out", str(out)]) == 0
    got = json.loads(out.read_text())
    assert len(got["program"]) == 2 and len(got["control"]) == 1
    for r in got["program"].values():
        assert r["batch"] == 0 and r["loss"] < 1e-4
    # the control and the half batch are each caught by some number
    assert next(iter(got["control"].values()))["loss"] > 1e-4
    assert next(iter(got["half_batch"].values()))["loss"] > 1e-4
    assert got["unchanged_state"]["update"] == 1.0


def _readings(program, control, half, unchanged=1.0):
    as_runs = lambda v: {str(i): {"loss": x, "first_grad": x, "update": x}
                         for i, x in enumerate(v)}
    return {"workload": "w", "device": "d", "program": as_runs(program * 6),
            "control": as_runs(control), "half_batch": as_runs(half),
            "unchanged_state": {"loss": program[0],
                                "first_grad": program[0],
                                "update": unchanged}}


def test_limits_lie_between_the_readings():
    from bench import set_limits

    out = set_limits.limits_from(_readings([1e-5, 2e-5], [1e-4, 2e-4],
                                           [1e-3]))
    for k in ("loss", "first_grad", "update"):
        assert 2e-5 < out["limits"][k] < 1e-4
        assert out["readings"][k]["upper_from"] == "control"
    assert out["limits"]["batch"] == 0
    # a control within 3x of the program bounds nothing; the half batch
    # does, and the control still has to fail another number
    r = _readings([1e-5, 2e-5], [3e-5], [1e-3])
    r["control"]["0"]["first_grad"] = 1e-4
    out = set_limits.limits_from(r)
    assert out["readings"]["loss"]["upper_from"] == "half_batch"
    assert out["readings"]["first_grad"]["upper_from"] == "control"


def test_number_without_upper_reading_is_not_compared():
    import pytest

    from bench import set_limits

    r = _readings([1e-5, 2e-5], [3e-5], [5e-5])
    r["control"]["0"]["update"] = 1e-4
    r["half_batch"]["0"]["update"] = 1e-3
    out = set_limits.limits_from(r)
    assert set(out["not_compared"]) == {"loss", "first_grad"}
    assert set(out["limits"]) == {"update", "batch"}
    # a control that fails no compared number leaves the cell without limits
    r["control"]["0"]["update"] = 3e-5
    with pytest.raises(ValueError):
        set_limits.limits_from(r)
