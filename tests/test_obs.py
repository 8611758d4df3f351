"""Observability layer — tracer, metrics registry, basis-term attribution.

Covers the three pillars of ``repro.obs``: nested-span tracing with the
predicted-duration overlay (Chrome-trace schema, fake-clock determinism,
the disabled-tracer no-op contract), the metrics registry (counter /
gauge / histogram semantics, Prometheus exposition golden, JSON dump,
get-or-create registration), and basis-term attribution
(``score_explain`` ≡ the fused ``PlanSpace.scores`` GEMV at rtol 1e-9
across every registered arch; residual attribution recovering an
injected single-term perturbation).  Plus the crash-safe telemetry save
regression (a failed save must never truncate the previous artifact).
"""
from __future__ import annotations

import json
import os

import numpy as np
import pytest

from repro.configs.base import SHAPES, shape_applicable
from repro.configs.registry import ARCHS
from repro.core import exprops, planspace, predictor
from repro.core import properties as props
from repro.core import workload as wl
from repro.core.workload import WorkloadSpec
from repro.distributed.plan import plan_for
from repro.obs import metrics as obs_metrics
from repro.obs import report as obs_report
from repro.obs import trace as obs_trace
from repro.obs.explain import (attribute_residual, attribute_residual_pv,
                               explain_program, score_explain)


class FakeClock:
    """Deterministic monotone clock: each call advances by ``tick``."""

    def __init__(self, tick: float = 1.0):
        self.t = 0.0
        self.tick = tick
        self.calls = 0

    def __call__(self) -> float:
        self.calls += 1
        v = self.t
        self.t += self.tick
        return v


# ---------------------------------------------------------------------------
# Tracer: span nesting, timing monotonicity, predicted overlay
# ---------------------------------------------------------------------------


def test_span_nesting_and_monotonic_timing():
    clk = FakeClock()
    tr = obs_trace.Tracer(clock=clk)          # epoch consumes tick 0
    with tr.span("outer", predicted_s=3.0) as outer:   # start t=1
        with tr.span("inner") as inner:                # start t=2
            pass                                       # finish t=3
        outer.set(tokens=7)                            # finish t=4
    assert len(tr.spans) == 2
    # completion order: child lands before parent
    sp_inner, sp_outer = tr.spans
    assert sp_inner.name == "inner" and sp_outer.name == "outer"
    assert sp_outer.depth == 0 and sp_inner.depth == 1
    # fake clock: outer spans [1, 4), inner [2, 3) — strictly contained
    assert sp_outer.t_start_s == 1.0 and sp_outer.duration_s == 3.0
    assert sp_inner.t_start_s == 2.0 and sp_inner.duration_s == 1.0
    assert sp_inner.t_start_s >= sp_outer.t_start_s
    assert (sp_inner.t_start_s + sp_inner.duration_s
            <= sp_outer.t_start_s + sp_outer.duration_s)
    assert sp_outer.args["tokens"] == 7
    assert sp_outer.predicted_s == 3.0
    assert sp_outer.gap_s == pytest.approx(0.0)
    assert sp_inner.gap_s is None            # no prediction on the child
    assert inner.duration_s == 1.0           # live handle sees the result


def test_span_predicted_can_arrive_late():
    tr = obs_trace.Tracer(clock=FakeClock())
    with tr.span("decode") as sp:
        sp.set(predicted_s=0.25, rid=3)
    assert tr.spans[0].predicted_s == 0.25
    assert tr.spans[0].args == {"rid": 3}


def test_summary_and_report_lines():
    tr = obs_trace.Tracer(clock=FakeClock())
    for _ in range(3):
        with tr.span("step", predicted_s=1.0):
            pass
    summ = tr.summary()["step"]
    assert summ["count"] == 3
    assert summ["measured_s"] == pytest.approx(3.0)
    assert summ["predicted_s"] == pytest.approx(3.0)
    assert summ["gap_s"] == pytest.approx(0.0)
    (line,) = tr.report_lines()
    assert line.startswith("step: n=3 measured=3000.00ms "
                           "predicted=3000.00ms")
    assert "ratio=1.00x" in line


# ---------------------------------------------------------------------------
# Chrome-trace export schema
# ---------------------------------------------------------------------------


def test_chrome_trace_schema_and_overlay():
    tr = obs_trace.Tracer(clock=FakeClock(), process_name="unit")
    with tr.span("outer", predicted_s=2.5):
        with tr.span("inner"):
            pass
    tr.instant("drift_event", direction="up")
    d = tr.to_chrome_trace()
    assert set(d) == {"traceEvents", "displayTimeUnit", "otherData"}
    ev = d["traceEvents"]

    meta = [e for e in ev if e["ph"] == "M"]
    names = {(e["name"], e["tid"]): e["args"]["name"] for e in meta}
    assert names[("process_name", obs_trace.MEASURED_TID)] == "unit"
    assert names[("thread_name", obs_trace.MEASURED_TID)] == "measured"
    assert names[("thread_name", obs_trace.PREDICTED_TID)] == "predicted"

    xs = [e for e in ev if e["ph"] == "X"]
    measured = [e for e in xs if e["tid"] == obs_trace.MEASURED_TID]
    predicted = [e for e in xs if e["tid"] == obs_trace.PREDICTED_TID]
    # export re-sorts by start time: parent precedes child
    assert [e["name"] for e in measured] == ["outer", "inner"]
    # ts/dur are microseconds (fake clock: outer [1s, 4s))
    assert measured[0]["ts"] == pytest.approx(1e6)
    assert measured[0]["dur"] == pytest.approx(3e6)
    # the predicted overlay: sibling event, same ts, dur = predicted
    (ov,) = predicted
    assert ov["name"] == "outer (predicted)"
    assert ov["ts"] == measured[0]["ts"]
    assert ov["dur"] == pytest.approx(2.5e6)
    assert ov["args"]["gap_s"] == pytest.approx(3.0 - 2.5)

    (inst,) = [e for e in ev if e["ph"] == "i"]
    assert inst["name"] == "drift_event"
    assert inst["args"] == {"direction": "up"}


def test_trace_save_round_trip(tmp_path):
    tr = obs_trace.Tracer(clock=FakeClock())
    with tr.span("s", predicted_s=1.0):
        pass
    path = tmp_path / "sub" / "trace.json"   # save creates parents
    tr.save(str(path))
    d = json.loads(path.read_text())
    assert any(e.get("tid") == obs_trace.PREDICTED_TID
               for e in d["traceEvents"] if e["ph"] == "X")
    assert not [p for p in os.listdir(path.parent)
                if p.endswith(".tmp")], "tmp files must not leak"


# ---------------------------------------------------------------------------
# Disabled tracer: a true no-op
# ---------------------------------------------------------------------------


def test_disabled_tracer_is_noop():
    clk = FakeClock()
    tr = obs_trace.Tracer(enabled=False, clock=clk)
    epoch_calls = clk.calls                  # __init__ reads the epoch once
    s1 = tr.span("a", predicted_s=1.0, x=1)
    s2 = tr.span("b")
    assert s1 is s2, "disabled span() must hand out ONE shared null object"
    with s1 as sp:
        sp.set(predicted_s=2.0, y=3)         # must not raise
    tr.instant("marker")
    assert clk.calls == epoch_calls, "disabled path must never read the clock"
    assert tr.spans == [] and tr.instants == []
    assert tr.report_lines() == []


def _host_events(logdir):
    """(name, stats) of every host-plane event of the one profile under
    ``logdir``."""
    import glob

    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(str(logdir), "**", "*.xplane.pb"),
                        recursive=True)
    return [(e.name, dict(e.stats))
            for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events]


@pytest.mark.parametrize("enabled", [True, False])
def test_span_lands_on_the_profiler_host_plane(tmp_path, enabled):
    import jax

    tr = obs_trace.Tracer(enabled=enabled, clock=FakeClock())
    jax.profiler.start_trace(str(tmp_path))
    try:
        with tr.span("probe.outer") as sp:
            sp.set(rows=7)
            with tr.span("probe.inner"):
                pass
    finally:
        jax.profiler.stop_trace()
    events = dict(_host_events(tmp_path))
    assert "probe.outer" in events and "probe.inner" in events
    assert events["probe.outer"]["rows"] == 7
    assert [s.name for s in tr.spans] == (
        ["probe.inner", "probe.outer"] if enabled else [])


def test_disabled_tracer_records_nothing_under_the_profiler(tmp_path):
    import jax

    clk = FakeClock()
    tr = obs_trace.Tracer(enabled=False, clock=clk)
    epoch_calls = clk.calls
    jax.profiler.start_trace(str(tmp_path))
    try:
        with tr.span("probe", predicted_s=1.0, x=1) as sp:
            sp.set(predicted_s=2.0, y=3)
        tr.instant("marker")
    finally:
        jax.profiler.stop_trace()
    assert clk.calls == epoch_calls
    assert tr.spans == [] and tr.instants == []
    # outside a profiler session the shared null span is back
    assert tr.span("a") is tr.span("b")


def test_trainer_iteration_is_a_profiler_step(tmp_path):
    import jax

    from repro.data.pipeline import DataConfig
    from repro.runtime.trainer import Trainer, TrainerConfig

    cfg = ARCHS["smollm-360m"].reduced()
    trainer = Trainer(cfg, DataConfig(vocab_size=cfg.vocab_size, seq_len=32,
                                      global_batch=2),
                      TrainerConfig(log_every=1000))
    trainer.train(1)                          # compiles outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        trainer.train(2)
    finally:
        jax.profiler.stop_trace()
    events = _host_events(tmp_path)
    iters = [st for name, st in events if name == "train.iter"]
    assert [st["step_num"] for st in iters] == [1, 2]
    names = {name for name, _ in events}
    for want in ("train.sync", "train.batch", "train_step",
                 "train.dispatch", "train.wait", "train.pull"):
        assert want in names, want
    assert "train.ckpt" not in names and "train.calib" not in names


def test_module_tracer_default_disabled_and_swap():
    assert obs_trace.get_tracer().enabled is False
    t = obs_trace.Tracer(clock=FakeClock(), process_name="t")
    prev = obs_trace.set_tracer(t)
    try:
        assert obs_trace.get_tracer() is t
    finally:
        obs_trace.set_tracer(prev)
    assert obs_trace.get_tracer() is prev


def test_planspace_emits_one_span_per_sweep():
    cfg = ARCHS["smollm-360m"]
    spec = wl.from_shape(SHAPES["train_4k"])
    plan = plan_for(cfg, SHAPES["train_4k"])
    space = planspace.PlanSpace.from_product(
        cfg, spec, [plan], [{"data": 16, "model": 16}])
    t = obs_trace.Tracer(process_name="test")
    prev = obs_trace.set_tracer(t)
    try:
        space.scores()
    finally:
        obs_trace.set_tracer(prev)
    assert [s.name for s in t.spans] == ["planspace.scores"]
    assert t.spans[0].args["cells"] == 1


# ---------------------------------------------------------------------------
# score_explain ≡ fused GEMV (rtol 1e-9, every registered arch)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_score_explain_matches_fused_scores(arch):
    cfg = ARCHS[arch]
    shape = SHAPES["train_4k"]
    ok, why = shape_applicable(cfg, shape)
    if not ok:
        pytest.skip(why)
    spec = wl.from_shape(shape)
    plan = plan_for(cfg, shape)
    mesh = {"data": 16, "model": 16}
    model = predictor.resolve_model(None)

    space = planspace.PlanSpace.from_product(cfg, spec, [plan], [mesh])
    fused = float(space.scores(model)[0])

    exp = score_explain(cfg, spec, plan, mesh, model=model)
    assert exp.total_seconds == pytest.approx(fused, rel=1e-9)
    # the decomposition is exact: rows sum to the total
    assert sum(r.seconds for r in exp.rows) == pytest.approx(
        exp.total_seconds, rel=1e-12)
    assert sum(r.share for r in exp.rows) == pytest.approx(1.0, rel=1e-9)
    # grouped views re-sum to the same total
    assert sum(exp.by_group().values()) == pytest.approx(fused, rel=1e-9)
    assert sum(exp.by_source().values()) == pytest.approx(fused, rel=1e-9)
    assert sum(exp.by_property().values()) == pytest.approx(fused, rel=1e-9)
    assert set(exp.by_group()) <= set(props.CATEGORIES)
    assert set(exp.by_source()) <= {"step", "collective", "launch"}
    assert exp.report()          # renders without raising


def test_score_explain_entry_points_agree():
    cfg = ARCHS["glm4-9b"]
    shape = SHAPES["train_4k"]
    plan = plan_for(cfg, shape)
    mesh = {"data": 16, "model": 16}
    via_predictor = predictor.score_explain(cfg, shape, plan, mesh)
    direct = score_explain(cfg, wl.from_shape(shape), plan, mesh)
    assert via_predictor.total_seconds == pytest.approx(
        direct.total_seconds, rel=1e-12)
    # and the fused-vs-explained check holds for the decode phase too
    dshape = SHAPES["decode_32k"]
    dplan = plan_for(cfg, dshape)
    dspec = wl.from_shape(dshape)
    dspace = planspace.PlanSpace.from_product(cfg, dspec, [dplan], [mesh])
    dexp = score_explain(cfg, dspec, dplan, mesh)
    assert dexp.total_seconds == pytest.approx(
        float(dspace.scores()[0]), rel=1e-9)
    assert dexp.phase == "decode"


def test_basis_program_explain_method():
    cfg = ARCHS["smollm-360m"]
    spec = wl.from_shape(SHAPES["train_4k"])
    model = predictor.resolve_model(None)
    prog = predictor.step_program(cfg, spec, "none")
    env = spec.env(cfg)
    env["M"] = 1
    rows = prog.explain(env, model)
    assert rows == explain_program(prog, env, model)
    total = sum(sec for _, sec, _, _ in rows)
    assert total == pytest.approx(float(prog.score(env, model)), rel=1e-9)


# ---------------------------------------------------------------------------
# Residual attribution: recover an injected perturbation
# ---------------------------------------------------------------------------


def _varied_envs(cfg, n=16):
    # batch/seq values deliberately OFF the ceil granularities (128-token
    # tiles, 16k chunks): on-grid windows make every ceil term an exact
    # multiple of B*S and the basis columns collinear — no single-term
    # perturbation is identifiable from such a window
    batches = (3, 5, 7, 9)
    seqs = (260, 388, 516, 644, 772, 900)
    envs = []
    for i in range(n):
        spec = WorkloadSpec(phase="train", global_batch=batches[i % 4],
                            seq_len=seqs[i % 6])
        env = spec.env(cfg)
        env["M"] = 1
        envs.append(env)
    return envs


def test_attribute_residual_recovers_injected_term_error():
    cfg = ARCHS["smollm-360m"]
    model = predictor.resolve_model(None)
    prog = predictor.step_program(cfg, wl.from_shape(SHAPES["train_4k"]),
                                  "none")
    envs = _varied_envs(cfg)
    # pick a live term whose value VARIES across the window (identifiable)
    per_env = [dict(((t, s) for t, s, _, _ in explain_program(
        prog, e, model))) for e in envs]
    terms = [t for t in per_env[0] if t != "1"]
    B = np.asarray([[d[t] for t in terms] for d in per_env])

    def unexplained(j):
        # seconds² of column j the OTHER columns cannot reproduce: the
        # attribution can only pin a perturbation on a term whose window
        # signature is not a linear mix of the rest of the basis
        y = B[:, j]
        X = np.delete(B, j, axis=1)
        coef = np.linalg.lstsq(X, y, rcond=None)[0]
        return float(((y - X @ coef) ** 2).sum())

    j_target = max(range(len(terms)), key=unexplained)
    target = terms[j_target]
    assert unexplained(j_target) > 0, "window must isolate the target"
    eps_true = 0.2
    measured = [sum(d.values()) + eps_true * d[target] for d in per_env]

    att = attribute_residual(prog, model, envs, measured)
    assert att.n_samples == len(envs)
    assert att.shares()[target] > 0.9, att.shares()
    i = att.columns.index(target)
    assert att.epsilon[i] == pytest.approx(eps_true, abs=0.02)
    # the attributed miss reconstructs the mean residual
    assert float(np.sum(att.miss_seconds)) == pytest.approx(
        att.residual_s, rel=1e-2)
    assert att.line().startswith("residual=")


def test_attribute_residual_pv_property_basis():
    rng = np.random.default_rng(3)
    model = predictor.resolve_model(None)
    priced = [k for k, w in zip(model.keys, model.weights) if w][:4]
    assert len(priced) >= 2
    pvs = [{k: float(rng.uniform(1e6, 1e9)) for k in priced}
           for _ in range(16)]
    target = priced[0]
    w = dict(zip(model.keys, model.weights))
    measured = [model.predict(pv) + 0.3 * w[target] * pv[target]
                for pv in pvs]
    att = attribute_residual_pv(model, pvs, measured)
    assert att.shares()[target] > 0.9
    i = att.columns.index(target)
    assert att.epsilon[i] == pytest.approx(0.3, rel=1e-2)
    assert att.group_shares()[props.category(target)] > 0.9


def test_attribute_residual_zero_residual_attributes_nothing():
    cfg = ARCHS["smollm-360m"]
    model = predictor.resolve_model(None)
    prog = predictor.step_program(cfg, wl.from_shape(SHAPES["train_4k"]),
                                  "none")
    envs = _varied_envs(cfg, n=6)
    measured = [sum(s for _, s, _, _ in explain_program(prog, e, model))
                for e in envs]
    att = attribute_residual(prog, model, envs, measured)
    assert att.residual_s == pytest.approx(0.0, abs=1e-12)
    assert float(np.abs(att.miss_seconds).sum()) == pytest.approx(
        0.0, abs=1e-9)


# ---------------------------------------------------------------------------
# Metrics registry
# ---------------------------------------------------------------------------


def test_counter_semantics():
    reg = obs_metrics.MetricsRegistry()
    c = reg.counter("events_total", "help text")
    c.inc()
    c.inc(2.5)
    c.inc(1, phase="decode")
    assert c.value() == 3.5
    assert c.value(phase="decode") == 1.0
    assert c.value(phase="absent") == 0.0
    with pytest.raises(ValueError):
        c.inc(-1)
    assert reg.counter("events_total") is c     # get-or-create


def test_gauge_semantics():
    g = obs_metrics.MetricsRegistry().gauge("occupancy")
    g.set(7)
    g.inc(2)
    g.dec(4)
    assert g.value() == 5.0
    g.set(1.5, ring="a")
    assert g.value(ring="a") == 1.5


def test_histogram_semantics():
    reg = obs_metrics.MetricsRegistry()
    h = reg.histogram("lat_seconds", buckets=(0.1, 1.0, 10.0))
    for v in (0.05, 0.5, 5.0, 50.0):
        h.observe(v)
    assert h.value() == 4.0                     # count
    assert h.sum() == pytest.approx(55.55)
    d = h.to_json_dict()
    (s,) = d["samples"]
    assert s["bucket_counts"] == [1.0, 2.0, 3.0]   # cumulative
    assert s["count"] == 4.0
    text = h.render()
    assert 'lat_seconds_bucket{le="0.1"} 1' in text
    assert 'lat_seconds_bucket{le="+Inf"} 4' in text
    assert "lat_seconds_count 4" in text


def test_registry_type_clash_raises():
    reg = obs_metrics.MetricsRegistry()
    reg.counter("x_total")
    with pytest.raises(ValueError, match="already registered"):
        reg.gauge("x_total")


def test_metric_name_validation():
    with pytest.raises(ValueError):
        obs_metrics.Counter("bad name")


def test_render_prometheus_golden():
    reg = obs_metrics.MetricsRegistry()
    c = reg.counter("repro_events_total", "events by kind")
    c.inc(3, kind="a")
    c.inc(1.5, kind="b")
    g = reg.gauge("repro_height")
    g.set(2.25)
    reg.counter("repro_untouched_total")
    assert reg.render() == (
        "# HELP repro_events_total events by kind\n"
        "# TYPE repro_events_total counter\n"
        'repro_events_total{kind="a"} 3\n'
        'repro_events_total{kind="b"} 1.5\n'
        "# TYPE repro_height gauge\n"
        "repro_height 2.25\n"
        "# TYPE repro_untouched_total counter\n"
        "repro_untouched_total 0\n"
    )


def test_registry_json_dump_and_reset(tmp_path):
    reg = obs_metrics.MetricsRegistry()
    reg.counter("c_total").inc(2, k="v")
    reg.histogram("h_seconds", buckets=(1.0,)).observe(0.5)
    path = tmp_path / "m" / "metrics.json"
    reg.save_json(str(path))
    d = json.loads(path.read_text())
    assert d["kind"] == "metrics" and d["schema"] == 1
    by_name = {m["name"]: m for m in d["metrics"]}
    assert by_name["c_total"]["samples"] == [
        {"labels": {"k": "v"}, "value": 2.0}]
    assert by_name["h_seconds"]["buckets"] == [1.0]
    reg.reset()
    assert reg.counter("c_total").value(k="v") == 0.0
    assert "c_total" in reg                   # registration survives reset


def test_process_registry_has_framework_families():
    # producers register at import time: the process-wide registry must
    # already know the cache / telemetry / report families
    text = obs_metrics.REGISTRY.render()
    for name in ("repro_basis_cache_hits_total",
                 "repro_compile_cache_events_total",
                 "repro_telemetry_samples_total",
                 "repro_lru_evictions_total"):
        assert name in text, name


def test_basis_cache_counters_flow_to_registry():
    cfg = ARCHS["smollm-360m"]
    spec = wl.from_shape(SHAPES["train_4k"])
    plan = plan_for(cfg, SHAPES["train_4k"])
    space = planspace.PlanSpace.from_product(
        cfg, spec, [plan], [{"data": 16, "model": 16}])
    hits = obs_metrics.REGISTRY.counter("repro_basis_cache_hits_total")
    misses = obs_metrics.REGISTRY.counter("repro_basis_cache_misses_total")
    h0, m0 = hits.value(), misses.value()
    cache = exprops.BasisCache()
    space.scores(cache=cache)                 # cold: misses
    space.scores(cache=cache)                 # warm: hits
    assert misses.value() > m0
    assert hits.value() > h0


# ---------------------------------------------------------------------------
# Structured report lines
# ---------------------------------------------------------------------------


def test_report_emit_format_and_counting():
    got = []
    line = obs_report.emit("admit", {"rid": 3, "score": 1.25,
                                     "pred": "0.006ms"},
                           text="policy=model", printer=got.append)
    assert line == "[admit] rid=3 score=1.25 pred=0.006ms policy=model"
    assert got == [line]
    assert obs_report.emit("quiet", printer=None) == "[quiet]"
    assert obs_report.emit("t", text="only text", printer=got.append) \
        == "[t] only text"
    assert got[-1] == "[t] only text"


# ---------------------------------------------------------------------------
# Crash-safe telemetry save (regression: truncated artifact)
# ---------------------------------------------------------------------------


def test_telemetry_save_failure_keeps_previous_artifact(tmp_path,
                                                        monkeypatch):
    from repro.calibration import telemetry
    sink = telemetry.TelemetrySink(capacity=8)
    for i in range(3):
        sink.record({"flops": 1e9 + i}, 0.01 * (i + 1), step=i)
    path = tmp_path / "telemetry.json"
    sink.save(str(path))
    good = path.read_text()

    # a crash mid-serialization: json.dump writes half a document and dies
    def exploding_dump(obj, f, **kw):
        f.write('{"kind": "telemetry", "samples": [[0,')
        raise OSError("disk full")

    monkeypatch.setattr(telemetry.json, "dump", exploding_dump)
    sink.record({"flops": 5e9}, 0.5)
    with pytest.raises(OSError, match="disk full"):
        sink.save(str(path))
    monkeypatch.undo()

    # the artifact is byte-identical to the last good save — not truncated
    assert path.read_text() == good
    loaded = telemetry.TelemetrySink.load(str(path))
    assert len(loaded) == 3
    assert not [p for p in os.listdir(tmp_path) if p.endswith(".tmp")], \
        "failed save must clean up its temp file"


def test_metrics_save_json_failure_keeps_previous_artifact(tmp_path,
                                                           monkeypatch):
    reg = obs_metrics.MetricsRegistry()
    reg.counter("c_total").inc()
    path = tmp_path / "metrics.json"
    reg.save_json(str(path))
    good = path.read_text()

    def exploding_dump(obj, f, **kw):
        f.write('{"kind": "met')
        raise OSError("disk full")

    monkeypatch.setattr(obs_metrics.json, "dump", exploding_dump)
    with pytest.raises(OSError):
        reg.save_json(str(path))
    monkeypatch.undo()
    assert path.read_text() == good
    assert not [p for p in os.listdir(tmp_path) if p.endswith(".tmp")]
