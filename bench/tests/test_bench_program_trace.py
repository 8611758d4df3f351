"""The program's spans and named scopes read from a profiler trace
(``bench/program_trace.py``): on constructed traces, on a recorded CPU
profile of a jitted gradient, and on the tiny cells' trainers."""
import json
import re
import types

import pytest

from bench import harness, program_trace as pt, spec, tracing
from bench_tiny import ROOT, tiny_config
from repro.obs import scopes

MS = 1_000_000  # ns


@pytest.mark.parametrize("op_name,scope", [
    ("jit(train_step)/jvp(attention)/attention_core/dot_general",
     scopes.ATTENTION_CORE),
    ("jit(train_step)/transpose(jvp(attention_core))/dot_general",
     scopes.ATTENTION_CORE),
    ("jit(f)/transpose(jvp(jvp()))/checkpoint/rematted_computation/"
     "attention/attention_core/exp", scopes.ATTENTION_CORE),
    ("jit(train_step)/jvp(attention)/mul", scopes.ATTENTION),
    ("jit(train_step)/optimizer/mlp_scale/add", scopes.OPTIMIZER),
    ("jit(train_step)/jit(mlp)/add", scopes.MLP),
    ("jit(train_step)/jvp(embed)/gather", pt.NO_SCOPE),
    ("", pt.NO_SCOPE),
])
def test_scope_is_the_innermost_listed_name(op_name, scope):
    assert pt.scope_of(op_name) == scope


def test_hlo_join_reads_instruction_names_and_modules():
    text = "\n".join([
        "HloModule jit_train_step, is_scheduled=true",
        "%fused (p: f32[8]) -> f32[8] {",
        '  %exp.1 = f32[8]{0} exponential(%p), metadata={op_name='
        '"jit(train_step)/attention/attention_core/exp" '
        'stack_frame_id=4}',
        '  ROOT %copy-start.7 = (f32[8]{0}, u32[]) copy-start(%p), '
        'metadata={op_name="jit(train_step)/mlp/copy"}',
        "  %p = f32[8]{0} parameter(0)",
        "}"])
    assert pt.hlo_module(text) == "jit_train_step"
    assert pt.hlo_scopes(text) == {"exp.1": scopes.ATTENTION_CORE,
                                   "copy-start.7": scopes.MLP,
                                   "p": pt.NO_SCOPE}
    assert pt.instruction("%copy-start.7 = (f32[8]{0:T(8)}, u32[]) "
                          "copy-start(f32[8] %p)") == "copy-start.7"
    assert pt.instruction("fusion.3") == "fusion.3"


def _constructed():
    spans = [("train.iter", 0, 100 * MS), ("train.sync", 0, 2 * MS),
             ("train.batch", 2 * MS, 10 * MS), ("train_step", 10 * MS,
                                                 95 * MS),
             ("train.dispatch", 10 * MS, 12 * MS),
             ("train.wait", 12 * MS, 95 * MS),
             ("train.pull", 95 * MS, 100 * MS)]
    ops = [("(no scope)", 11 * MS, 90 * MS),        # a loop round the layers
           (scopes.ATTENTION_CORE, 11 * MS, 50 * MS),
           (scopes.MLP, 60 * MS, 90 * MS),
           (scopes.OPTIMIZER, 92 * MS, 94 * MS)]
    return pt.ProgramTrace(steps=[(0, 100 * MS)], spans=spans,
                           ops={"/device:TPU:0": {"XLA Ops": ops}})


def test_scopes_take_device_self_time():
    got = dict(pt.by_scope(_constructed()))
    assert got == {scopes.ATTENTION_CORE: pytest.approx(0.039),
                   scopes.MLP: pytest.approx(0.030),
                   pt.NO_SCOPE: pytest.approx(0.010),
                   scopes.OPTIMIZER: pytest.approx(0.002)}


def test_host_stall_leaves_out_idle_time_under_the_wait():
    trace = _constructed()
    idle = pt.idle_by_span(trace)
    # idle: 0..11 (sync 2, batch 8, dispatch 1), 90..92 and 94..95 under
    # the wait, 95..100 under the pull
    assert idle == {"train.sync": pytest.approx(0.002),
                    "train.batch": pytest.approx(0.008),
                    "train.dispatch": pytest.approx(0.001),
                    "train.wait": pytest.approx(0.003),
                    "train.pull": pytest.approx(0.005)}
    assert pt.host_stall_ms(trace) == pytest.approx(16.0)


def test_nothing_to_read_without_program_spans_or_scopes():
    trace = _constructed()
    ops = trace.ops["/device:TPU:0"]["XLA Ops"]
    bare = pt.ProgramTrace(trace.steps, [], {"/device:TPU:0": {
        "XLA Ops": [(pt.NO_SCOPE, s, e) for _, s, e in ops]}})
    assert pt.host_stall_ms(bare) is None
    assert pt.attn_roofline(bare, 1e12, 1e15) is None
    assert pt.by_scope(bare) == [[pt.NO_SCOPE, pytest.approx(0.081)]]
    # 1e12 FLOPs a step in 0.039 s at 1e14 FLOP/s: 25.6 % of the roofline
    assert pt.attn_roofline(trace, 1e12, 1e14) == pytest.approx(
        100 * 1e12 / (0.039 * 1e14))


def _cpu_ops(plane, line):
    """A CPU run's XLA threads, standing in for a chip's op line."""
    return plane == "/host:CPU" and line.startswith("tf_XLA")


def test_scope_join_on_a_checkpointed_gradient(tmp_path):
    """Forward, recomputed forward and backward ops of a scope nested in
    another, under ``jax.checkpoint``, all belong to the inner scope; every
    op event of the program's module finds its instruction."""
    import jax
    import jax.numpy as jnp

    def block(x):
        with jax.named_scope(scopes.ATTENTION):
            y = jnp.sin(x) @ x
            with jax.named_scope(scopes.ATTENTION_CORE):
                y = jnp.tanh(y) @ x
            return (y * x).sum()

    f = jax.jit(jax.value_and_grad(jax.checkpoint(block)))
    x = jnp.ones((64, 64))
    f(x)[0].block_until_ready()
    text = f.lower(x).compile().as_text()
    forms = {}
    for op_name in re.findall(r'op_name="([^"]*)"', text):
        if scopes.ATTENTION_CORE in op_name:
            form = ("recomputed" if "rematted_computation" in op_name
                    else "backward" if "transpose(" in op_name
                    else "forward")
            forms.setdefault(form, set()).add(pt.scope_of(op_name))
    assert forms == {f: {scopes.ATTENTION_CORE}
                     for f in ("forward", "recomputed", "backward")}

    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(tracing.STEP_SPAN):
        f(x)[0].block_until_ready()
    jax.profiler.stop_trace()
    from jax.profiler import ProfileData
    events = [e for plane in ProfileData.from_file(
        tracing.find_xplane(str(tmp_path))).planes for line in plane.lines
        for e in line.events
        if dict(e.stats).get("hlo_module") == pt.hlo_module(text)]
    by_instr = pt.hlo_scopes(text)
    missing = {e.name for e in events} - set(by_instr)
    assert events and not missing, missing


def test_step_hlo_text_has_this_builds_scopes(tmp_path):
    """Where the persistent cache holds the same step compiled without
    scopes (JAX's key leaves metadata out), the text still has the scopes
    of the code that runs now, and is that executable's program."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    def make(scoped):
        def train_step(state, batch):
            if scoped:
                with jax.named_scope(scopes.MLP):
                    return (jnp.sin(state) @ batch["x"]).sum()
            return (jnp.sin(state) @ batch["x"]).sum()
        return jax.jit(train_step)

    from jax.experimental.compilation_cache import compilation_cache

    settings = {"jax_compilation_cache_dir": str(tmp_path),
                "jax_persistent_cache_min_compile_time_secs": 0,
                "jax_persistent_cache_min_entry_size_bytes": 0}
    before = {k: getattr(jax.config, k) for k in settings}
    for k, v in settings.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()
    try:
        state = jnp.ones((64, 64))
        batch = {"x": np.ones((64, 64), np.float32)}
        make(False)(state, batch)  # fills the cache, without scopes
        cached = make(True).lower(state, batch).compile().as_text()
        trainer = types.SimpleNamespace(
            step_fn=make(True), state=state,
            loader=types.SimpleNamespace(batch=lambda step: batch))
        fresh = pt.step_hlo_text(trainer)
    finally:
        for k, v in before.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()
    assert f"/{scopes.MLP}/" not in cached
    assert f"/{scopes.MLP}/" in fresh
    assert set(pt.hlo_scopes(fresh)) == set(pt.hlo_scopes(cached))
    assert jax.config.jax_enable_compilation_cache


@pytest.fixture
def traced_cell(tiny_root, tmp_path):
    """A tiny cell's trainer after its checked steps, three steps traced
    the way the harness traces its window."""
    import jax

    def run(family):
        arch, _ = tiny_config(family)
        cell = spec.load_cell(f"{arch.name}.tiny", tiny_root)
        peaks = spec.peaks("cpu", tiny_root)
        ses = harness.Session(cell, 1.0, peaks)
        ses.start(2**31 + 77)
        ses.checked()
        logdir = tmp_path / f"trace-{family}"
        jax.profiler.start_trace(str(logdir))
        for _ in range(3):
            harness._train_one(ses.trainer)
        jax.profiler.stop_trace()
        path = tracing.find_xplane(str(logdir))
        return ses, peaks, path, pt.step_hlo_text(ses.trainer)
    return run


@pytest.mark.parametrize("family", ["dense", "ssm"])
def test_tiny_cells_read_scopes_and_host_stall(traced_cell, family):
    ses, peaks, path, text = traced_cell(family)
    red = tracing.reduce(tracing.load(path, _cpu_ops))
    trace = pt.load(path, text, _cpu_ops)
    # the benchmark's own reduction counts its own spans only
    assert red.steps == len(trace.steps) == 3
    assert {n for n, _, _ in tracing.load(path, _cpu_ops).host_spans} \
        <= set(tracing.HOST_SPANS)
    assert sum(1 for n, _, _ in trace.spans if n == "train.iter") == 3
    idle_ms = 1e3 * (red.window_s - red.busy_s) / red.steps
    stall = pt.host_stall_ms(trace)
    assert 0 <= stall <= idle_ms + 1e-9
    found = {n for n, t in pt.by_scope(trace) if t > 0}
    if family == "dense":
        assert {scopes.ATTENTION_CORE, scopes.ATTENTION, scopes.MLP,
                scopes.LM_HEAD_LOSS, scopes.OPTIMIZER} <= found
        work = (pt.attention_flops_per_token(ses.ref, ses.config, ses.seq)
                * ses.rows * ses.seq)
        roof = pt.attn_roofline(trace, work, peaks["bf16_flops_per_s"])
        assert 0 < roof <= 100
    else:
        assert scopes.ATTENTION_CORE not in found
        assert scopes.SSD_SCAN in found


@pytest.mark.parametrize("which", ["tiny", "smollm-360m"])
def test_attention_work_is_the_reference_term(which):
    from bench.models import dense

    if which == "tiny":
        _, c = tiny_config("dense")
    else:
        c = json.loads((ROOT / "bench/configs/smollm-360m.json").read_text())
    S = 2048 if which != "tiny" else 64
    L, H, dh = (c["num_hidden_layers"], c["num_attention_heads"],
                c["head_dim"])
    want = 6 * L * 2 * H * dh * (S / 2)
    assert pt.attention_flops_per_token(dense, c, S) == pytest.approx(
        want, rel=1e-12)
    if which != "tiny":  # 7 x 2048 tokens a step: 5.41 TFLOP
        assert want * 7 * S == pytest.approx(5.4117e12, rel=1e-4)
