"""The trace reduction and the per-layer readers, on constructed traces."""
import pytest

from bench import spec, tracing
from bench_tiny import ROOT

MS = 1_000_000  # ns


def _trace(ops, spans):
    return tracing.Trace(device_ops={"/device:TPU:0": ops}, host_spans=spans)


def test_union_merges_and_clips():
    assert tracing.union([(5, 8), (0, 3), (2, 4), (7, 12)], 1, 10) == [
        (1, 4), (5, 10)]
    assert tracing.union([(0, 1)], 2, 3) == []


def test_reduce_busy_window_gaps_and_top_ops():
    spans = [("trainer.train", 0, 100 * MS), ("loader.batch", 2 * MS, 8 * MS),
             ("trainer.train", 100 * MS, 200 * MS),
             ("loader.batch", 101 * MS, 109 * MS)]
    ops = [("%while.1 = (s32[], f32[8]{0}) while(...)", 10 * MS, 95 * MS),
           ("%fusion.1 = f32[8,128]{1,0:T(8,128)} fusion(...)", 10 * MS,
            60 * MS),
           ("%fusion.2 = bf16[4]{0} fusion(...)", 60 * MS, 95 * MS),
           ("%fusion.1 = f32[8,128]{1,0:T(8,128)} fusion(...)", 110 * MS,
            190 * MS),
           ("outside", 300 * MS, 400 * MS)]  # after the window: not counted
    red = tracing.reduce(_trace(ops, spans))
    assert red.steps == 2
    assert red.window_s == pytest.approx(0.2)
    assert red.busy_s == pytest.approx(0.165)  # 10..95 and 110..190
    # self time: the loop's body is its children's, not its own
    assert red.device_ops == [["fusion.1 = f32[8,128]", pytest.approx(0.13)],
                              ["fusion.2 = bf16[4]", pytest.approx(0.035)],
                              ["while.1 = (s32[], f32[8])", 0.0]]
    # gaps 0..10, 95..110 and 190..200, labelled at their midpoints
    assert red.idle_gaps == [
        ["trainer.train>loader.batch", pytest.approx(0.015)],
        ["trainer.train>loader.batch", pytest.approx(0.01)],
        ["trainer.train", pytest.approx(0.01)]]


def test_op_names_drop_layouts():
    assert tracing.op_name(
        "%copy.32 = bf16[49152,960]{1,0:T(8,128)(2,1)S(1)} copy(bf16[4]{0} "
        "%p)") == "copy.32 = bf16[49152,960]"
    assert tracing.op_name(
        "%copy-start.7 = (s32[7,2048]{1,0:T(8,128)S(1)}, u32[]{:S(2)}) "
        "copy-start(s32[7,2048]") == "copy-start.7 = (s32[7,2048], u32[])"
    assert tracing.op_name("fusion.3") == "fusion.3"


def test_reduce_finds_nothing_without_steps_or_ops():
    assert tracing.reduce(_trace([("f", 0, 5)], [])) is None
    assert tracing.reduce(tracing.Trace({}, [("trainer.train", 0, 5)])) is None


def test_busy_is_averaged_over_chips():
    spans = [("trainer.train", 0, 100)]
    tr = tracing.Trace({"/device:TPU:0": [("a", 0, 100)],
                        "/device:TPU:1": [("a", 0, 50)]}, spans)
    assert tracing.reduce(tr).busy_s == pytest.approx(75e-9)


@pytest.mark.parametrize("name", ["train_mfu", "device_idle_share.train",
                                  "attn_roofline.train",
                                  "host_stall_ms.train"])
def test_readers_return_none_when_nothing_to_read(name):
    cell = spec.load_cell("smollm-360m.train.seq2k", ROOT)
    reader = cell.reader(name)
    assert reader.read({"reduced": None, "program": None,
                        "flops_per_step": 1.0, "tokens_per_step": 1,
                        "peaks": {"bf16_flops_per_s": 1.0},
                        "ref": cell.reference(), "config": cell.config,
                        "traffic": cell.traffic}) is None


def test_readers_decompose_the_rate():
    """tokens/s = mfu × peak / flops-per-token × (1 − idle)."""
    cell = spec.load_cell("smollm-360m.train.seq2k", ROOT)
    red = tracing.Reduced(busy_s=0.8, window_s=1.0, steps=2,
                          device_ops=[], idle_gaps=[])
    ctx = {"reduced": red, "flops_per_step": 4e12,
           "peaks": {"bf16_flops_per_s": 2e13}}
    mfu = cell.reader("train_mfu").read(ctx)
    idle = cell.reader("device_idle_share.train").read(ctx)
    assert mfu == pytest.approx(100 * 8e12 / (0.8 * 2e13))
    assert idle == pytest.approx(20.0)
    tokens_per_step = 1000
    rate = mfu / 100 * 2e13 / (4e12 / tokens_per_step) * (1 - idle / 100)
    assert rate == pytest.approx(red.steps * tokens_per_step / red.window_s)


def test_load_reads_a_recorded_profile(tmp_path):
    """A real (CPU) profile: host spans come back by name."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    for _ in range(2):
        with jax.profiler.TraceAnnotation(tracing.STEP_SPAN):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    tr = tracing.load(tracing.find_xplane(str(tmp_path)))
    assert [n for n, _, _ in tr.host_spans] == [tracing.STEP_SPAN] * 2
    assert tr.device_ops == {}  # no TPU plane on the CPU
    assert tracing.reduce(tr) is None
