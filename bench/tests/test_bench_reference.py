"""Each reference against the program's loss, gradients and optimizer
steps at the program's reduced sizes, and the fp8 control against the
reference."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import check, harness, spec, traffic
from bench.models import common
from bench_tiny import ROOT, TINY_LIMITS, TINY_TRAFFIC, tiny_config

#: the limits of the numbers a reference run gives (``batch`` needs a loader)
LIMITS = {k: v for k, v in TINY_LIMITS.items() if k != "batch"}


def _setup(family, dtype):
    arch, config = tiny_config(family)
    arch = dataclasses.replace(arch, param_dtype=dtype, compute_dtype=dtype)
    config = dict(config, param_dtype=dtype, compute_dtype=dtype)
    ref = spec.load_module(ROOT / f"bench/models/{family}.py", f"r_{family}")
    docs = traffic.Documents(TINY_TRAFFIC, config["vocab_size"], 5, 6)
    batches = [traffic.pack_rows(docs, range(2 * k, 2 * k + 2))
               for k in range(3)]
    return arch, config, ref, batches


def _program(arch, config, params, batches):
    """The program's own train step, as the trainer jits it."""
    from repro.optim import optimizers as opt
    from repro.runtime import steps

    tr = config["training"]
    optimizer = opt.get_optimizer("adamw")
    step = jax.jit(steps.make_train_step(
        arch, optimizer, lr_schedule=opt.warmup_cosine(
            tr["lr"], tr["warmup"], tr["total_steps"])))
    state = steps.TrainState(params, optimizer.init(params),
                             jnp.zeros((), jnp.int32))
    losses, first = [], None
    for k, b in enumerate(batches):
        state, m = step(state, {x: jnp.asarray(v) for x, v in b.items()})
        losses.append(float(m["loss"]))
        if k == 0:
            first = [g / (1 - tr["b1"])
                     for g in common.leaf_norms(state.opt_state["m"])]
    change = common.leaf_norms(jax.tree.map(
        lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32),
        state.params, params))
    return {"losses": losses, "first_grad": first, "change": change}


@pytest.mark.parametrize("family", ["dense", "ssm"])
def test_reference_matches_program_in_float32(family):
    """At float32 the program and the reference agree to rounding: the
    loss, each leaf's first gradient and its change over three steps."""
    arch, config, ref, batches = _setup(family, "float32")
    params = jax.jit(lambda k: ref.init_params(config, k))(
        jax.random.PRNGKey(3))
    prog = _program(arch, config, params, batches)
    theirs = common.train_steps(ref.block_loss, config, params, batches,
                                common.Ops())
    np.testing.assert_allclose(prog["losses"], theirs["losses"], rtol=1e-5)
    np.testing.assert_allclose(prog["first_grad"], theirs["first_grad"],
                               rtol=1e-3)
    np.testing.assert_allclose(prog["change"], theirs["change"], rtol=1e-3,
                               atol=1e-9)
    assert min(c for c, g in zip(theirs["change"], theirs["first_grad"])
               if g > 0) > 0  # every leaf with a gradient moved


@pytest.mark.parametrize("family", ["dense", "ssm"])
def test_bf16_program_within_and_fp8_control_outside_the_limits(family):
    """The configuration's bfloat16 program passes the tiny cells' limits;
    the same reference computed in fp8 (the control) fails one of them."""
    arch, config, ref, batches = _setup(family, "bfloat16")
    params = jax.jit(lambda k: ref.init_params(config, k))(
        jax.random.PRNGKey(4))
    theirs = common.train_steps(ref.block_loss, config, params, batches,
                                common.Ops())
    prog = _program(arch, config, params, batches)
    assert check.passed(check.judge(check.readings(prog, theirs),
                                    LIMITS))
    control = common.train_steps(ref.block_loss, config, params, batches,
                                 common.Fp8Ops())
    readings = check.readings(control, theirs)
    assert not check.passed(check.judge(readings, LIMITS)), readings


def test_seeds_make_the_same_weights_on_every_call():
    arch, config, ref, _ = _setup("dense", "bfloat16")
    make = jax.jit(lambda k: ref.init_params(config, k))
    a = make(harness.weights_key(2 ** 31 + 9))
    b = make(harness.weights_key(2 ** 31 + 9))
    c = make(harness.weights_key(2 ** 31 + 10))
    same = jax.tree.map(lambda x, y: bool(jnp.all(x == y)), a, b)
    assert all(jax.tree.leaves(same))
    assert not bool(jnp.all(a["embed"]["w"] == c["embed"]["w"]))


def test_leaf_gap_and_still_leaves():
    assert check.leaf_gap([1.0, 2.2], [1.0, 2.0]) == pytest.approx(0.1)
    # a leaf far below the median is measured against the median
    assert check.leaf_gap([0.02, 4.0, 4.0], [0.01, 4.0, 4.0]) == \
        pytest.approx(0.01 / 4.0)
    assert check.moving_leaves([1e-9, 1.0, 2.0]) == [False, True, True]
    unchanged = check.readings(
        {"losses": [1.0], "first_grad": [1.0, 1.0], "change": [0.0, 0.0]},
        {"losses": [1.0], "first_grad": [1.0, 1.0], "change": [0.1, 0.3]})
    assert unchanged["update"] == pytest.approx(1.0)
    assert unchanged["update_median"] == pytest.approx(0.75)  # 0.5 and 1
    gaps = check.readings(
        {"losses": [1.1, 2.0], "first_grad": [1.0, 2.0, 3.3],
         "change": [1.0, 1.0, 1.0]},
        {"losses": [1.0, 2.0], "first_grad": [1.0, 2.0, 3.0],
         "change": [1.0, 1.0, 1.0]})
    assert gaps["loss_first"] == pytest.approx(0.1) == gaps["loss"]
    assert gaps["first_grad"] == pytest.approx(0.1)
    assert gaps["first_grad_median"] == 0.0
