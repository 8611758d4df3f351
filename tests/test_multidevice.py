"""Multi-device tests (8 virtual CPU devices, run in subprocesses so the
main pytest process keeps the single real device — see the dry-run brief).

Covers: compressed DP all-reduce (wire-format correctness + collective-byte
reduction in HLO), manual-DP train-step equivalence, sharded lowering of a
small arch on a (2, 4) mesh, and elastic-resume across mesh shapes.
"""
from __future__ import annotations

import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run8(body: str, timeout: int = 420) -> str:
    """Run ``body`` in a python subprocess with 8 virtual devices."""
    script = ("import os\n"
              "os.environ['XLA_FLAGS'] = "
              "'--xla_force_host_platform_device_count=8'\n"
              + textwrap.dedent(body))
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    p = subprocess.run([sys.executable, "-c", script], env=env,
                       capture_output=True, text=True, timeout=timeout)
    assert p.returncode == 0, f"STDOUT:\n{p.stdout}\nSTDERR:\n{p.stderr}"
    return p.stdout


def test_psum_compressed_matches_fp32_psum():
    run8("""
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P
    from repro.distributed import compression as comp

    from repro.launch.mesh import make_mesh
    mesh = make_mesh((8,), ('data',))
    x = jax.random.normal(jax.random.PRNGKey(0), (8, 4096))

    def body(xs):
        exact = jax.lax.psum(xs, 'data')
        approx = comp.psum_compressed(xs, 'data')
        return exact, approx

    f = jax.shard_map(body, mesh=mesh, in_specs=(P('data'),),
                      out_specs=(P(), P()), check_vma=False)
    exact, approx = f(x.reshape(8, 1, 4096))
    rel = float(jnp.max(jnp.abs(exact - approx))
                / jnp.max(jnp.abs(exact)))
    assert rel < 0.05, rel

    # wire bytes: compressed int8 must move ~4x less than fp32
    from repro.core import hloparse
    txt = jax.jit(f).lower(x.reshape(8, 1, 4096)).compile().as_text()
    coll = hloparse.collective_summary(txt)
    total = sum(coll.values())
    assert total > 0
    print('collective bytes:', coll)
    """)


def test_manual_dp_train_step_compression_converges_like_fp32():
    run8("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs.registry import ARCHS
    from repro.runtime import steps
    from repro.optim import optimizers as opt
    from repro.models import transformer

    cfg = ARCHS['smollm-360m'].reduced()
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((8,), ('data',))
    optimizer = opt.get_optimizer('adamw')
    params, _ = transformer.init_params(cfg, jax.random.PRNGKey(0))
    B, S = 16, 32
    batch = {
        'tokens': jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                                     cfg.vocab_size, jnp.int32),
        'labels': jax.random.randint(jax.random.PRNGKey(2), (B, S), 0,
                                     cfg.vocab_size, jnp.int32),
    }
    losses = {}
    for compression in (None, 'int8_ef'):
        st = steps.TrainState(params, optimizer.init(params),
                              jnp.zeros((), jnp.int32))
        fn, init_ef = steps.make_manual_dp_train_step(
            cfg, optimizer, mesh, compression=compression)
        fn = jax.jit(fn)
        ef = init_ef(params)
        ls = []
        for i in range(4):
            st, ef, m = fn(st, ef, batch)
            ls.append(float(m['loss']))
        losses[compression] = ls
    print('fp32 :', losses[None])
    print('int8 :', losses['int8_ef'])
    # same trajectory within quantization noise; both decreasing
    for a, b in zip(losses[None], losses['int8_ef']):
        assert abs(a - b) / a < 0.05, (a, b)
    assert losses['int8_ef'][-1] < losses['int8_ef'][0]
    """)


def test_sharded_train_lowering_small_mesh():
    run8("""
    import jax
    from repro.configs.base import SHAPES
    from repro.configs.registry import ARCHS
    from repro.distributed.plan import plan_for
    from repro.distributed.sharding import use_sharding
    from repro.launch.specs import step_and_specs
    from repro.core import extract as cx

    from repro.launch.mesh import make_mesh
    mesh = make_mesh((2, 4), ('data', 'model'))
    cfg, shape = ARCHS['smollm-360m'], SHAPES['train_4k']
    plan = plan_for(cfg, shape, tp_size=4)
    with mesh, use_sharding(mesh, plan):
        fn, specs, sh, osh = step_and_specs(cfg, shape, mesh, plan)
        compiled = jax.jit(fn, in_shardings=sh, out_shardings=osh).lower(*specs).compile()
    c = cx.extract_compiled(compiled)
    assert c.flops > 0 and c.collective_bytes, c
    print('ok', c.flops, c.collective_bytes)
    """)


def test_elastic_mesh_switch_resumes_from_checkpoint(tmp_path):
    run8(f"""
    import jax, jax.numpy as jnp, numpy as np
    from repro.checkpoint import store
    from repro.configs.registry import ARCHS
    from repro.models import transformer
    from repro.optim import optimizers as opt
    from repro.runtime import steps

    cfg = ARCHS['smollm-360m'].reduced()
    optimizer = opt.get_optimizer('adamw')
    params, _ = transformer.init_params(cfg, jax.random.PRNGKey(0))
    st = steps.TrainState(params, optimizer.init(params),
                          jnp.zeros((), jnp.int32))
    B, S = 16, 32
    batch = {{
        'tokens': jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                                     cfg.vocab_size, jnp.int32),
        'labels': jax.random.randint(jax.random.PRNGKey(2), (B, S), 0,
                                     cfg.vocab_size, jnp.int32),
    }}
    # train 2 steps on an 8-device DP mesh, checkpoint
    from repro.launch.mesh import make_mesh
    mesh8 = make_mesh((8,), ('data',))
    fn8, init_ef = steps.make_manual_dp_train_step(cfg, optimizer, mesh8)
    ef = init_ef(params)
    for _ in range(2):
        st, ef, m = jax.jit(fn8)(st, ef, batch)
    store.save(r'{tmp_path}', int(st.step), st)

    # 'failure': restart on a 4-device mesh from the checkpoint
    mesh4 = make_mesh((4,), ('data',), devices=jax.devices()[:4])
    st2, _ = store.restore(r'{tmp_path}', st)
    assert int(st2.step) == 2
    fn4, init_ef4 = steps.make_manual_dp_train_step(cfg, optimizer, mesh4)
    st3, _, m = jax.jit(fn4)(st2, init_ef4(st2.params), batch)
    assert int(st3.step) == 3 and np.isfinite(float(m['loss']))
    print('elastic resume ok', float(m['loss']))
    """)


def test_moe_expert_parallel_lowering():
    """EP shards the expert dim when it divides the axis (8 experts on an
    8-wide model axis) — the plan must lower/compile with cross-device
    dispatch traffic.  (GSPMD may choose all-gather-based dispatch for the
    dense GShard formulation rather than all-to-all; both are accepted —
    the collective KIND is the partitioner's choice, the sharding is
    ours.)"""
    run8("""
    import jax, dataclasses
    from repro.configs.base import SHAPES
    from repro.configs.registry import ARCHS
    from repro.distributed.plan import plan_for
    from repro.distributed.sharding import use_sharding
    from repro.launch.specs import step_and_specs
    from repro.core import extract as cx

    from repro.launch.mesh import make_mesh
    mesh = make_mesh((1, 8), ('data', 'model'))
    cfg, shape = ARCHS['mixtral-8x7b'], SHAPES['prefill_32k']
    shape = dataclasses.replace(shape, global_batch=8)  # CPU-sized lowering
    plan = plan_for(cfg, shape, tp_size=8).with_(moe_mode='ep')
    with mesh, use_sharding(mesh, plan):
        fn, specs, sh, osh = step_and_specs(cfg, shape, mesh, plan)
        compiled = jax.jit(fn, in_shardings=sh,
                           out_shardings=osh).lower(*specs).compile()
    c = cx.extract_compiled(compiled)
    assert sum(c.collective_bytes.values()) > 0, c.collective_bytes
    assert c.flops > 0
    print('EP collectives:', c.collective_bytes)
    """)
