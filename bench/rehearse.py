"""Compile each cell's train step, and its reference's (and fp8
control's) gradient over one block, for a described TPU v5e, and print what
``memory_analysis()`` says they need against the chip's memory.  No chip is
needed.

    JAX_PLATFORMS=cpu PYTHONPATH=src python3 bench/rehearse.py \
        [--workload NAME ...]

The trainer is built as the benchmark builds it (so its parameters are made
once on the host); only shapes go to the compiler.
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]
#: usable bytes of one v5e chip as JAX reports ``bytes_limit`` there
V5E_BYTES_LIMIT = 16_909_336_576


def _need(ma) -> int:
    return (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes)


def _row(ma) -> dict:
    from bench import program_trace

    return dict(program_trace.memory_bytes(ma), needed=_need(ma),
                limit=V5E_BYTES_LIMIT)


def rehearse(workload: str, one_chip) -> dict:
    import jax
    import jax.numpy as jnp

    from bench import spec
    from bench.models import common
    from repro.launch import train as train_launch
    from repro.runtime.trainer import Trainer

    cell = spec.load_cell(workload, ROOT)
    config, mix = cell.config, cell.traffic
    rows, seq = mix["rows"], mix["seq_len"]
    args = train_launch.parse_args(["--arch", config["program_arch"],
                                    "--batch", str(rows), "--seq", str(seq)])
    cfg, dc, tc = train_launch.trainer_config(args)
    trainer = Trainer(cfg, dc, tc)
    put = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)
    state = jax.tree.map(put, trainer.state)
    batch = {k: jax.ShapeDtypeStruct((rows, seq), dt, sharding=one_chip)
             for k, dt in (("tokens", jnp.int32), ("labels", jnp.int32),
                           ("loss_mask", jnp.float32))}
    t0 = time.perf_counter()
    step = trainer.step_fn.lower(state, batch).compile()
    out = {"workload": workload, "compile_s": time.perf_counter() - t0,
           "train_step": _row(step.memory_analysis())}

    ref = cell.reference()
    per_block = max(1, config["reference_block_tokens"] // seq)
    pf = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, jnp.float32,
                                                     sharding=one_chip),
                      trainer.state.params)
    blk = {k: jax.ShapeDtypeStruct((per_block, seq), v.dtype,
                                   sharding=one_chip)
           for k, v in batch.items()}
    for name, ops in (("reference_block", common.Ops()),
                      ("control_block", common.Fp8Ops())):
        grad = jax.jit(jax.value_and_grad(
            lambda p, t, l, m: ref.block_loss(p, config, t, l, m, ops)))
        t0 = time.perf_counter()
        g = grad.lower(pf, blk["tokens"], blk["labels"], blk["loss_mask"]
                       ).compile()
        out[name] = dict(_row(g.memory_analysis()), rows=per_block,
                         compile_s=time.perf_counter() - t0)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", action="append")
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    names = args.workload or [w["name"] for w in json.loads(
        (ROOT / "BENCHMARK.json").read_text())["workloads"]]
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one_chip = SingleDeviceSharding(topo.devices[0])
    for name in names:
        print(json.dumps(rehearse(name, one_chip)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
