"""Training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch smollm-360m \
        --steps 200 --batch 8 --seq 256 --reduced --ckpt /tmp/ck

On the CPU container this drives reduced configs end-to-end (the ~100M-scale
example); on a TPU slice the same entry point runs the full configs on the
production mesh (``--mesh single|multi``) with the plan from
``plan_for`` / ``autoshard``.
"""
from __future__ import annotations

import argparse
import dataclasses
from typing import Optional, Sequence, Tuple

import jax

from repro import compile_cache
from repro.configs.base import SHAPES, ArchConfig
from repro.configs.registry import ARCHS, get_arch
from repro.data.pipeline import DataConfig
from repro.obs import metrics as _obs_metrics
from repro.obs import trace as _obs_trace
from repro.runtime.trainer import Trainer, TrainerConfig
from repro.core import predictor
from repro.distributed.plan import plan_for


def parse_args(argv: Optional[Sequence[str]] = None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", required=True, choices=sorted(ARCHS))
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--reduced", action="store_true",
                    help="train the reduced same-family config (CPU scale)")
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--online-calibrate", action="store_true",
                    help="stream per-step timings into the online "
                         "calibrator (RLS refit + drift watch)")
    ap.add_argument("--calib-device", default=None,
                    help="registry device name for online refits "
                         "(default: '<arch>-online')")
    ap.add_argument("--calib-auto-register", action="store_true",
                    help="write drift-refit models into the registry "
                         "(bumps the model file revision)")
    ap.add_argument("--trace-json", default=None, metavar="PATH",
                    help="write a Chrome-trace/Perfetto JSON of the run "
                         "(measured step spans + predicted overlay)")
    ap.add_argument("--metrics-json", default=None, metavar="PATH",
                    help="dump the metrics registry as JSON on exit")
    # --- supervised recovery / chaos (runtime/supervisor.py, faults.py) ---
    ap.add_argument("--supervise", action="store_true",
                    help="run under the Supervisor: watchdog deadlines, "
                         "backoff, elastic replan + checkpoint-resume on "
                         "device loss")
    ap.add_argument("--fault-plan", default=None, metavar="SPEC|PATH",
                    help="deterministic fault schedule — "
                         "'kind@step[:k=v,..];..' (e.g. "
                         "'corrupt_registry@7;device_loss@12') or a JSON "
                         "plan path; implies --supervise")
    ap.add_argument("--chaos-seed", type=int, default=0,
                    help="seed for fault payloads and backoff jitter")
    ap.add_argument("--devices", type=int, default=1,
                    help="fleet size the supervisor replans over")
    ap.add_argument("--model", default=None, metavar="DEVICE",
                    help="cost-model device name pricing the replan "
                         "candidates (hardened registry lookup)")
    ap.add_argument("--registry", default=None, metavar="DIR",
                    help="model-registry directory override")
    ap.add_argument("--watchdog-k", type=float, default=6.0,
                    help="watchdog deadline = k x max(predicted, median)")
    ap.add_argument("--max-recoveries", type=int, default=8)
    return ap.parse_args(argv)


def trainer_config(args: argparse.Namespace
                   ) -> Tuple[ArchConfig, DataConfig, TrainerConfig]:
    """The (arch, data, trainer) configs a ``Trainer`` is built from."""
    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    dc = DataConfig(vocab_size=cfg.vocab_size, seq_len=args.seq,
                    global_batch=args.batch, seed=args.seed,
                    n_codebooks=cfg.n_input_codebooks)
    tc = TrainerConfig(ckpt_dir=args.ckpt, ckpt_every=args.ckpt_every,
                       lr=args.lr, total_steps=args.steps, seed=args.seed,
                       online_calibrate=args.online_calibrate,
                       calib_device=args.calib_device,
                       calib_auto_register=args.calib_auto_register)
    return cfg, dc, tc


def main(argv: Optional[Sequence[str]] = None) -> None:
    args = parse_args(argv)
    compile_cache.enable_jax_cache()
    if args.trace_json:
        _obs_trace.enable(process_name="train")

    cfg, dc, tc = trainer_config(args)

    # cost-model prediction for the straggler monitor threshold
    shape = dataclasses.replace(SHAPES["train_4k"], seq_len=args.seq,
                                global_batch=args.batch)
    plan = plan_for(ARCHS[args.arch], SHAPES["train_4k"])
    pred = predictor.predict_step(ARCHS[args.arch], shape, plan,
                                  {"data": 1, "model": 1})
    print(f"[train] {cfg.name}: {cfg.n_params()/1e6:.1f}M params, "
          f"predicted full-arch step {pred.seconds*1e3:.1f}ms on 1 chip")

    if args.supervise or args.fault_plan:
        from repro.core.workload import WorkloadSpec
        from repro.runtime.faults import FaultInjector, FaultPlan
        from repro.runtime.supervisor import BackoffPolicy, Supervisor

        fplan = FaultPlan.parse(args.fault_plan, seed=args.chaos_seed) \
            if args.fault_plan else FaultPlan(seed=args.chaos_seed)
        injector = FaultInjector(fplan, ckpt_dir=args.ckpt,
                                 registry_dir=args.registry,
                                 registry_device=args.model)
        if fplan:
            print(f"[train] fault plan armed: {fplan.describe()}")
        workload = WorkloadSpec(phase="train", global_batch=args.batch,
                                seq_len=args.seq, name="train_live")
        sup = Supervisor(
            lambda mesh: Trainer(cfg, dc, tc, injector=injector),
            args.steps, cfg=ARCHS[args.arch], workload=workload,
            n_devices=args.devices, model=args.model,
            registry_dir=args.registry, injector=injector,
            watchdog_k=args.watchdog_k,
            backoff=BackoffPolicy(seed=args.chaos_seed),
            max_recoveries=args.max_recoveries)
        hist = sup.run()
        sup.report()
        trainer = sup.trainer
    else:
        trainer = Trainer(cfg, dc, tc)
        hist = trainer.train(args.steps)
    print(f"[train] done: loss {hist[0]['loss']:.4f} -> {hist[-1]['loss']:.4f}")
    if trainer.calibrator is not None:
        print("[calib] refit report:")
        print(trainer.calibrator.final_report())

    tracer = _obs_trace.get_tracer()
    if args.trace_json:
        for line in tracer.report_lines():
            print(f"[trace] {line}")
        tracer.save(args.trace_json)
        print(f"[train] trace written to {args.trace_json}")
    if args.metrics_json:
        _obs_metrics.REGISTRY.save_json(args.metrics_json)
        print(f"[train] metrics written to {args.metrics_json}")


if __name__ == "__main__":
    main()
