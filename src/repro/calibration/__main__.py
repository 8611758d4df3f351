"""CLI for the calibration subsystem.

    # fit a model for this machine and register it under "cpu"
    PYTHONPATH=src python -m repro.calibration --device cpu \
        --out experiments/registry

    # quick partial recalibration (two kernel classes, fewer runs)
    PYTHONPATH=src python -m repro.calibration --device cpu --scale tiny \
        --runs 8 --classes stride1_global,arith

    # inspect the registry
    PYTHONPATH=src python -m repro.calibration --list
    PYTHONPATH=src python -m repro.calibration --show tpu-v5e
"""
from __future__ import annotations

import argparse
import sys

from repro import compile_cache
from repro.calibration import registry
from repro.calibration.calibrate import calibrate, measured_device
from repro.core.model import ModelSchemaError


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.calibration",
        description="Fit, register and inspect per-device cost models.")
    ap.add_argument("--device", default="cpu",
                    help="registry name for the fitted model (default: cpu)")
    ap.add_argument("--out", metavar="DIR", default=None,
                    help="registry directory (default: $REPRO_MODEL_REGISTRY "
                         f"or {registry.DEFAULT_REGISTRY_DIR})")
    ap.add_argument("--scale", default="cpu", choices=("cpu", "tiny"),
                    help="measurement-kernel size ladder (tiny = smoke)")
    ap.add_argument("--runs", type=int, default=30,
                    help="timing runs per kernel (paper: 30)")
    ap.add_argument("--drop", type=int, default=4,
                    help="warmup runs discarded (paper: 4)")
    ap.add_argument("--ridge", type=float, default=1e-4,
                    help="unit-free ridge strength (0 disables)")
    ap.add_argument("--nonneg", action="store_true",
                    help="project weights to >= 0 (paper default: off)")
    ap.add_argument("--classes", default=None,
                    help="comma-separated kernel classes to measure "
                         "(default: full 9-class suite)")
    ap.add_argument("--dry-run", action="store_true",
                    help="fit and report but do not write the registry")
    ap.add_argument("--list", action="store_true", dest="list_",
                    help="list registered devices and exit")
    ap.add_argument("--show", metavar="DEVICE", default=None,
                    help="print a registered model's weight report and exit")
    args = ap.parse_args(argv)

    if args.list_:
        models = registry.list_models(args.out)
        width = max((len(n) for n in models), default=6)
        print(f"registry: {args.out or registry.default_registry_dir()}")
        for name, kind in sorted(models.items()):
            print(f"  {name:<{width}}  {kind}")
        return 0

    if args.show:
        try:
            model = registry.load_model(args.show, args.out)
        except (registry.UnknownDeviceError, ModelSchemaError) as e:
            # unknown device OR a registry file with a mismatched/unreadable
            # SCHEMA_VERSION: report clearly, don't traceback
            print(f"cannot load model {args.show!r}: {e}", file=sys.stderr)
            return 1
        print(model.interpretation_report())
        return 0

    compile_cache.enable_jax_cache()
    dev = measured_device()
    print(f"# measuring on platform={dev['platform']} "
          f"device_kind={dev['device_kind']} "
          f"device_count={dev['device_count']} as {args.device!r}")
    classes = ([c.strip() for c in args.classes.split(",") if c.strip()]
               if args.classes else None)
    result = calibrate(
        args.device, scale=args.scale, runs=args.runs, drop=args.drop,
        ridge=args.ridge, nonneg=args.nonneg, classes=classes,
        registry_dir=args.out, write_registry=not args.dry_run)
    return 0 if result.model is not None else 1


if __name__ == "__main__":
    sys.exit(main())
