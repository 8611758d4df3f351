"""Readings that the limits of ``correct`` are set from, for one cell.

    python3 bench/readings.py --workload NAME --seeds 12 --control 3 \
        --out readings_NAME.json

Runs on the chip at the cell's own size, in one process.  The trainer is
built once; for each seed it gets that seed's weights, documents and a
fresh optimizer state and runs its checked steps as a benchmark run does.
Then, with the trainer freed, the float32 reference follows each seed, and
on the first ``--control`` seeds so do:

  control     the reference with every matrix product in fp8 (the nearest
              precision below the configuration's bfloat16)
  half_batch  the reference with the second half of each batch's tokens
              left out of the loss, the mean taken over the rest

Each is compared with the reference as a run compares the program.  A
state left unchanged and a token altered in the loader need no run: their
``update`` reading is 1 (every moving leaf's change is lost) and their
``batch`` reading at least 2 (the token, and the same id as a label).
The file keeps each side's losses and per-leaf norms too (``raw``).
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]


def half_batch(batches):
    """Each batch with the later half of its tokens (row-major) unmasked."""
    out = []
    for b in batches:
        mask = b["loss_mask"].copy()
        flat = mask.reshape(-1)
        flat[flat.size // 2:] = 0.0
        out.append({**b, "loss_mask": mask})
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=1000)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    t_start = time.perf_counter()
    from bench import check, harness, spec
    from bench.models import common

    cell = spec.load_cell(args.workload, ROOT)
    dev = harness._devices(cell.chips, allow_cpu=False)[0]
    ses = harness.Session(cell, 0.0, spec.peaks(dev.device_kind, ROOT))
    seeds = [args.first_seed + i for i in range(args.seeds)]
    progs, docs = {}, {}
    for s in seeds:
        docs[s] = ses.start(s)
        progs[s] = ses.checked()
        harness.log(t_start, f"seed {s}: program losses {progs[s]['losses']}")
    ses.trainer = None

    out = {"workload": args.workload, "device": dev.device_kind,
           "seeds": seeds, "program": {}, "control": {}, "half_batch": {},
           "seconds": {}, "raw": {}}

    def keep(name, seed, run):
        out["raw"].setdefault(name, {})[seed] = {
            k: run[k] for k in ("losses", "first_grad", "change")}

    for i, s in enumerate(seeds):
        batches = ses.batches(docs[s])
        prog = progs[s]
        prog["batch"] = check.batch_mismatch(prog.pop("fed"), batches)
        t0 = time.perf_counter()
        ref = ses.reference(s, batches)
        out["seconds"].setdefault("reference", []).append(
            time.perf_counter() - t0)
        out["program"][s] = check.readings(prog, ref)
        keep("program", s, prog)
        keep("reference", s, ref)
        harness.log(t_start, f"seed {s}: program {out['program'][s]}")
        if i < args.control:
            for name, kw in (("control", {"ops": common.Fp8Ops()}),
                             ("half_batch", {})):
                t0 = time.perf_counter()
                other = ses.reference(
                    s, half_batch(batches) if name == "half_batch"
                    else batches, **kw)
                out["seconds"].setdefault(name, []).append(
                    time.perf_counter() - t0)
                out[name][s] = check.readings(other, ref)
                keep(name, s, other)
                harness.log(t_start, f"seed {s}: {name} {out[name][s]}")
        if i == 0:
            unchanged = dict(prog, change=[0.0] * len(prog["change"]))
            out["unchanged_state"] = check.readings(unchanged, ref)
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1)
    for name in ("program", "control", "half_batch"):
        for k in out["control"][seeds[0]]:
            vals = [r[k] for r in out[name].values()]
            print(f"{name:10s} {k:10s} min {min(vals):.3e} "
                  f"max {max(vals):.3e}")
    print(f"unchanged  {out['unchanged_state']}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
