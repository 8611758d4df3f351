"""Set a cell's limits from its readings (``bench/readings.py``).

    python3 bench/set_limits.py readings_NAME.json \
        [--program-from OLDER_READINGS.json ...] [--runs RESULTS.jsonl ...] \
        [--copy DIR]

For each number ``correct`` compares, the lower reading is the largest the
program gave over a dozen seeds or more: those of the readings, the
program's readings in older readings files, and the benchmark's own runs
of the cell (lines of ``{"workload": ..., "seed": ..., "line": <result
line>}``).  The upper reading is the smallest of: the fp8 control's
smallest reading where that is at least 3 × the lower; the half batch's
where at least 10 ×; a state left unchanged where at least 3 ×.  The limit
lies between them, a third of the way up from the lower on a log scale
(more room above the lower, which fresh seeds can exceed, than below the
upper), to two significant digits.  A number with no upper reading is not
compared: it could only fail sound runs.  ``batch`` is exact: its limit is
0.  The control, and each planted fault, has to fail a compared number on
every seed it was read on; if one does not, no limits are written.
"""
from __future__ import annotations

import argparse
import json
import math
import pathlib
import shutil
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
#: how far above the lower reading a planted fault must read to bound it
MARGINS = {"control": 3.0, "half_batch": 10.0, "unchanged_state": 3.0}
MIN_SEEDS = 12


def add_program(readings: dict, older=(), runs=()) -> None:
    """Count older readings' program seeds, and each sound run's numbers,
    among the program's readings."""
    prog = readings["program"]
    for path in older:
        for seed, r in json.loads(pathlib.Path(path).read_text()
                                  )["program"].items():
            prog[seed] = {**r, **prog.get(seed, {})}
    for path in runs:
        for text in pathlib.Path(path).read_text().splitlines():
            run = json.loads(text)
            line = run.get("line")
            if run["workload"] == readings["workload"] and line \
                    and line["failed"] == 0:
                prog[f"run {run['seed']}"] = {
                    k: c["value"] for k, c in line["checks"].items()}


def limits_from(readings: dict) -> dict:
    out = {"about": __doc__.split("\n\n")[2].replace("\n", " "),
           "limits": {}, "not_compared": {}, "readings": {}}
    faults = {n: list(readings[n].values())
              for n in ("control", "half_batch")}
    faults["unchanged_state"] = [readings["unchanged_state"]]
    for k in faults["control"][0]:
        progs = [r[k] for r in readings["program"].values() if k in r]
        if k == "batch" or len(progs) < MIN_SEEDS:
            continue
        lower = max(progs)
        found = {n: min(r[k] for r in runs) for n, runs in faults.items()}
        bounding = {n: v for n, v in found.items()
                    if v >= MARGINS[n] * lower}
        row = {"lower": lower, "seeds": len(progs),
               **{f"{n}_min": v for n, v in found.items()}}
        out["readings"][k] = row
        if not bounding:
            out["not_compared"][k] = row
            continue
        row["upper_from"] = min(bounding, key=bounding.get)
        row["upper"] = bounding[row["upper_from"]]
        limit = math.exp((math.log(lower) + 2 * math.log(row["upper"])) / 3)
        out["limits"][k] = float(f"{limit:.2g}")
    for name, runs in faults.items():
        for r in runs:
            if not any(r[k] > v for k, v in out["limits"].items()):
                raise ValueError(f"{name} fails no compared number: {r}")
    out["limits"]["batch"] = 0
    out["readings"]["batch"] = {
        "lower": 0, "upper": 2,
        "upper_from": "a token altered in the loader: the token, and the "
                      "same id as the previous position's label"}
    out["readings"]["device"] = readings["device"]
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("readings")
    ap.add_argument("--program-from", nargs="*", default=[])
    ap.add_argument("--runs", nargs="*", default=[])
    ap.add_argument("--copy", help="also write the file into this directory")
    args = ap.parse_args(argv)
    readings = json.loads(pathlib.Path(args.readings).read_text())
    add_program(readings, args.program_from, args.runs)
    out = limits_from(readings)
    path = ROOT / "bench" / "limits" / f"{readings['workload']}.json"
    path.write_text(json.dumps(out, indent=2) + "\n")
    print(json.dumps(out["limits"]))
    if args.copy:
        shutil.copy(path, args.copy)
    return 0


if __name__ == "__main__":
    sys.exit(main())
