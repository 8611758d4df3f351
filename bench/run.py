"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload smollm-360m.train.seq2k --seed 7 \
        --seconds 30 --trace 0

Run from the root of a checkout.  The last line of standard output is one
JSON object (``correct``, ``attempted``, ``failed``, ``metrics``,
``device``, with ``--trace 1`` also ``breakdown``, and last ``checks``: each
number ``correct`` compared beside its limit, which also end standard
error).  Without a TPU, or with fewer chips than the cell asks for, it
prints no result and exits 3.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    # libtpu would otherwise log to a fixed directory outside the checkout
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness, spec

    try:
        result = harness.run(args.workload, args.seed, args.seconds,
                             bool(args.trace), T_START, root=ROOT)
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    except spec.SpecError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 4
    harness.print_result(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
