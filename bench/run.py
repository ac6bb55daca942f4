"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload table1.sim-msweep --seed 7 \
        --seconds 10 --trace 0

Run from the root of a checkout: the cell, its configuration, traffic mix
and metric readers are found by name from ``BENCHMARK.json`` (see
``bench/harness.py``).  Set-up (imports, the chip, the fleet, a warm
request of the cell's own shapes) is timed from the start of this
process; then requests run back to back for ``--seconds``, and the
comparison with the plain reference decides ``correct``.  ``--trace 1``
profiles a window of the traffic's first ``trace_requests`` requests (at
most ``--seconds``) and reports the per-layer metrics instead of the
end-to-end ones.  The last line of standard output is one JSON object;
the last lines of standard error are the numbers compared, each with its
limit.  Without an accelerator, or with fewer chips than the cell asks
for, it prints no result and exits non-zero.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    from bench import harness

    try:
        cell = harness.load_cell(args.workload, ROOT)
    except (OSError, KeyError, ValueError) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    code, _ = harness.run(cell, seed=args.seed, seconds=args.seconds,
                          trace=bool(args.trace), t_start=T_START)
    return code


if __name__ == "__main__":
    sys.exit(main())
