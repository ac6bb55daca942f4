"""Readings that the limits of ``correct`` are set from, on the chip.

    python3 bench/control.py --workload table1.sim-msweep \
        --seeds 11 12 13 --requests 1 --control f32-reference
    python3 bench/control.py --workload class1m.sim-m1000 \
        --seeds 11 12 13 --requests 1 --control f32-reference
    python3 bench/control.py --workload table1.analyze-timeopt \
        --seeds 11 12 13 --control pallas

For each seed: the cell's set-up, ``--requests`` requests at the cell's
own size, then the numbers the benchmark compares.  ``sound`` is the
program against the reference (the lower reading); ``control`` is the
same comparison with the control in the program's place (the upper
reading):

* ``f32-reference``: the plain event reference of the fleet's kind
  (client by client, or class by class) with a float32 clock in place of
  the program (simulate cells);
* ``pallas``: the program itself with its float32 Buzen kernel switched
  on (``REPRO_BUZEN_BACKEND=pallas``; analyze cells).

One JSON line per seed.  The benchmark's own runs never run this.
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--requests", type=int, default=1)
    ap.add_argument("--control", choices=("none", "f32-reference", "pallas"),
                    default="none")
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")

    from bench import harness

    cell = harness.load_cell(args.workload, ROOT)
    try:
        devices = harness._devices(cell.chips, require_chip=True)
    except harness.NoChip as e:
        print(f"control: {e}", file=sys.stderr)
        return 1
    from repro.serve.xla_cache import enable_persistent_cache

    enable_persistent_cache()
    if args.control == "pallas":
        from repro.core import buzen

        buzen.set_backend("pallas")
    mode_cls = harness.load_mode(cell).Mode
    for seed in args.seeds:
        mode = mode_cls(cell, seed)
        mode.setup()
        requests = []
        for i in range(args.requests):
            t0 = time.perf_counter()
            work, output = mode.request(i)
            requests.append(harness.Request(i, t0, time.perf_counter(), work,
                                            output))
        run = harness.Run(cell=cell, mode=cell.traffic["mode"], setup_s=0.0,
                          window_start=requests[0].start,
                          window_end=requests[-1].end, requests=requests)
        line = {"workload": cell.name, "seed": seed,
                "device": devices[0].device_kind,
                "request_s": [r.end - r.start for r in requests]}
        if args.control == "pallas":
            line["control"] = mode.readings(run)
        else:
            line["sound"] = mode.readings(run)
            if args.control == "f32-reference":
                line["control"] = mode.readings(run, control=True)
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
