#!/usr/bin/env python3
"""Run one benchmark cell once on the chip this process finds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout.  The cell, its configuration, its traffic mix
and its metrics are found by name from ``BENCHMARK.json`` (see
``bench/harness/spec.py``).  The last line of standard output is one JSON
object: ``correct``, ``attempted``, ``failed``, ``metrics`` (end-to-end
with ``--trace 0``, per-layer with ``--trace 1``), ``device``, with
``--trace 1`` a ``breakdown``, and last the ``checks``: each number the
correctness comparison read, beside its limit.  The same numbers close
standard error.

A run that finds no TPU, or fewer chips than the cell asks for, exits 2
and prints no result.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "src"))


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench.harness import spec
    from bench.harness.cell import execute
    from bench.harness.serve import NoChip

    try:
        bench = spec.Bench()
        out = execute(bench, args.workload, args.seed, args.seconds,
                      bool(args.trace), t_start=T_START, log=log)
    except NoChip as e:
        log(f"no chip: {e}")
        return 2
    except spec.SpecError as e:
        log(f"benchmark definition: {e}")
        return 2
    log(f"correct: {out['correct']}")
    for k, c in out["checks"].items():
        log(f"check {k} = {c['value']!r} (limit {c['limit']!r})")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
