#!/usr/bin/env python3
"""Readings for the correctness limits: the program and the control.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 \\
        --seconds 50 [--out calib.jsonl]

Runs the cell once per seed in this one process (set-up, warm-up, a
window of ``--seconds``, the check), and for each run also puts the
configuration's control — the plain reference at the precision one step
below the configuration's — in the program's place on the same prompts.
Prints one JSON line per seed with the program's readings, the
control's, whether each passes the cell's limits, the run's metrics and
its device; with ``--out`` appends the lines to that file.  Exits 1 when
the control passes the limits on any seed (the limits then catch
nothing) or the program fails them, 2 without a chip.  Needs the chip,
like ``run.py``.
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
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out")
    args = ap.parse_args(argv)

    from bench.harness import check, spec
    from bench.harness.cell import execute
    from bench.harness.serve import NoChip

    bench = spec.Bench()
    t0 = T_START
    rc = 0
    for seed in (int(s) for s in args.seeds.split(",")):
        try:
            out = execute(bench, args.workload, seed, args.seconds, False,
                          t_start=t0, log=log, control=True)
        except NoChip as e:
            log(f"no chip: {e}")
            return 2
        limits = {k: v["limit"] for k, v in out["checks"].items()}
        control_passes = check.judge(out["control"], limits)
        log(f"seed {seed}: program correct {out['correct']}, control "
            f"passes the limits {control_passes}")
        if control_passes or not out["correct"]:
            rc = 1
        line = {"workload": args.workload, "seed": seed,
                "seconds": args.seconds, "correct": out["correct"],
                "program": {k: v["value"] for k, v in out["checks"].items()},
                "control": out["control"],
                "control_passes": control_passes, "limits": limits,
                "metrics": out["metrics"],
                "attempted": out["attempted"], "failed": out["failed"],
                "device": out["device"]}
        print(json.dumps(line), flush=True)
        if args.out:
            os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                        exist_ok=True)
            with open(args.out, "a") as f:
                f.write(json.dumps(line) + "\n")
        t0 = time.perf_counter()
    return rc


if __name__ == "__main__":
    sys.exit(main())
