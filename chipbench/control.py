#!/usr/bin/env python3
"""Readings of a cell's checks on sound runs and on its control, one process.

    python chipbench/control.py --workload oneshot.kron11 --seconds 51 \
        --seeds 11,12,13 --control-seeds 21,22,23

Runs the cell as ``run.py`` does, once per ``--seeds`` entry, then once
per ``--control-seeds`` entry with the cell's control (``faults.py``) in
the program's place, and prints each run's result line.  The limits of
``correct`` are set from these readings (``PERF.md``); the benchmark's
own runs never run the control.
"""

import argparse
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent.parent
for p in (ROOT / "src", ROOT):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from chipbench import faults, run  # noqa: E402


def main(argv=None) -> int:
    """Sound runs, then control runs; returns the worst exit code."""
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    generator = run.cell_spec(args.workload)["traffic"]["generator"]
    rc = 0
    for seeds, control in ((args.seeds, None),
                           (args.control_seeds, faults.CONTROLS[generator])):
        for seed in filter(None, seeds.split(",")):
            argv = ["--workload", args.workload, "--seed", seed,
                    "--seconds", args.seconds] + (
                        ["--rehearse"] if args.rehearse else [])
            print(f"=== {'control' if control else 'sound'} seed {seed}",
                  flush=True)
            if control is None:
                rc = max(rc, run.main(argv, t_start=time.perf_counter()))
            else:
                with control():
                    rc = max(rc, run.main(argv, t_start=time.perf_counter()))
    return rc


if __name__ == "__main__":
    sys.exit(main())
