#!/usr/bin/env python3
"""The benchmark's one command:

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

runs one cell of `BENCHMARK.json` on the TPU chips of this machine and
prints, as the last line of standard output, one JSON object: `correct`,
`attempted`, `failed`, `metrics`, `device` (with `--trace 1` also
`breakdown`) and, last, `checks`: every number compared beside its limit.
Without the chips the cell asks for it exits non-zero and prints no result.
"""

from __future__ import annotations

import argparse
import importlib
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

#: traffic kind -> the driver that runs it
DRIVERS = {"train_job": "train_driver", "open_loop": "serve_driver",
           "backlog": "serve_driver"}


def run_cell(files: dict, seed: int, seconds: float, trace: bool,
             devices) -> dict:
    """One run of one cell on ``devices`` (the look for the chips is the
    caller's)."""
    from benchmark import check

    kind = files["traffic"]["kind"]
    if kind not in DRIVERS:
        raise SystemExit(f"traffic kind {kind!r} has no driver "
                         f"(have {sorted(DRIVERS)})")
    driver = importlib.import_module(f"benchmark.{DRIVERS[kind]}")
    limits = check.load_limits(files["cell"]["name"])
    return driver.run(files, seed, seconds, trace, devices, limits)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        raise SystemExit("--seed is a non-negative whole number")

    from benchmark import harness

    files = harness.load_cell(args.workload)
    devices = harness.require_chips(files["cell"]["chips"])
    from benchmark import program

    program.configure_compile_cache()
    result = run_cell(files, args.seed, args.seconds, bool(args.trace),
                      devices)
    harness.emit(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
