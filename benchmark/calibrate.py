#!/usr/bin/env python3
"""Readings the limits and the fixed rate are set from, many seeds in one
process (a run of the benchmark itself is one seed, one process):

    python3 benchmark/calibrate.py --workload <cell> --seeds 1,2,... \
        [--control-seeds 1,2,3] [--seconds S] [--rates r1,r2,...]

For a training cell, per seed: the program's numbers against the reference
(the lower reading) and, for the control seeds, the reference in the lower
precision and with each planted fault, each put in the program's place (the
upper readings). For a serving cell: one engine, the seed's weights swapped
in; `--rates` sweeps offered rates for the knee instead. Results go to
`chiprun_out/calibrate_<cell>.json` and to standard output.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def _ints(text: str) -> list:
    return [int(x) for x in text.split(",") if x]


def train(files, seeds, control_seeds, seconds, devices, say) -> list:
    from benchmark import check, program, train_driver

    cfg, job = files["config"], files["traffic"]
    out = []
    for seed in seeds:
        t = time.perf_counter()
        win = train_driver.Window(cfg, job, seed, seconds, None, 0)
        result = program.run_training(
            cfg, program.train_overrides(cfg, job, seed, len(devices)), win)
        del result
        ref_n = train_driver.reference_numbers(cfg, job, seed, win.batches,
                                               devices)
        row = {"seed": seed, "program": _values(
            check.train_numbers(win.read, ref_n)),
            "tokens_per_s": win.steps_in_window * job["sequences_per_chip"]
            * len(devices) * job["seq_len"] / (win.t_close - win.t_open)}
        if seed in control_seeds:
            for quant in ("int8", "fp8"):
                ctl = train_driver.reference_numbers(
                    cfg, job, seed, win.batches, devices, quant=quant)
                row[f"control_{quant}"] = _values(
                    check.train_numbers(ctl, ref_n))
            faults = ["half_batch"] + (
                ["no_exchange"] if len(devices) > 1 else [])
            for fault in faults:
                got = train_driver.reference_numbers(
                    cfg, job, seed, win.batches, devices, step_fault=fault)
                row[f"fault_{fault}"] = _values(
                    check.train_numbers(got, ref_n))
        row["seconds"] = time.perf_counter() - t
        say(row)
        out.append(row)
    return out


def _values(numbers: dict) -> dict:
    return {k: v["value"] for k, v in numbers.items()} | {
        k + "_leaf": v["leaf"] for k, v in numbers.items() if "leaf" in v}


def serve(files, seeds, control_seeds, seconds, rates, say) -> list:
    from benchmark import harness, program, serve_driver

    cfg, mix = files["config"], dict(files["traffic"])
    eng = serve_driver.build(cfg, mix, seeds[0])
    say({"setup_s": time.perf_counter() - harness.T_PROCESS_START})
    out = []
    if rates:
        # the first seed's weights throughout; each seed orders the traffic
        for rate in rates:
            mix["rate_per_s"] = rate
            for seed in seeds:
                got = serve_driver.drive(eng, mix, seed, seconds)
                row = {"rate_per_s": rate, "seed": seed, **got["values"],
                       "ttft_p50_ms": got["ttft_p50_ms"],
                       "requests": got["requests"], "failed": got["failed"],
                       "backlog_at_close": got["backlog_at_close"],
                       "compiled_in_window": got["compiled_in_window"]}
                say(row)
                out.append(row)
        return out
    for seed in seeds:
        t = time.perf_counter()
        eng.params = None
        eng.params = program.program_weights(cfg, seed)
        got = serve_driver.drive(eng, mix, seed, seconds)
        numbers, notes = serve_driver.served_numbers(
            cfg, mix, seed, got["reqs"], got["done"])
        row = {"seed": seed, "program": _values(numbers), **notes,
               **got["values"], "failed": got["failed"],
               "backlog_at_close": got["backlog_at_close"]}
        if seed in control_seeds:
            for quant in ("int8", "fp8"):
                ctl, ctl_notes = serve_driver.served_numbers(
                    cfg, mix, seed, got["reqs"], got["done"], quant=quant)
                row[f"control_{quant}"] = _values(ctl)
                row[f"control_{quant}_notes"] = ctl_notes
        row["seconds"] = time.perf_counter() - t
        say(row)
        out.append(row)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=_ints, required=True)
    ap.add_argument("--control-seeds", type=_ints, default=[])
    ap.add_argument("--seconds", type=float, default=1.0)
    ap.add_argument("--rates", type=lambda s: [float(x) for x in s.split(",")],
                    default=[])
    args = ap.parse_args(argv)

    from benchmark import harness

    files = harness.load_cell(args.workload)
    devices = harness.require_chips(files["cell"]["chips"])
    from benchmark import program

    program.configure_compile_cache()

    def say(row):
        print(json.dumps(row), flush=True)

    if files["traffic"]["kind"] == "train_job":
        rows = train(files, args.seeds, args.control_seeds, args.seconds,
                     devices, say)
    else:
        rows = serve(files, args.seeds, args.control_seeds, args.seconds,
                     args.rates, say)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    tag = "sweep" if args.rates else "calibrate"
    with open(os.path.join(ROOT, "chiprun_out",
                           f"{tag}_{args.workload}.json"), "w") as f:
        json.dump({"device": harness.device_record(devices), "rows": rows},
                  f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
