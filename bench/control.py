#!/usr/bin/env python3
"""Read the comparison's two ends for one cell, on the chip, in one
process: for each seed, a whole run with the fp8 control put in the
program's place (its gap and whether `correct` comes out false), and the
program's own widest logit gap on the same sample of requests.

    python3 bench/control.py --workload <cell> --seeds 11,12,13 --seconds 10

A limit is set between the largest program reading over a dozen seeds or
more and the smallest control reading (PERF.md gives both). Not part of
the benchmark's runs.
"""
import time

T_START = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]
    from benchlib import harness
    spec = harness.load_spec(ROOT, args.workload)
    dev = harness.device_record()
    if dev["platform"] != "tpu":
        print(f"control: no TPU ({dev})", file=sys.stderr)
        return 2
    harness.enable_compile_cache(ROOT)
    rows = []
    t = T_START
    for seed in (int(s) for s in args.seeds.split(",")):
        res = harness.run_cell(spec, seed, args.seconds, False, t,
                               log=lambda m: print(m, file=sys.stderr,
                                                   flush=True),
                               control=True)
        row = {"seed": seed, "control_correct": res["correct"],
               "program": res["program_checks"]["logit_gap"]["value"],
               "control": res["checks"]["logit_gap"]["value"],
               "limit": res["checks"]["logit_gap"]["limit"],
               "program_correct": harness.verdict(res["program_checks"]),
               "metrics": {k: v["value"] for k, v in res["metrics"].items()}}
        print(json.dumps(row), flush=True)
        rows.append(row)
        t = time.time()
    print(json.dumps({
        "workload": args.workload,
        "program_max": max(r["program"] for r in rows),
        "control_min": min(r["control"] for r in rows)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
