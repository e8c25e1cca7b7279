#!/usr/bin/env python3
"""Run one benchmark cell on the accelerator this machine holds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout. The cell, its configuration, its traffic
mix and its metrics are read from `BENCHMARK.json` and the files under
`bench/`. Set-up (weights from the seed, the engine, every shape the
window runs compiled) counts from process start to the first timed
request; then the window runs for `--seconds`; then the program's output
is compared with the plain reference. The last line of standard output is
one JSON object: correct, attempted, failed, metrics, device (and, with
`--trace 1`, breakdown), and last the numbers compared beside their
limits, which also close standard error.

Without a TPU, with fewer chips than the cell asks for, or outside a
checkout that holds the program, it names what it found on standard
error, prints no result and exits non-zero.
"""
import time

T_START = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def fail(msg: str) -> int:
    print(f"bench: {msg}", file=sys.stderr, flush=True)
    return 2


def main(argv=None) -> int:
    args = parse(argv)
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        return fail(f"no program (src/repro) in {ROOT}; run from a checkout "
                    f"of the repository")
    sys.path[:0] = [BENCH, src]
    from benchlib import harness
    try:
        spec = harness.load_spec(ROOT, args.workload)
    except (OSError, KeyError, ValueError, harness.CellError) as e:
        return fail(f"cannot resolve workload {args.workload!r}: {e}")

    import jax
    dev = harness.device_record()
    if dev["platform"] != "tpu":
        return fail(f"no TPU: JAX found {dev['count']} {dev['platform']} "
                    f"device(s) ({dev['kind']})")
    need = int(spec["cell"]["chips"])
    if dev["count"] < need:
        return fail(f"{args.workload} needs {need} chips, found "
                    f"{dev['count']}")
    cache = harness.enable_compile_cache(ROOT)
    print(f"bench: {json.dumps(dev)}; jax {jax.__version__}; compile cache "
          f"{cache}", file=sys.stderr, flush=True)

    def log(msg):
        print(msg, file=sys.stderr, flush=True)

    result = harness.run_cell(spec, args.seed, args.seconds,
                              bool(args.trace), T_START, log=log)
    for name, c in result["checks"].items():
        log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
