"""Chip benchmark of the live HeRo path: one run of one cell.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Run from the root of a checkout, on a machine that holds the chips the
cell asks for.  It needs a TPU: without one, or with fewer chips than the
cell asks for, it exits with code 2 before it builds anything.  It prints
the device on standard error, then set-up, the measured window (with
``--trace 1`` a profiler trace of a few seconds inside it), the metric
readers, and the comparison with the plain reference.  The numbers
compared, each beside its limit, are the last lines on standard error;
the last line on standard output is the result as one JSON object.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parents[1]


def main(argv=None) -> int:
    t_start = time.monotonic()
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(BENCH), str(ROOT / "src")]
    import harness

    cell = harness.load_cell(args.workload)
    import jax

    devices = jax.devices()
    dev = devices[0]
    print(f"device: {dev.platform} {dev.device_kind!r} x{len(devices)}",
          file=sys.stderr, flush=True)
    if dev.platform != "tpu" or len(devices) < cell.chips:
        print(f"run.py: {args.workload} needs {cell.chips} TPU chip(s); "
              f"JAX found {len(devices)} {dev.platform} device(s)",
              file=sys.stderr)
        return 2
    out = harness.run(cell, args.seed, args.seconds, bool(args.trace),
                      t_start=t_start, device=dev)
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
