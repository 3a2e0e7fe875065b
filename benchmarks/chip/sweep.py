"""Sweep of the open-loop rate on the chip, to find the knee: the highest
rate at which completions keep up with arrivals over the window.

    python3 benchmarks/chip/sweep.py --workload <open-loop cell> \
        --seconds <s> --seed <n> --rates 1 2 3 ...

One process, one run of the cell per rate (the mix's rate replaced), each
printed as a JSON line: queries due, answered, the latency median and
90th percentile, the mean latency of the first and the last third of the
arrivals (a backlog that grows through the window shows as a rising
last third), and the seconds the answers ran past the window's end.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(BENCH), str(BENCH.parents[1] / "src")]
    import jax

    import harness

    cell = harness.load_cell(args.workload)
    if cell.mix["loop"] != "open":
        print("sweep.py: the cell's loop is not open", file=sys.stderr)
        return 2
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print("sweep.py: needs a TPU", file=sys.stderr)
        return 2
    for rate in args.rates:
        c = dataclasses.replace(cell, mix=dict(cell.mix, rate_qps=rate))
        keep: dict = {}
        out = harness.run(c, args.seed, args.seconds, False,
                          t_start=time.monotonic(), device=dev, keep=keep)
        qs = keep["queries"]
        lat = [r.last - r.due for r in qs if r.answered]
        third = max(1, len(qs) // 3)
        first = [r.last - r.due for r in qs[:third] if r.answered]
        last = [r.last - r.due for r in qs[-third:] if r.answered]
        t0 = keep["window"][0]
        print(json.dumps({
            "rate_qps": rate, "due": len(qs), "answered": len(lat),
            "correct": out["correct"],
            "p50_s": float(np.median(lat)) if lat else None,
            "p90_s": float(np.percentile(lat, 90)) if lat else None,
            "first_third_mean_s": float(np.mean(first)) if first else None,
            "last_third_mean_s": float(np.mean(last)) if last else None,
            "past_window_s": max((r.last for r in qs if r.answered),
                                 default=t0) - (t0 + args.seconds)}),
            flush=True)
        del keep
    return 0


if __name__ == "__main__":
    sys.exit(main())
