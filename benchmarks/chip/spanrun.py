"""Runs of a cell with the program's span recorder on, on the chip.

    python3 benchmarks/chip/spanrun.py --workload <cell> --seconds <s> \
        --trace <0|1> --seeds <n> [<n> ...] [--spans on|off|both] \
        [--out <file.jsonl>]

For each seed, in this one process, one run of the cell as ``run.py``
makes it (``harness.run``), with the program's span recorder
(``repro.serving.spans``) on, as ``run.py --trace 1`` runs it
(``--spans on``); or off, as ``run.py --trace 0`` runs it
(``--spans off``); or both, the pairs in turns (off, on; on, off; ...),
so the recorder's cost shows: in the end-to-end metrics with
``--trace 0``, in the per-layer ones with ``--trace 1``.  After a run
with the recorder on, ``span_reduce`` also reads the spans against the
profiler trace (``--trace 1``): the clock offset and the device's idle
time by the program span open.  Tables go to standard error, one JSON
line per run to standard output (and to ``--out``).  The benchmark's own
runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent


def run_one(cell, seed: int, seconds: float, trace: bool, record: bool,
            device, pipe_hook=None) -> dict:
    """One run of ``cell``; -> its result line, with the span numbers
    under ``spans`` when ``record``."""
    import harness
    import span_reduce

    keep: dict = {}
    out = harness.run(cell, seed, seconds, trace, t_start=time.monotonic(),
                      device=device, pipe_hook=pipe_hook, keep=keep,
                      record_spans=record)
    if record:
        ctx = keep["ctx"]
        reduced = span_reduce.reduce(
            ctx.program_spans, ctx.counters, ctx.hero, ctx.devices,
            ctx.window, ctx.trace_window, ctx.queries)
        for line in span_reduce.table(reduced):
            print(line, file=sys.stderr)
        out["spans"] = dict(reduced, counters=ctx.counters,
                            recorded=len(ctx.program_spans))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=1)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--spans", choices=("on", "off", "both"), default="on")
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(BENCH), str(BENCH.parents[1] / "src")]
    import jax

    import harness

    cell = harness.load_cell(args.workload)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print("spanrun.py: needs a TPU", file=sys.stderr)
        return 2
    plan = []
    for i, seed in enumerate(args.seeds):
        if args.spans == "both":
            plan += [(seed, i % 2 == 1), (seed, i % 2 == 0)]
        else:
            plan.append((seed, args.spans == "on"))
    for seed, record in plan:
        out = run_one(cell, seed, args.seconds, bool(args.trace), record,
                      dev)
        line = json.dumps({
            "workload": args.workload, "seed": seed, "spans": record,
            "trace": args.trace, "correct": out["correct"],
            "attempted": out["attempted"], "failed": out["failed"],
            "metrics": {k: v["value"] for k, v in out["metrics"].items()},
            "device": out["device"], "breakdown": out.get("breakdown"),
            "program_spans": out.get("spans")})
        print(line, flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
