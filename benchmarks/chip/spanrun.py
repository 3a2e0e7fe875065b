"""Runs of a cell with the program's span recorder on, on the chip.

    python3 benchmarks/chip/spanrun.py --workload <cell> --seconds <s> \
        --trace <0|1> --seeds <n> [<n> ...] [--spans on|off|both] \
        [--out <file.jsonl>]

For each seed, in this one process, one run of the cell as ``run.py``
makes it (``harness.run``), with the program's span recorder
(``repro.serving.spans``) on from the end of set-up, annotating the
profiler trace, and cleared where the measured window starts
(``--spans on``); or off, as ``run.py`` runs (``--spans off``); or both,
the pairs in turns (off, on; on, off; ...), so the recorder's cost shows
in the end-to-end metrics of ``--trace 0`` runs.  After a run with the
recorder on, ``span_reduce`` reads the spans against the profiler trace
(``--trace 1``): the clock offset, the device's idle time by the program
span open, and the per-layer numbers the spans give.  Tables go to
standard error, one JSON line per run to standard output (and to
``--out``).  The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path
from typing import Optional

BENCH = Path(__file__).resolve().parent


def run_one(cell, seed: int, seconds: float, trace: bool, record: bool,
            device, pipe_hook=None) -> dict:
    """One run of ``cell``; -> its result line, with the span numbers
    under ``spans`` when ``record``."""
    import harness
    import span_reduce
    import trace_reduce
    from repro.serving import spans

    class SpanRecorder(harness.Recorder):
        """Clearing the harness's records (at the end of set-up and where
        the window starts) also clears the program's spans."""

        def clear(self):
            super().clear()
            if record:
                spans.enable(annotate=True)
            spans.clear()

    class KeepingTracer(harness.Tracer):
        """``harness.Tracer`` that keeps, as it reads the trace and before
        the trace's files go, the device's op events and the program's
        ``hero:`` annotations."""

        last: Optional["KeepingTracer"] = None

        def __init__(self, seconds):
            super().__init__(seconds)
            self.kept = {"devices": {}, "hero": []}
            KeepingTracer.last = self

        def finish(self):
            load = trace_reduce.load

            def keep(path):
                events = load(path)
                self.kept = {"devices": events["devices"],
                             "hero": span_reduce.load_hero(path)}
                return events

            trace_reduce.load = keep
            try:
                return super().finish()
            finally:
                trace_reduce.load = load

    spans.disable()
    spans.clear()
    keep: dict = {}
    saved = harness.Recorder, harness.Tracer
    harness.Recorder, harness.Tracer = SpanRecorder, KeepingTracer
    try:
        out = harness.run(cell, seed, seconds, trace,
                          t_start=time.monotonic(), device=device,
                          pipe_hook=pipe_hook, keep=keep)
    finally:
        harness.Recorder, harness.Tracer = saved
        recorded, counters = spans.drain()
        spans.disable()
    if record:
        tr = KeepingTracer.last if trace else None
        kept = tr.kept if tr is not None else {"devices": {}, "hero": []}
        reduced = span_reduce.reduce(
            recorded, counters, kept["hero"], kept["devices"],
            keep["window"], (tr.t0, tr.t1) if tr is not None else None,
            keep["queries"])
        for line in span_reduce.table(reduced):
            print(line, file=sys.stderr)
        out["spans"] = dict(reduced, counters=counters,
                            recorded=len(recorded))
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
