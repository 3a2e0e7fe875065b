"""Benchmark driver — one section per paper table/figure.
Prints one CSV block per section.

    python benchmarks/run.py --list          # enumerate sections
    python benchmarks/run.py --only Serving  # run matching sections only
    python benchmarks/run.py --quick         # reduced sweeps
"""
from __future__ import annotations

import os
import sys
import time

# make ``python benchmarks/run.py`` work from a checkout: the script's dir
# is on sys.path but the ``benchmarks`` package root (repo root) is not
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def session_facade(csv=print):
    """Facade smoke: the same session script on both backends (sim gives
    modelled SoC seconds, live gives wall seconds over dry executors)."""
    from repro.api import HeroSession
    from repro.rag import sample_traces

    trace = sample_traces("finqabench", 1, seed=2)[0]
    csv("backend,strategy,makespan_s,dispatches")
    for backend in ("sim", "live"):
        for strategy in ("hero", "llamacpp_gpu"):
            sess = HeroSession(world="sd8gen4", family="qwen3",
                               strategy=strategy, backend=backend)
            sess.submit(trace, wf=2)
            [res] = sess.run(timeout=120)
            csv(f"{backend},{strategy},{res.makespan:.3f},{res.dispatches}")


def main() -> None:
    import argparse

    from benchmarks import (fig2_affinity, fig3_contention, fig5_qwen3,
                            fig6_bge, grid_search, multiquery,
                            table3_ablation)
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true",
                    help="reduced sweeps for the big sections")
    ap.add_argument("--list", action="store_true",
                    help="print section names and exit")
    ap.add_argument("--only", metavar="SUBSTR",
                    help="run only sections whose name contains SUBSTR "
                         "(case-insensitive)")
    args = ap.parse_args()
    quick = args.quick
    sections = [
        ("SessionFacade_sim_live (api)", session_facade, {}),
        ("Fig2_affinity_shape_sensitivity", fig2_affinity.run, {}),
        ("Fig3_contention_slowdown", fig3_contention.run, {}),
        ("Fig5_e2e_latency_qwen3", fig5_qwen3.run,
         {"n": 2, "datasets": ("finqabench", "2wikimqa")} if quick else {}),
        ("Fig6_e2e_latency_bge", fig6_bge.run,
         {"n": 2, "datasets": ("finqabench", "2wikimqa")} if quick else {}),
        ("Table3_technique_ablation", table3_ablation.run,
         {"n": 2} if quick else {}),
        ("GridSearch_alpha_beta (paper §5)", grid_search.run,
         {"n": 2} if quick else {}),
        ("MultiQuery_throughput (beyond-paper)", multiquery.run_admission,
         {}),
        ("Serving_continuous_batching (bench-smoke gate)",
         multiquery.serving_metrics, {}),
        ("Serving_prefix_cache (paged-KV bench-smoke leg)",
         multiquery.serving_metrics, {"regimes": ("prefix",)}),
        ("Serving_spec_decode (specdec bench-smoke leg)",
         multiquery.serving_metrics, {"regimes": ("specdec",)}),
        ("Serving-ablation_adaptive_vs_fixed_caps (CI gate)",
         multiquery.serving_ablation, {}),
    ]
    if args.list:
        for name, _, _ in sections:
            print(name)
        return
    only = args.only.lower() if args.only else None
    for name, fn, kwargs in sections:
        if only is not None and only not in name.lower():
            continue
        print(f"\n=== {name} ===")
        t0 = time.time()
        fn(**kwargs)
        print(f"# section wall time: {time.time() - t0:.1f}s")


if __name__ == "__main__":
    main()
