"""End-to-end behaviour tests: paper-claim validation on the simulator and
the full executable RAG pipeline under the HeRo runtime."""
import dataclasses
import time

import jax
import numpy as np
import pytest

from repro.configs import get_family, reduced
from repro.core import (GroundTruthPerf, HeroScheduler, LinearPerfModel,
                        SchedulerConfig, Simulator, snapdragon_8gen3,
                        snapdragon_8gen4, strategy_config, tpu_v5e_slices)
from repro.rag import (STAGE_ROLES, build_stages, build_workflow,
                       default_means, make_template, sample_traces)


def run_strategy(strat, soc, family, wf, ds, n=4, seed=1):
    stages = build_stages(get_family(family))
    gt = GroundTruthPerf(soc, stages)
    perf = LinearPerfModel().fit(gt)
    traces = sample_traces(ds, n, seed=seed)
    means = default_means(traces)
    lat = []
    for tr in traces:
        if strat == "hero":
            cfg, tmpl = SchedulerConfig(), make_template(wf, means)
        else:
            cfg, tmpl = strategy_config(strat, STAGE_ROLES), None
        dag = build_workflow(wf, tr, fine_grained=cfg.enable_partition)
        sched = HeroScheduler(perf, [p.name for p in soc.pus], soc.dram_bw,
                              cfg, template=tmpl)
        lat.append(Simulator(gt, sched).run(dag).makespan)
    return float(np.mean(lat))


@pytest.mark.parametrize("wf", [1, 2, 3])
def test_hero_beats_all_baselines(wf):
    """Paper §6.2: HeRo delivers consistent improvements over all baselines."""
    soc = snapdragon_8gen4()
    hero = run_strategy("hero", soc, "qwen3", wf, "hotpotqa")
    for strat in ("llamacpp_gpu", "powerserve_npu", "ayo_like"):
        base = run_strategy(strat, soc, "qwen3", wf, "hotpotqa")
        assert hero < base, (wf, strat, hero, base)


def test_speedup_magnitudes_in_paper_range():
    """Headline ranges: multi-x vs GPU-only; >1 vs Ayo-like."""
    soc = snapdragon_8gen3()
    hero = run_strategy("hero", soc, "qwen3", 3, "2wikimqa")
    gpu = run_strategy("llamacpp_gpu", soc, "qwen3", 3, "2wikimqa")
    ayo = run_strategy("ayo_like", soc, "qwen3", 3, "2wikimqa")
    assert gpu / hero > 3.0        # paper: up to 10.94x
    assert 1.2 < ayo / hero < 4.0  # paper: 1.5x (text) / 3.2x (Table 3)


def test_ablation_ordering_matches_table3():
    """Table 3: each technique helps; ALL is best."""
    soc = snapdragon_8gen4()
    stages = build_stages(get_family("bge"))
    gt = GroundTruthPerf(soc, stages)
    perf = LinearPerfModel().fit(gt)
    traces = sample_traces("2wikimqa", 3, seed=3)
    means = default_means(traces)

    def run(flags):
        lat = []
        for tr in traces:
            tmpl = None
            if flags == "ayo":
                cfg = strategy_config("ayo_like", STAGE_ROLES)
            elif flags == "all":
                cfg, tmpl = SchedulerConfig(), make_template(3, means)
            elif flags == "crit":
                cfg = dataclasses.replace(
                    strategy_config("ayo_like", STAGE_ROLES),
                    enable_criticality=True, static_map=None)
                tmpl = make_template(3, means)
            elif flags == "part":
                cfg = dataclasses.replace(
                    strategy_config("ayo_like", STAGE_ROLES),
                    enable_partition=True)
            dag = build_workflow(3, tr, fine_grained=cfg.enable_partition)
            sched = HeroScheduler(perf, [p.name for p in soc.pus],
                                  soc.dram_bw, cfg, template=tmpl)
            lat.append(Simulator(gt, sched).run(dag).makespan)
        return float(np.mean(lat))

    base = run("ayo")
    part, crit, full = run("part"), run("crit"), run("all")
    assert part < base * 1.02          # partition alone helps (C2 regime)
    assert crit < base                 # criticality alone helps
    assert full <= min(part, crit) * 1.05  # ALL is best (within noise)


def test_tpu_slice_deployment_runs():
    """The TPU-pod PU-group deployment: same scheduler, v5e slices."""
    soc = tpu_v5e_slices({"slice_s": 8, "slice_m": 32, "slice_l": 216})
    stages = build_stages(get_family("qwen3"))
    gt = GroundTruthPerf(soc, stages)
    perf = LinearPerfModel().fit(gt)
    tr = sample_traces("hotpotqa", 1, seed=0)[0]
    dag = build_workflow(2, tr, fine_grained=True)
    sched = HeroScheduler(perf, [p.name for p in soc.pus], soc.dram_bw,
                          SchedulerConfig())
    res = Simulator(gt, sched).run(dag)
    assert not dag.unfinished()
    assert res.makespan < 5.0          # a pod is far faster than a phone


@pytest.mark.slow
def test_executable_pipeline_end_to_end():
    """The real JAX pipeline (tiny models) under the HeRo wall-clock
    runtime: chunk -> embed -> index -> search -> rerank -> agents -> chat."""
    import sys
    import repro.launch.serve as serve_mod
    argv = sys.argv
    sys.argv = ["serve", "--workflow", "2", "--queries", "1", "--reduced"]
    try:
        serve_mod.main()
    finally:
        sys.argv = argv
