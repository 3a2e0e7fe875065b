"""Serving runtime tests: engine (chunked prefill + continuous batching)
and the wall-clock HeRo runtime (straggler/fault handling)."""
import time

import jax
import pytest

from repro.configs import get_config, reduced
from repro.core import (GroundTruthPerf, HeroScheduler, LinearPerfModel,
                        SchedulerConfig, StageModel, snapdragon_8gen4)
from repro.core.dag import DynamicDAG, Node
from repro.models import build_model
from repro.serving import HeroRuntime, PUExecutor, ServingEngine


@pytest.fixture(scope="module")
def engine():
    cfg = reduced(get_config("qwen1.5-0.5b"))
    params = build_model(cfg).init(jax.random.PRNGKey(0))
    return ServingEngine(cfg, params, max_len=128, prefill_chunk=16,
                         token_group=4)


def test_engine_continuous_batching(engine):
    rids = [engine.submit([5 + i] * (10 + 7 * i), max_new=5)
            for i in range(3)]
    done = engine.run_to_completion()
    assert sorted(r.rid for r in done) == sorted(rids)
    for r in done:
        assert 1 <= len(r.generated) <= 5
        assert r.prefilled == len(r.prompt_ids)   # chunked prefill completed


def test_engine_chunked_prefill_bounded(engine):
    rid = engine.submit(list(range(4, 64)), max_new=3)
    steps = 0
    while engine.queue or engine.active:
        engine.step()
        steps += 1
        assert steps < 100
    # 60 prompt tokens / 16-token chunks -> at least 4 prefill steps
    assert steps >= 4


@pytest.fixture(scope="module")
def runtime_world():
    soc = snapdragon_8gen4()
    stages = {"a": StageModel("a", int(1e8), 512, "batchable"),
              "b": StageModel("b", int(1e8), 512, "batchable")}
    gt = GroundTruthPerf(soc, stages)
    return soc, LinearPerfModel().fit(gt)


def test_runtime_straggler_rerouting(runtime_world):
    soc, perf = runtime_world
    dag = DynamicDAG()
    dag.add(Node("n1", "a", "batchable", 4))
    dag.add(Node("n2", "b", "batchable", 4, deps={"n1"}))
    calls = {"n": 0}

    def work(node, batch):
        calls["n"] += 1
        time.sleep(2.0 if calls["n"] == 1 else 0.01)
        return node.id

    sched = HeroScheduler(perf, ["cpu", "gpu", "npu"], soc.dram_bw,
                          SchedulerConfig())
    rt = HeroRuntime(sched, {p: PUExecutor(p) for p in ("cpu", "gpu", "npu")},
                     {"a": work, "b": work})
    t0 = time.time()
    res = rt.run(dag, timeout=30)
    assert sorted(res) == ["n1", "n2"]
    assert time.time() - t0 < 1.5          # straggler absorbed, not awaited
    assert any(e[1] == "straggler" for e in rt.events)


def test_runtime_retry_on_exception(runtime_world):
    soc, perf = runtime_world
    dag = DynamicDAG()
    dag.add(Node("n1", "a", "batchable", 4))
    attempts = {"n": 0}

    def flaky(node, batch):
        attempts["n"] += 1
        if attempts["n"] == 1:
            raise RuntimeError("transient")
        return "ok"

    sched = HeroScheduler(perf, ["cpu", "gpu", "npu"], soc.dram_bw,
                          SchedulerConfig())
    rt = HeroRuntime(sched, {p: PUExecutor(p) for p in ("cpu", "gpu", "npu")},
                     {"a": flaky})
    res = rt.run(dag, timeout=30)
    assert res["n1"] == "ok"
    assert attempts["n"] == 2
    assert any(e[1] == "retry" for e in rt.events)


class _DrainingExecutor(PUExecutor):
    """Reports busy for its first ``busy_calls`` queries: a cancelled
    straggler that finishes draining right after a dispatch pass saw it."""

    def __init__(self, name, busy_calls):
        super().__init__(name)
        self.busy_calls = busy_calls

    def busy(self):
        if self.busy_calls > 0:
            self.busy_calls -= 1
            return True
        return super().busy()


def test_runtime_straggler_drain_is_not_a_deadlock(runtime_world):
    soc, perf = runtime_world
    dag = DynamicDAG()
    dag.add(Node("n1", "a", "batchable", 4))
    sched = HeroScheduler(perf, ["cpu"], soc.dram_bw, SchedulerConfig())
    rt = HeroRuntime(sched, {"cpu": _DrainingExecutor("cpu", busy_calls=2)},
                     {"a": lambda n, b: n.id})
    assert rt.run(dag, timeout=30) == {"n1": "n1"}


def test_runtime_elastic_membership(runtime_world):
    soc, perf = runtime_world
    sched = HeroScheduler(perf, ["cpu"], soc.dram_bw, SchedulerConfig())
    rt = HeroRuntime(sched, {"cpu": PUExecutor("cpu")},
                     {"a": lambda n, b: n.id})
    rt.add_executor("npu", PUExecutor("npu"))
    assert "npu" in sched.pus
    dag = DynamicDAG()
    dag.add(Node("n1", "a", "batchable", 64))
    res = rt.run(dag, timeout=30)
    assert res["n1"] == "n1"
    rt.remove_executor("npu")
    assert "npu" not in sched.pus
