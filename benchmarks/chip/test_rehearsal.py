"""Both cells end to end on the CPU at reduced widths: traffic, set-up,
window, metric readers and the comparison (``rehearsal.py``)."""
import json
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

import harness
import rehearsal

ROOT = Path(__file__).resolve().parents[2]
E2E = {"qwen3-w2-poisson": {"setup_s", "query_p50_s"},
       "qwen3-w2-single": {"setup_s", "query_p50_s", "query_p90_s",
                           "ttft_p90_s"}}
HOST_LAYER = {"first_dispatch_wait_p90_ms", "decode_width_mean",
              "redispatches", "window_compiles", "lm_dispatch_ms",
              "retrieval_dispatch_ms", "dispatch_pass_share",
              "ready_wait_p90_ms", "wasted_dispatch_share",
              "lm_kv_used_share"}
DEVICE_ONLY = {"decode_mfu", "topk_retrieval_roofline", "device_idle_share",
               "lm_step_idle_ms", "idle_with_work_share"}
SPAN_READERS = {"dispatch_pass_share", "ready_wait_p90_ms",
                "wasted_dispatch_share", "lm_kv_used_share",
                "lm_step_idle_ms", "idle_with_work_share"}


def watch_recorder(seen):
    """A pipe hook that notes, at every embed call of set-up and window,
    whether the program's span recorder is on."""
    from repro.serving import spans

    def hook(pipe):
        embed = pipe.embedder.embed

        def run(token_lists):
            seen.append(spans.enabled())
            return embed(token_lists)
        pipe.embedder.embed = run
    return hook


def _check_line(cell, out, trace):
    assert list(out)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(out)[-1] == "checks"
    assert out["correct"] is True, out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    json.dumps(out)
    names = set(out["metrics"])
    if trace:
        assert HOST_LAYER <= names and not names & DEVICE_ONLY
        assert "breakdown" in out
    else:
        assert names == E2E[cell]


@pytest.mark.parametrize("trace", [False, True])
def test_open_loop_cell(trace):
    from repro.serving import spans

    seen = []
    cell = rehearsal.reduced_cell("qwen3-w2-poisson", rate_qps=2.0)
    out = rehearsal.run(cell, 2 ** 31 + 11, 3.0, trace,
                        pipe_hook=watch_recorder(seen))
    _check_line("qwen3-w2-poisson", out, trace)
    # the recorder is on through a traced run only, and off after it
    assert seen and set(seen) == {trace} and not spans.enabled()
    if trace:
        assert out["metrics"]["window_compiles"]["value"] == 0


@pytest.mark.parametrize("trace", [False, True])
def test_closed_loop_cell(trace):
    from repro.serving import spans

    seen = []
    cell = rehearsal.reduced_cell("qwen3-w2-single", max_queries=40)
    out = rehearsal.run(cell, 17, 3.0, trace, pipe_hook=watch_recorder(seen))
    _check_line("qwen3-w2-single", out, trace)
    assert seen and set(seen) == {trace} and not spans.enabled()
    if trace:
        # one query at a time: nothing to coalesce
        assert out["metrics"]["decode_width_mean"]["value"] == 1


def test_every_metric_has_a_reader():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    names = set()
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert callable(harness.reader(m["name"]))
        names.add(m["name"])
    assert SPAN_READERS <= names


@pytest.mark.parametrize("name", sorted(SPAN_READERS))
def test_span_reader_reads_nothing_with_the_recorder_off(name):
    ctx = SimpleNamespace(program_spans=None, counters=None, clock=None,
                          devices={}, trace_window=(0.0, 1.0),
                          window=(0.0, 1.0), queries=[])
    assert harness.reader(name)(ctx) is None


def test_command_refuses_to_run_without_a_tpu():
    p = subprocess.run([sys.executable, "benchmarks/chip/run.py",
                        "--workload", "qwen3-w2-single", "--seed", "1",
                        "--seconds", "1", "--trace", "0"], cwd=ROOT,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode == 2 and p.stdout == ""
