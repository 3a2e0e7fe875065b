"""The served live path at reduced widths: every dispatched stage reaches a
real stage fn, a warm run compiles nothing, and the compile cache lands
where the entry points say."""
from collections import Counter
from pathlib import Path

import jax
import pytest
from jax import monitoring

from repro.api import HeroSession, SessionOptions
from repro.core.events import EV_RETRY
from repro.launch import compile_cache
from repro.launch.serve import SERVE_OVERRIDES, build_stage_fns
from repro.rag import default_means, sample_traces

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


@pytest.fixture
def compile_count():
    seen = Counter()

    def listen(event, secs, **kw):
        seen[event] += 1

    monitoring.register_event_duration_secs_listener(listen)
    yield lambda: seen[COMPILE_EVENT]
    monitoring.unregister_event_duration_listener(listen)


@pytest.mark.slow
def test_live_w2_serves_every_stage_and_warm_run_compiles_nothing(
        compile_count):
    calls = Counter()

    def counted(stage, fn):
        def run(node, batch):
            calls[stage] += 1
            return fn(node, batch)
        return run

    fns = {s: counted(s, f)
           for s, f in build_stage_fns(reduced_widths=True).items()}

    def run_one():
        traces = sample_traces("finqabench", 1, seed=1)
        sess = HeroSession(world="sd8gen4", family="qwen3", backend="live",
                           means=default_means(traces),
                           options=SessionOptions(
                               coalesce=True, cfg_overrides=SERVE_OVERRIDES),
                           stage_fns=fns)
        done = set()
        sess.submit(traces[0], wf=2, on_stage_done=lambda h, node, t:
                    done.add((node.stage, node.kind)))
        results = sess.run(mode="shared", timeout=300)
        assert len(results) == 1 and "chat_decode" in results[0].stage_latency
        assert not any(e[1] == EV_RETRY for e in sess.last_run.events)
        return done

    done = run_one()
    served = {s for s, kind in done if kind != "io"}
    assert served and served <= set(fns) - {"__io__"}
    assert {"embed", "vsearch", "rerank", "rewrite_decode",
            "chat_decode"} <= set(calls)
    before = compile_count()
    run_one()
    assert compile_count() == before     # jitted prefill/decode, fixed shapes


def test_compile_cache_honours_env(monkeypatch, tmp_path):
    monkeypatch.setenv(compile_cache.ENV, str(tmp_path))
    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a: updates.append(a))
    assert compile_cache.enable_compile_cache() == tmp_path
    assert updates == []        # JAX reads the variable itself


def test_compile_cache_defaults_to_fixed_checkout_dir(monkeypatch):
    monkeypatch.delenv(compile_cache.ENV, raising=False)
    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a: updates.append(a))
    first = compile_cache.enable_compile_cache()
    assert compile_cache.enable_compile_cache() == first
    assert first == Path(__file__).resolve().parents[1] / ".jax_cache"
    assert updates == [("jax_compilation_cache_dir", str(first))] * 2
