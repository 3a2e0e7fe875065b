"""repro.analysis.lint — one positive and one negative case per rule,
plus the gate the CI job enforces: the real tree lints clean."""
import ast
import os

from repro.analysis.lint import (check_config_gates, check_core_determinism,
                                 check_counter_pairing, check_event_literals,
                                 check_fit_rng_order, lint_paths)

SRC = os.path.join(os.path.dirname(__file__), os.pardir, "src")

SIM = "src/repro/core/simulator.py"        # an event module, in core/
PM = "src/repro/core/perf_model.py"
SCHED = "src/repro/core/scheduler.py"
BACK = "src/repro/api/backends.py"
RES = "src/repro/api/results.py"


def _rules(violations):
    return [v.rule for v in violations]


def _evt(src):
    return check_event_literals(ast.parse(src), "core/simulator.py", SIM)


# --- EVT001 / EVT002 ---------------------------------------------------------

def test_evt001_raw_string_in_note():
    vs = _evt("self._note(timeline, t, 'done', n)")
    assert _rules(vs) == ["EVT001"]


def test_evt001_raw_string_in_emit():
    vs = _evt("self._emit(t, 'start', n)")
    assert _rules(vs) == ["EVT001"]


def test_evt001_raw_string_on_events_queue():
    vs = _evt("self._events.append(('kv_evict', node))")
    assert _rules(vs) == ["EVT001"]


def test_evt001_comparison_against_event_literal():
    vs = _evt("if ev == 'redispatch':\n    pass")
    assert _rules(vs) == ["EVT001"]


def test_evt001_membership_tuple_literal():
    vs = _evt("if ev in ('start', EV_DONE):\n    pass")
    assert _rules(vs) == ["EVT001"]


def test_evt_negative_constants_are_clean():
    vs = _evt("self._note(timeline, t, EV_DONE, n)\n"
              "self._emit(t, EV_START, n)\n"
              "self._events.append((EV_KV_EVICT, node))\n"
              "if ev in (EV_START, EV_DONE):\n    pass")
    assert vs == []


def test_evt002_typo_flagged():
    vs = _evt("if ev == 'kv_migrat':\n    pass")
    assert _rules(vs) == ["EVT002"]


def test_evt002_negative_unrelated_string():
    # not within edit distance 1 of any event name
    vs = _evt("if mode == 'shared':\n    pass")
    assert vs == []


def test_evt_rules_only_apply_to_event_modules():
    tree = ast.parse("self._note(timeline, t, 'done', n)")
    assert check_event_literals(tree, "rag/workflow.py",
                                "src/repro/rag/workflow.py") == []


# --- EVT001 / EVT002 on span and counter names --------------------------------

EXE = "src/repro/serving/executor.py"     # an event module that records spans


def _span(src, key="serving/executor.py", path=EXE):
    return check_event_literals(ast.parse(src), key, path)


def test_evt001_raw_span_name():
    vs = _span("with spans.span('runtime.poll'):\n    pass")
    assert _rules(vs) == ["EVT001"]


def test_evt001_misspelled_span_name_in_a_recording_module():
    vs = _span("spans.record('lm.stp', t0, t1)", key="rag/agents.py",
               path="src/repro/rag/agents.py")
    assert _rules(vs) == ["EVT001"]
    assert "'lm.step'" in vs[0].message


def test_evt001_raw_counter_name():
    vs = _span("spans.count('runtime.passes')", key="api/session.py",
               path="src/repro/api/session.py")
    assert _rules(vs) == ["EVT001"]


def test_evt001_constant_of_the_wrong_kind():
    assert _rules(_span("spans.count(SP_LM_STEP, 3)")) == ["EVT001"]
    assert _rules(_span("spans.span(CT_LM_STEPS)")) == ["EVT001"]


def test_evt001_comparison_against_raw_span_name():
    vs = _span("if s.name == 'lm.fetch':\n    pass")
    assert _rules(vs) == ["EVT001"]


def test_evt002_span_name_typo_flagged():
    vs = _span("if s.name == 'runtime.pol':\n    pass")
    assert _rules(vs) == ["EVT002"]


def test_span_negative_constants_and_other_counts_are_clean():
    vs = _span("with spans.span(SP_RUNTIME_POLL, qids, stage=s):\n"
               "    pass\n"
               "spans.record(SP_RUNTIME_READY_WAIT, t0, t1)\n"
               "spans.count(CT_RUNTIME_PASSES)\n"
               "n = ids.count('q1')\n")
    assert vs == []


def test_span_rules_only_apply_to_recording_modules():
    vs = check_event_literals(ast.parse("spans.span('lm.stpe')"),
                              "rag/chunker.py", "src/repro/rag/chunker.py")
    assert vs == []


# --- CFG001 / CFG002 ---------------------------------------------------------

def _cfg(sched_src, extra=None):
    trees = {SCHED: ast.parse(sched_src)}
    if extra is not None:
        trees["src/repro/api/other.py"] = ast.parse(extra)
    return check_config_gates(trees)


def test_cfg001_default_on_knob_flagged():
    vs = _cfg("BASELINE_ON_KNOBS = frozenset({'decode_batch'})\n"
              "class SchedulerConfig:\n"
              "    sneaky: bool = True\n"
              "    decode_batch: bool = True\n"
              "if cfg.sneaky: pass\n"
              "if cfg.decode_batch: pass\n")
    assert _rules(vs) == ["CFG001"]
    assert "sneaky" in vs[0].message


def test_cfg001_negative_baseline_declared():
    vs = _cfg("BASELINE_ON_KNOBS = frozenset({'decode_batch'})\n"
              "class SchedulerConfig:\n"
              "    decode_batch: bool = True\n"
              "if cfg.decode_batch: pass\n")
    assert vs == []


def test_cfg002_unread_gate_flagged():
    vs = _cfg("BASELINE_ON_KNOBS = frozenset()\n"
              "class SchedulerConfig:\n"
              "    ghost_feature: bool = False\n")
    assert _rules(vs) == ["CFG002"]


def test_cfg002_negative_boolean_read_anywhere_in_tree():
    vs = _cfg("BASELINE_ON_KNOBS = frozenset()\n"
              "class SchedulerConfig:\n"
              "    coalesce: bool = False\n",
              extra="if cfg.coalesce and ready:\n    pass\n")
    assert vs == []


def test_cfg002_negative_keyword_passthrough_counts_as_read():
    # the scheduler's own idiom: PagedKVCache(..., prefetch=cfg.kv_prefetch)
    vs = _cfg("BASELINE_ON_KNOBS = frozenset()\n"
              "class SchedulerConfig:\n"
              "    kv_prefetch: bool = False\n"
              "kv = PagedKVCache(prefetch=self.cfg.kv_prefetch)\n")
    assert vs == []


# --- RNG001 / RNG002 ---------------------------------------------------------

GOOD_FIT = """
class M:
    def _fit_noisy(self, rng):
        return rng.normal()

    def fit(self, seed=0):
        rng = np.random.default_rng(seed)
        for s in self.stages:
            self._fit_noisy(rng)
        self._grid = [self.solve(x) for x in self.xs]
        return self
"""

BAD_FIT = """
class M:
    def _fit_noisy(self, rng):
        return rng.normal()

    def fit(self, seed=0):
        rng = np.random.default_rng(seed)
        self._grid = [self.solve(x) for x in self.xs]
        for s in self.stages:
            self._fit_noisy(rng)
        return self
"""


def test_rng001_noiseless_grid_before_noisy_loop():
    vs = check_fit_rng_order(ast.parse(BAD_FIT), "core/perf_model.py", PM)
    assert _rules(vs) == ["RNG001"]


def test_rng001_negative_correct_order():
    vs = check_fit_rng_order(ast.parse(GOOD_FIT), "core/perf_model.py", PM)
    assert vs == []


def test_rng002_unseeded_or_rebound_rng():
    src = GOOD_FIT.replace("rng = np.random.default_rng(seed)",
                           "rng = make_rng()")
    vs = check_fit_rng_order(ast.parse(src), "core/perf_model.py", PM)
    assert "RNG002" in _rules(vs)


def test_rng_rules_only_apply_to_perf_model():
    assert check_fit_rng_order(ast.parse(BAD_FIT), "core/other.py",
                               "src/repro/core/other.py") == []


# --- DET001 / DET002 / DET003 ------------------------------------------------

def _det(src, key="core/simulator.py"):
    return check_core_determinism(ast.parse(src), key, SIM)


def test_det001_time_and_random_imports():
    assert _rules(_det("import time")) == ["DET001"]
    assert _rules(_det("from random import choice")) == ["DET001"]


def test_det002_legacy_global_stream():
    assert _rules(_det("x = np.random.normal(0, 1)")) == ["DET002"]


def test_det003_unseeded_default_rng():
    assert _rules(_det("rng = np.random.default_rng()")) == ["DET003"]


def test_det_negative_seeded_rng_and_math():
    assert _det("import math\n"
                "rng = np.random.default_rng(7)\n"
                "x = rng.normal(0, 1)\n") == []


def test_det_rules_only_apply_to_core():
    assert check_core_determinism(ast.parse("import time"),
                                  "serving/executor.py",
                                  "src/repro/serving/executor.py") == []


# --- CNT001 ------------------------------------------------------------------

def _cnt(back_src, res_src):
    return check_counter_pairing({BACK: ast.parse(back_src),
                                  RES: ast.parse(res_src)})


def test_cnt001_orphan_counter_flagged():
    vs = _cnt("RUN_ONLY_COUNTERS = frozenset({'kv_evictions'})\n"
              "class BackendRun:\n"
              "    makespan: float\n"
              "    events: list\n"
              "    batching: dict\n"
              "    kv_evictions: int = 0\n"
              "    orphan_count: int = 0\n",
              "class QueryResult:\n"
              "    makespan: float\n")
    assert _rules(vs) == ["CNT001"]
    assert "orphan_count" in vs[0].message


def test_cnt001_negative_paired_or_declared():
    vs = _cnt("RUN_ONLY_COUNTERS = frozenset({'kv_evictions'})\n"
              "class BackendRun:\n"
              "    makespan: float\n"
              "    events: list\n"
              "    batching: dict\n"
              "    kv_evictions: int = 0\n"
              "    kv_fetches: int = 0\n",
              "class QueryResult:\n"
              "    makespan: float\n"
              "    kv_fetches: int = 0\n")
    assert vs == []


# --- the CI gate: the real tree is clean -------------------------------------

def test_real_tree_lints_clean():
    violations = lint_paths([SRC])
    assert violations == [], "\n".join(str(v) for v in violations)
