"""Predictive tier prefetch (PR 7) + the three accounting bugfixes.

Tentpole coverage: speculative staging of spill-resident pages under a
compute-overlap credit (``PagedKVCache.prefetch``) — budget debiting,
headroom clipping, pin safety (prefetch never demotes a pinned page and
never soft-overflows an arena), prefetch-hit accounting at the dispatch
gather, hit-frequency-weighted eviction, and the scheduler/session
gates.  Bugfix regressions: the hit-or-recompute rule declining
fetch-dominated prefix hits, soft overflows counted + recovered at
release, and the ``kv_stage`` convention trap (unit + spec level).
Plus sim/live accounting-protocol parity and a hypothesis
bytes-conservation property on a single-PU tier stack.
"""
import warnings

import pytest

from repro.api import HeroSession
from repro.api.spec import DecodeSpec, StageSpec, WorkflowSpec
from repro.core import SchedulerConfig
from repro.core.dag import Node
from repro.core.kv_pages import (DISK, DRAM, PagedKVCache, decode_stage_for)
from repro.core.scheduler import HeroScheduler
from repro.rag import shared_corpus_traces
from test_kv_pages import (STAGE, check_invariants, decode_node, paged_perf,
                           prefill_node, round_node)


def warm_pages(kv, key, tokens, pu="gpu", nid="w/p"):
    """Seed unpinned (refs == 0) hashed prefix pages and return their pids
    in prefix order."""
    before = set(kv._pages)
    kv.on_prefill_done(prefill_node(nid, [(key, tokens)]), pu)
    return sorted(set(kv._pages) - before)


# --- speculative staging -----------------------------------------------------

def test_prefetch_stages_spill_group_under_credit():
    kv = PagedKVCache(paged_perf(), page_tokens=4, prefetch=True)
    pids = warm_pages(kv, "ctx:a", 8)
    for pid in pids:
        kv._place(kv._pages[pid], DRAM)       # demoted between reuses
    n = decode_node("q0/d", ctx=0)
    spent = kv.prefetch(n, "gpu", 1.0, pids=pids)
    # fitted line: 8 tokens at 2e-3 s/tok, fully inside the budget
    assert spent == pytest.approx(8 * 2e-3)
    assert all(kv._pages[pid].tier == "gpu" for pid in pids)
    assert all(pid in kv._prefetched for pid in pids)
    assert kv.prefetches == 1 and kv.prefetch_bytes == 8.0
    assert n.payload["kv_prefetches"] == 1
    assert n.payload["kv_prefetch_bytes"] == 8.0
    assert [e for e, _n in kv.drain_events()] == ["kv_prefetch"]
    # the backend contract: one (stage, src, dst, tokens, credit) group
    assert kv.drain_prefetches() == [
        (STAGE, DRAM, "gpu", 8, pytest.approx(8 * 2e-3))]
    check_invariants(kv)


def test_prefetch_gates_off_and_zero_budget():
    n = decode_node("q0/d", ctx=0)
    for flag, budget in ((False, 1.0), (True, 0.0)):
        kv = PagedKVCache(paged_perf(), page_tokens=4, prefetch=flag)
        pids = warm_pages(kv, "ctx:a", 8)
        for pid in pids:
            kv._place(kv._pages[pid], DRAM)
        assert kv.prefetch(n, "gpu", budget, pids=pids) == 0.0
        assert kv.prefetches == 0 and not kv._prefetched
        assert all(kv._pages[pid].tier == DRAM for pid in pids)
        assert kv.drain_prefetches() == []


def test_prefetch_skips_resident_and_already_staged_pages():
    kv = PagedKVCache(paged_perf(), page_tokens=4, prefetch=True)
    pids = warm_pages(kv, "ctx:a", 8)
    kv._place(kv._pages[pids[1]], DRAM)       # only page 1 is in spill
    n = decode_node("q0/d", ctx=0)
    spent = kv.prefetch(n, "gpu", 1.0, pids=pids)
    assert spent == pytest.approx(4 * 2e-3)   # PU-resident page 0 is free
    assert kv.prefetches == 1 and kv.prefetch_bytes == 4.0
    # idempotent: the staged page is skipped until a gather consumes it
    assert kv.prefetch(n, "gpu", 1.0, pids=pids) == 0.0
    assert kv.prefetches == 1


def test_prefetch_budget_caps_credit_and_group_order():
    kv = PagedKVCache(paged_perf(), page_tokens=4, prefetch=True)
    pids = warm_pages(kv, "ctx:a", 8)
    kv._place(kv._pages[pids[0]], DISK)
    kv._place(kv._pages[pids[1]], DRAM)
    n = decode_node("q0/d", ctx=0)
    # budget covers exactly the disk group (sorted first): the staging
    # still completes, its credit is clipped to the window, and the dram
    # group waits for the next pass — the serial transfer-queue model
    spent = kv.prefetch(n, "gpu", 8 * 1e-3, pids=pids)
    assert spent == pytest.approx(8 * 1e-3)
    assert kv.prefetches == 1
    assert kv._pages[pids[0]].tier == "gpu"
    assert kv._pages[pids[1]].tier == DRAM
    assert kv.drain_prefetches() == [
        (STAGE, DISK, "gpu", 4, pytest.approx(8 * 1e-3))]


def test_prefetch_clips_group_to_headroom():
    # gpu arena: 12 B; a live stream pins 8 B, so headroom is 4 B — the
    # 3-page (12 B) spill group is clipped to its first page, the tail
    # left for the on-path gather (not skipped, not forced)
    kv = PagedKVCache(paged_perf(caps={"gpu": 12.0}), page_tokens=4,
                      prefetch=True)
    d = decode_node("q0/d", ctx=8, workload=1 << 20)
    kv.migrate_for_dispatch(round_node([d]), "gpu")
    pids = warm_pages(kv, "ctx:a", 12, pu=DRAM)
    n = decode_node("q1/d", ctx=0)
    spent = kv.prefetch(n, "gpu", 1.0, pids=pids)
    assert spent == pytest.approx(4 * 2e-3)
    assert kv._pages[pids[0]].tier == "gpu"
    assert [kv._pages[p].tier for p in pids[1:]] == [DRAM, DRAM]
    assert kv.prefetch_bytes == 4.0
    assert kv.soft_overflows == 0 and kv.evictions == 0
    check_invariants(kv)


def test_prefetch_never_demotes_pinned_pages_or_overflows():
    # arena exactly full of pinned stream pages: zero headroom, so the
    # staging is a no-op — prefetch must never evict a live stream's
    # pages or soft-overflow an arena to make room for speculation
    kv = PagedKVCache(paged_perf(caps={"gpu": 8.0}), page_tokens=4,
                      prefetch=True)
    d = decode_node("q0/d", ctx=8, workload=1 << 20)
    kv.migrate_for_dispatch(round_node([d]), "gpu")
    stream_pages = list(kv.tracked(d).pages)
    pids = warm_pages(kv, "ctx:a", 4, pu=DRAM)
    n = decode_node("q1/d", ctx=0)
    assert kv.prefetch(n, "gpu", 1.0, pids=pids) == 0.0
    assert kv.prefetches == 0 and kv.soft_overflows == 0
    assert kv._pages[pids[0]].tier == DRAM
    assert all(kv._pages[p].tier == "gpu" for p in stream_pages)
    check_invariants(kv)


def test_prefetch_hit_and_thrash_accounting_at_gather():
    kv = PagedKVCache(paged_perf(), page_tokens=4, prefetch=True)
    pids = warm_pages(kv, "ctx:a", 8)
    for pid in pids:
        kv._place(kv._pages[pid], DISK)
    # a new query hits the disk-resident prefix, the scheduler stages it
    hit = prefill_node("q1/p", [("ctx:a", 8), ("q:q1", 4)], stream="q1/d")
    kv.apply_prefix_hits(hit)
    assert hit.payload["kv_page_hits"] == 2
    kv.prefetch(hit, "gpu", 1.0, pids=hit.payload["kv_hit_pages"])
    kv.on_prefill_done(hit, "gpu")
    d = decode_node("q1/d", ctx=12, workload=1 << 20)
    d.group = "q1/d"
    moved = kv.migrate_for_dispatch(round_node([d]), "gpu")
    # the gather finds the staged pages resident: prefetch hits, and no
    # on-path fetch is paid for them
    assert moved == []
    assert kv.prefetch_hits == 2 and kv.fetches == 0
    assert d.payload["kv_prefetch_hits"] == 2
    assert not kv._prefetched                 # consumed, not re-counted
    check_invariants(kv)


def test_prefetch_staged_to_wrong_pu_is_thrash_not_hit():
    kv = PagedKVCache(paged_perf(), page_tokens=4, prefetch=True)
    pids = warm_pages(kv, "ctx:a", 4)
    kv._place(kv._pages[pids[0]], DRAM)
    hit = prefill_node("q1/p", [("ctx:a", 4), ("q:q1", 4)], stream="q1/d")
    kv.apply_prefix_hits(hit)
    kv.prefetch(hit, "cpu", 1.0, pids=hit.payload["kv_hit_pages"])
    kv.on_prefill_done(hit, "cpu")
    d = decode_node("q1/d", ctx=8, workload=1 << 20)
    d.group = "q1/d"
    # the decode lands elsewhere: the staged page is NOT a hit — it pays
    # the PU->PU gather like any other misplaced page
    kv.migrate_for_dispatch(round_node([d]), "gpu")
    assert kv.prefetch_hits == 0
    assert kv.migrations == 1
    assert not kv._prefetched
    assert all(kv._pages[p].tier == "gpu" for p in kv.tracked(d).pages)


def test_hit_frequency_eviction_prefers_cold_pages():
    def build(prefetch):
        kv = PagedKVCache(paged_perf(caps={"gpu": 8.0}), page_tokens=4,
                          prefetch=prefetch)
        [a] = warm_pages(kv, "ctx:hot", 4, nid="w0/p")
        hot = prefill_node("h/p", [("ctx:hot", 4), ("q:h", 4)])
        kv.apply_prefix_hits(hot)             # the hot page earns hits
        kv.on_prefill_done(hot, "gpu")
        [b] = warm_pages(kv, "ctx:cold", 4, nid="w1/p")
        assert kv._pages[a].hits > 0 and kv._pages[b].hits == 0
        assert kv._pages[a].last_use < kv._pages[b].last_use
        d = decode_node("q0/d", ctx=4, workload=1 << 20)
        kv.migrate_for_dispatch(round_node([d]), "gpu")  # needs 4 B
        return kv, a, b

    # prefetch on: the cold page demotes even though it is more recent
    kv, a, b = build(True)
    assert kv._pages[b].tier == DRAM and kv._pages[a].tier == "gpu"
    # prefetch off: plain LRU (the PR 6 behaviour) demotes the older page
    kv, a, b = build(False)
    assert kv._pages[a].tier == DRAM and kv._pages[b].tier == "gpu"


# --- bugfix regressions ------------------------------------------------------

def recompute_perf():
    """Profile where re-prefilling is cheap and disk fetches are ruinous:
    a handcrafted prefill grid (table-first, so the exact queried token
    counts must be present) plus 1 s/token disk fetch lines."""
    m = paged_perf()
    for p in ("cpu", "gpu", "npu"):
        m.fetch_coef[(STAGE, DISK, p)] = (0.0, 1.0)
    m.table[("chat_prefill", "gpu")] = {64: (0.01, 0.0), 128: (0.02, 0.0)}
    m.coef[("chat_prefill", "gpu")] = None    # key presence only
    return m


def test_hit_or_recompute_declines_fetch_dominated_hits():
    """Bugfix: a disk-resident 'hit' whose fetch costs more than the
    prefill it skips is declined, not blindly taken."""
    kv = PagedKVCache(recompute_perf(), page_tokens=64)
    segs = [("ctx:a", 128)]
    pids = warm_pages(kv, "ctx:a", 128)
    for pid in pids:
        kv._place(kv._pages[pid], DISK)
    n = prefill_node("q1/p", segs)
    kv.apply_prefix_hits(n)
    assert n.workload == 128                  # nothing trimmed
    assert "kv_page_hits" not in n.payload
    assert n.payload["kv_hit_declined"] == 2
    assert kv.hit_declined == 2 and kv.hits == 0
    assert "kv_hit_declined" in [e for e, _n in kv.drain_events()]
    assert all(kv._pages[pid].refs == 0 for pid in pids)  # not pinned


def test_hit_or_recompute_keeps_the_profitable_prefix():
    # page 0 stays PU-resident (free to hit); page 1 is on disk and
    # costs 64 s to fetch vs 0.02 s to recompute — keep 1, decline 1
    kv = PagedKVCache(recompute_perf(), page_tokens=64)
    pids = warm_pages(kv, "ctx:a", 128)
    kv._place(kv._pages[pids[1]], DISK)
    n = prefill_node("q1/p", [("ctx:a", 128)])
    kv.apply_prefix_hits(n)
    assert n.payload["kv_page_hits"] == 1
    assert n.payload["kv_hit_tokens"] == 64
    assert n.workload == 64
    assert n.payload["kv_hit_declined"] == 1
    assert kv.hits == 1 and kv.hit_declined == 1


def test_soft_overflow_counted_and_recovered_on_release():
    """Bugfix: an all-pinned arena breach is counted and emitted (not
    silent), and release demotes the excess so every tier returns under
    capacity once the pins drop."""
    kv = PagedKVCache(paged_perf(caps={"gpu": 8.0}), page_tokens=4)
    p = prefill_node("q0/p", [("ctx:a", 16)], stream="q0/d")
    kv.on_prefill_done(p, "gpu")              # 16 B pinned into an 8 B arena
    assert kv.resident_bytes("gpu") == 16.0
    assert kv.soft_overflows == 2             # pages 3 and 4 each breached
    assert kv.evictions == 0                  # the stream was never touched
    events = [e for e, _n in kv.drain_events()]
    assert events.count("kv_soft_overflow") == 2
    d = decode_node("q0/d", ctx=16)
    d.group = "q0/d"
    kv.release(d)
    # hashed pages survive at refs == 0, but the overflow excess demotes
    assert kv.resident_bytes("gpu") <= 8.0
    assert kv.resident_bytes(DRAM) == 8.0
    assert kv.evictions == 2
    assert "kv_evict" in [e for e, _n in kv.drain_events()]
    check_invariants(kv)


def test_kv_stage_override_and_convention_trap_warns_once():
    """Bugfix: stages that do not follow the *_prefill naming convention
    are warned-and-skipped (once per stage pair) instead of silently
    paged under a guessed decode shape; the explicit override re-enables
    reuse under the right profile."""
    n = decode_node("q0/d", ctx=0)
    assert decode_stage_for(n) == STAGE
    n.payload["kv_decode_stage"] = "other_decode"
    assert decode_stage_for(n) == "other_decode"

    kv = PagedKVCache(paged_perf(), page_tokens=64)

    def odd(nid, **extra):
        return Node(nid, "oddgen", "stream_prefill", 64,
                    payload={"prefix_segments": (("ctx:a", 64),), **extra})

    with pytest.warns(RuntimeWarning, match="kv_stage"):
        kv.apply_prefix_hits(odd("q0/g"))
    with warnings.catch_warnings():           # warn once, then silent
        warnings.simplefilter("error")
        kv.apply_prefix_hits(odd("q1/g"))
    # the override pages the cache under the profiled decode shape
    warm = odd("q2/g", kv_decode_stage=STAGE)
    kv.apply_prefix_hits(warm)                # cold
    kv.on_prefill_done(warm, "gpu")
    again = odd("q3/g", kv_decode_stage=STAGE)
    kv.apply_prefix_hits(again)
    assert again.payload["kv_page_hits"] == 1
    assert again.workload == 1


def test_spec_level_kv_stage_trap_and_override():
    trace = {"context_tokens": 64, "chunk_ids": (1, 2)}

    def mk(kv_stage):
        return WorkflowSpec("odd", statics=(
            StageSpec("gen_ctx", "oddgen", "stream_prefill",
                      lambda v: v.context_tokens,
                      shared_ctx=lambda v: v.context_tokens,
                      decode=(DecodeSpec(kv_stage=kv_stage)
                              if kv_stage else None)),
            StageSpec("gen", "oddgen_d", "stream_decode", lambda v: 8,
                      deps=("gen_ctx",)),
        ))

    with pytest.warns(RuntimeWarning, match="kv_stage"):
        dag = mk(None).build_dag(trace)
    assert "prefix_segments" not in dag.nodes["gen_ctx"].payload
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        dag = mk(STAGE).build_dag(trace)
    n = dag.nodes["gen_ctx"]
    assert n.payload["decode_spec"].kv_stage == STAGE
    assert decode_stage_for(n) == STAGE
    assert sum(t for _k, t in n.payload["prefix_segments"]) == n.workload


def test_stagespec_kv_stage_kwarg_deprecated_shim():
    """PR 9 shim: the legacy ``StageSpec(kv_stage=...)`` kwarg warns and
    folds into the typed ``decode=DecodeSpec(...)``; a conflicting pair
    still raises."""
    with pytest.warns(DeprecationWarning, match="kv_stage is deprecated"):
        s = StageSpec("gen_ctx", "oddgen", "stream_prefill",
                      lambda v: 64, kv_stage=STAGE)
    assert s.decode == DecodeSpec(kv_stage=STAGE)
    with pytest.warns(DeprecationWarning):
        s2 = StageSpec("gen_ctx", "oddgen", "stream_prefill",
                       lambda v: 64, kv_stage=STAGE,
                       decode=DecodeSpec(draft_width=2))
    assert s2.decode.kv_stage == STAGE
    assert s2.decode.draft_width == 2
    with pytest.warns(DeprecationWarning):
        with pytest.raises(ValueError, match="conflicts"):
            StageSpec("gen_ctx", "oddgen", "stream_prefill",
                      lambda v: 64, kv_stage=STAGE,
                      decode=DecodeSpec(kv_stage="other_decode"))


# --- gates + backend accounting protocol -------------------------------------

def test_scheduler_prefetch_gate_requires_pages():
    perf = paged_perf()
    on = HeroScheduler(perf, ["cpu", "gpu", "npu"], 1e9,
                       SchedulerConfig(kv_pages=True, kv_prefetch=True))
    assert on.kv.prefetch_on
    off = HeroScheduler(perf, ["cpu", "gpu", "npu"], 1e9,
                        SchedulerConfig(kv_pages=True))
    assert not off.kv.prefetch_on             # off = the PR 6 behaviour


def test_prefetch_off_counters_stay_zero_e2e():
    traces = shared_corpus_traces("hotpotqa", 4, seed=5)
    sess = HeroSession(world="sd8gen4", family="qwen3", strategy="hero",
                       coalesce=True, batch_policy="adaptive", kv_pages=True)
    for qi, tr in enumerate(traces):
        sess.submit(tr, wf=1, arrival_time=qi * 0.5)
    res = sess.run()
    run = sess.last_run
    assert run.kv_prefetches == 0 and run.kv_prefetch_hits == 0
    assert run.kv_prefetch_bytes == 0.0
    assert all(r.kv_prefetches == 0 for r in res)


@pytest.mark.parametrize("backend", ["sim", "live"])
def test_prefetch_counter_protocol_parity(backend):
    """Both backends drain the same prefetch queue (the simulator charges
    the overlap residual, the live runtime records) and surface the same
    counter protocol: run totals come from the shared tracker and the
    per-query payload attribution sums back to them exactly."""
    traces = shared_corpus_traces("hotpotqa", 3, seed=3)
    sess = HeroSession(world="sd8gen4", family="qwen3", strategy="hero",
                       coalesce=True, batch_policy="adaptive",
                       kv_pages=True, kv_prefetch=True, backend=backend)
    for qi, tr in enumerate(traces):
        sess.submit(tr, wf=1, arrival_time=qi * 0.5)
    res = sess.run(timeout=120)
    run = sess.last_run
    assert len(res) == 3 and all(r.makespan > 0 for r in res)
    assert run.kv_prefetches == sum(r.kv_prefetches for r in res)
    assert run.kv_prefetch_hits == sum(r.kv_prefetch_hits for r in res)
    assert run.kv_prefetch_bytes == pytest.approx(
        sum(r.kv_prefetch_bytes for r in res))
    assert run.kv_hit_declined == sum(r.kv_hit_declined for r in res)
    assert run.kv_page_hits > 0               # the shared corpus still hits


# --- hypothesis: bytes conservation ------------------------------------------

def test_prefetch_bytes_conservation_single_pu():
    """On a single-PU tier stack every spill->PU byte crossing is either
    a prefetch staging or an on-path fetch (no PU->PU moves exist), so
    ``prefetch_bytes + fetched_bytes`` must equal the bytes observed
    moving up — and speculation never soft-overflows the arena."""
    hyp = pytest.importorskip("hypothesis")
    st_ = pytest.importorskip("hypothesis.strategies")

    # staging a prefetch group used to evict the pages staged just before
    # it, so the staged bytes outran the bytes that reached the PU
    @hyp.example([(0, 0, 0.0), (0, 0, 0.0), (0, 3, 0.0), (2, 0, 0.0),
                  (3, 1, 0.0), (1, 0, 1.0)])
    @hyp.given(st_.lists(st_.tuples(st_.integers(0, 3),     # op selector
                                    st_.integers(0, 7),     # page pick
                                    st_.floats(0.0, 1.0)),  # budget
                         min_size=1, max_size=40))
    @hyp.settings(max_examples=40, deadline=None)
    def prop(ops):
        kv = PagedKVCache(paged_perf(caps={"gpu": 48.0, "dram": 64.0},
                                     pus=("gpu",)),
                          page_tokens=8, prefetch=True)
        for i in range(3):
            warm_pages(kv, f"ctx:{i}", 16, nid=f"w{i}/p")
        d = decode_node("q0/d", ctx=16, workload=1 << 20)
        d.group = "q0/d"
        up = 0.0
        shadow = {pid: pg.tier for pid, pg in kv._pages.items()}

        def sync():
            nonlocal up
            for pid, pg in kv._pages.items():
                if shadow.get(pid) in (DRAM, DISK) and pg.tier == "gpu":
                    up += kv._page_bytes(pg)
            shadow.clear()
            shadow.update({pid: pg.tier for pid, pg in kv._pages.items()})

        for op, pick, budget in ops:
            if op == 0:        # demotion pressure (unpinned pages only)
                pids = sorted(pid for pid, pg in kv._pages.items()
                              if pg.refs <= 0 and pg.tier == "gpu")
                if pids:
                    kv._place(kv._pages[pids[pick % len(pids)]],
                              (DRAM, DISK)[pick % 2])
            elif op == 1:      # speculative staging
                before = kv.soft_overflows
                kv.prefetch(d, "gpu", budget, pids=sorted(kv._pages))
                assert kv.soft_overflows == before
            elif op == 2:      # on-path gather
                kv.migrate_for_dispatch(round_node([d]), "gpu")
            else:              # prefix reuse of a warmed corpus
                n = prefill_node(f"h{pick}/p", [(f"ctx:{pick % 3}", 16)])
                kv.apply_prefix_hits(n)
                kv.on_prefill_done(n, "gpu")
            sync()
            check_invariants(kv)
            assert kv.prefetch_bytes + kv.fetched_bytes \
                == pytest.approx(up)
        kv.release(d)
        check_invariants(kv)

    prop()
