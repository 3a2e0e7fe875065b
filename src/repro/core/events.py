"""Central registry of timeline-event names.

Both execution substrates (``core/simulator.py`` and
``serving/executor.py``) record the run as ``(t, event, node_id)``
triples, and a long tail of consumers — ``BackendRun`` counter
derivation, per-query attribution in ``api/results.py``, the session's
streaming observer, benchmark metrics — dispatch on the *string value*
of ``event``.  A typo'd emit therefore fails silently: the event lands
on the timeline, every ``e[1] == "..."`` filter misses it, and a
counter quietly under-reports (exactly the bug class the soft-overflow
accounting leak in PR 7 was).

This module is the single source of truth.  Emit sites and comparison
sites use the ``EV_*`` constants; ``repro.analysis.lint`` rejects raw
event-string literals in the event-handling modules, and
``repro.analysis.tracecheck`` rejects recorded events whose name is not
in :data:`ALL_EVENTS`.

The constant *values* are the historical strings, so recorded
timelines, goldens, and bench baselines are bit-identical across the
migration.

The live runtime's span recorder (``repro.serving.spans``) names its
spans with the ``SP_*`` constants and its counters with the ``CT_*``
constants below, under the same lint discipline.
"""
from __future__ import annotations

# -- node lifecycle ----------------------------------------------------------
EV_START = "start"            # dispatch began on a PU
EV_DONE = "done"              # node (or fused dispatch) completed
EV_TOKENS = "tokens"          # resident decode-round member advanced one
#                               token group at a boundary without finishing
EV_CANCELLED = "cancelled"    # user-requested cancel finalized the node

# -- re-serve (the first attempt did not complete) ---------------------------
EV_REDISPATCH = "redispatch"  # simulator: speculative straggler re-dispatch
EV_STRAGGLER = "straggler"    # live runtime: heartbeat-detected straggler
EV_RETRY = "retry"            # live runtime: stage fn raised; retrying
EV_PREEMPT = "preempt"        # member released from a preempted fused
#                               dispatch at a boundary split (returns READY)

# -- KV-cache subsystem ------------------------------------------------------
EV_KV_MIGRATE = "kv_migrate"            # resident cache moved PU -> PU
EV_KV_FETCH = "kv_fetch"                # cache gathered from a spill tier
EV_KV_PAGE_HIT = "kv_page_hit"          # prefix-cache hit on a prefill
EV_KV_HIT_DECLINED = "kv_hit_declined"  # hit-or-recompute rule declined
EV_KV_EVICT = "kv_evict"                # page demoted/dropped for room
EV_KV_PREFETCH = "kv_prefetch"          # pages staged ahead of a dispatch
EV_KV_SOFT_OVERFLOW = "kv_soft_overflow"  # all-pinned capacity breach

ALL_EVENTS = frozenset({
    EV_START, EV_DONE, EV_TOKENS, EV_CANCELLED,
    EV_REDISPATCH, EV_STRAGGLER, EV_RETRY, EV_PREEMPT,
    EV_KV_MIGRATE, EV_KV_FETCH, EV_KV_PAGE_HIT, EV_KV_HIT_DECLINED,
    EV_KV_EVICT, EV_KV_PREFETCH, EV_KV_SOFT_OVERFLOW,
})

# the three "this dispatch did not complete; a re-serve follows" events —
# BackendRun.redispatches and QueryResult.redispatches count exactly these
REDISPATCH_EVENTS = (EV_REDISPATCH, EV_STRAGGLER, EV_RETRY)

# spill tiers of the paged KV store ("dram"/"disk", vs. PU-name tiers);
# a gather sourced from one of these is a fetch, not a migration
SPILL_TIERS = ("dram", "disk")

# -- spans of the live runtime (repro.serving.spans) --------------------------
SP_SESSION_BUILD = "session.build"        # DAG assembly + scheduler build
SP_RUNTIME_RUN = "runtime.run"            # t0 = the run's timeline epoch
SP_RUNTIME_DISPATCH_PASS = "runtime.dispatch_pass"  # reap + schedule + launch
SP_RUNTIME_HARVEST = "runtime.harvest"    # one finished task handled
SP_RUNTIME_POLL = "runtime.poll"          # the loop's sleep
SP_RUNTIME_READY_WAIT = "runtime.ready_wait"  # first seen ready -> launch
SP_EXECUTOR_QUEUE = "executor.queue"      # submit -> worker start
SP_EXECUTOR_RUN = "executor.run"          # a stage fn on its PU's worker
SP_LM_CALL = "lm.call"                    # one width-CALL_WIDTH agent call
SP_LM_STEP = "lm.step"                    # the call's device work: the
#                                           prefill and every decode step
#                                           enqueued, then the wait
SP_LM_FETCH = "lm.fetch"                  # device -> host of the tokens
SP_VDB_SEARCH = "vdb.search"
SP_VDB_KERNEL = "vdb.kernel"              # the top-k program's enqueue
SP_VDB_FETCH = "vdb.fetch"                # device -> host of the results
SP_VDB_IDS = "vdb.ids"                    # row -> id mapping on the host
SP_VDB_ADD = "vdb.add"
SP_EMBED_CALL = "embed.call"
SP_RERANK_CALL = "rerank.call"

ALL_SPANS = frozenset({
    SP_SESSION_BUILD, SP_RUNTIME_RUN, SP_RUNTIME_DISPATCH_PASS,
    SP_RUNTIME_HARVEST, SP_RUNTIME_POLL, SP_RUNTIME_READY_WAIT,
    SP_EXECUTOR_QUEUE, SP_EXECUTOR_RUN, SP_LM_CALL, SP_LM_STEP,
    SP_LM_FETCH, SP_VDB_SEARCH, SP_VDB_KERNEL, SP_VDB_FETCH,
    SP_VDB_IDS, SP_VDB_ADD, SP_EMBED_CALL, SP_RERANK_CALL,
})

# -- counters of the live runtime ---------------------------------------------
CT_RUNTIME_PASSES = "runtime.passes"
CT_EXECUTOR_CANCELLED_RUNS = "executor.cancelled_runs"  # ran to the end
#                                                         after a cancel
CT_LM_STEPS = "lm.steps"                  # decode steps whose tokens
#                                           the call returns
CT_LM_FETCHES = "lm.fetches"              # device -> host syncs per call
CT_LM_REAL_TOKENS = "lm.real_tokens"      # tokens returned to real rows
CT_LM_PAD_ROWS = "lm.pad_rows"            # padding rows per call
CT_LM_KV_BYTES_RESERVED = "lm.kv_bytes_reserved"  # the call's cache
CT_LM_KV_BYTES_USED = "lm.kv_bytes_used"  # positions the call wrote
# what a MoE generator's routers did in the decode steps, real rows only
CT_MOE_ROUTED = "moe.routed"              # assignments over all experts
CT_MOE_ROUTED_HELD = "moe.routed_held"    # of those, to held experts
CT_MOE_EXPERTS_HIT = "moe.experts_hit"    # held experts with an
#                                           assignment, per layer-step
CT_MOE_EXPERTS_READ = "moe.experts_read"  # held experts whose weights
#                                           the step read, all rows
CT_MOE_LAYER_STEPS = "moe.layer_steps"    # MoE layers x decode steps
CT_MOE_DROPPED = "moe.dropped"            # held assignments not computed
#                                           (0: the layer is dropless)

ALL_COUNTERS = frozenset({
    CT_RUNTIME_PASSES, CT_EXECUTOR_CANCELLED_RUNS, CT_LM_STEPS,
    CT_LM_FETCHES, CT_LM_REAL_TOKENS, CT_LM_PAD_ROWS,
    CT_LM_KV_BYTES_RESERVED, CT_LM_KV_BYTES_USED, CT_MOE_ROUTED,
    CT_MOE_ROUTED_HELD, CT_MOE_EXPERTS_HIT, CT_MOE_EXPERTS_READ,
    CT_MOE_LAYER_STEPS, CT_MOE_DROPPED,
})
