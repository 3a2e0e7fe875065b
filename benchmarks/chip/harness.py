"""One run of one cell: set-up, the measured window, the metric readers,
and the comparison with the reference.

Everything that belongs to a configuration, a traffic mix or a metric is
found by name: ``configs/<config>.json``, ``traffic/<traffic>.json`` and
``metrics/<metric>.py``, as ``BENCHMARK.json`` names them; a model entry
of a configuration names the module of its plain reference
(``reference_module``).  This module drives the program only through its
entry points (``build_pipeline``, ``stage_fns``, ``HeroSession``), records
spans and model calls by wrapping what those return, and in a traced run
turns on the program's own span recorder (``repro.serving.spans``).
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib
import importlib.util
import json
import shutil
import sys
import tempfile
import threading
import time
from pathlib import Path
from types import SimpleNamespace
from typing import Callable, Dict, List, Optional

import numpy as np

import checks
import traffic
import peaks
import span_reduce
import trace_reduce

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parents[1]
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
TRACE_SECONDS = 10.0
# keys of a model entry that say how the benchmark uses the model, not
# what the program builds; every other key is a width checked at set-up
MODEL_META = frozenset({"source", "role_index", "init", "params",
                        "reference"})


# -- the cell, as BENCHMARK.json and its files describe it ------------------

@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    mix: dict
    metrics: List[dict]          # end-to-end (trace 0) metric entries
    layer_metrics: List[dict]    # per-layer (trace 1) metric entries


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    config = json.loads((BENCH / "configs" / f"{w['config']}.json")
                        .read_text())
    mix = json.loads((BENCH / "traffic" / f"{w['traffic']}.json")
                     .read_text())
    return Cell(name, w["chips"], config, mix,
                [m for m in bench["end_to_end"] if _applies(m, name)],
                [m for m in bench["per_layer"] if _applies(m, name)])


def reader(metric: str) -> Callable:
    """The ``read(ctx)`` function of ``metrics/<metric>.py``."""
    path = BENCH / "metrics" / f"{metric}.py"
    if str(path.parent) not in sys.path:
        sys.path.append(str(path.parent))
    spec = importlib.util.spec_from_file_location(
        f"chipbench_metric_{metric.replace('.', '_').replace('-', '_')}",
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def reference_module(m: dict):
    """The module of model entry ``m``'s plain reference: the module of
    this directory that its ``reference`` key names (dotted below a
    subdirectory), ``reference.py`` where it names none.  It exports
    ``Reference(m, seed, quant=None)`` with ``.logits(seqs)`` (and
    ``.embed``/``.rerank`` for those roles), and may export
    ``decode_flops(m, tokens)``."""
    mod = importlib.import_module(m.get("reference", "reference"))
    if not Path(mod.__file__).resolve().is_relative_to(BENCH):
        raise ValueError(f"reference {m['reference']!r} is not a module "
                         f"of {BENCH}")
    return mod


def make_reference(m: dict, seed: int, quant: Optional[str] = None):
    """Model entry ``m``'s plain reference, its weights drawn from
    ``seed``."""
    return reference_module(m).Reference(m, seed, quant)


def check_widths(role: str, stated: dict, built, prefix: str = ""):
    """Stop set-up where the program builds another model than the entry
    ``stated`` describes: each key that names a field of the built config
    is compared, a dict against a sub-config (``moe``, ``mla``, ...) key
    by key, and a key that is neither such a field nor ``MODEL_META``
    stops set-up with its name, so a misspelt width cannot pass."""
    fields = {f.name for f in dataclasses.fields(built)}
    for key, want in stated.items():
        name = prefix + key
        if not prefix and key in MODEL_META:
            continue
        if key not in fields:
            raise RuntimeError(f"{role}: the configuration states {name}, "
                               f"which is no field of the program's "
                               f"{type(built).__name__} and no benchmark "
                               f"metadata")
        got = getattr(built, key)
        if dataclasses.is_dataclass(got) and isinstance(want, dict):
            check_widths(role, want, got, name + ".")
        elif (list(got) if isinstance(got, tuple) else got) != want:
            raise RuntimeError(f"{role}: the program builds {name}="
                               f"{got!r}, the configuration states "
                               f"{want!r}")


# -- recording ---------------------------------------------------------------

@dataclasses.dataclass
class Span:
    """One stage-fn call on the host clock (time.monotonic)."""
    stage: str
    t0: float
    t1: float
    qids: List[int]
    width: int                   # real members (1 for an unfused node)
    tokens: int                  # tokens returned for real members
    n_valid: Optional[int]       # store rows at a vector search


@dataclasses.dataclass
class Query:
    qid: int
    want: int                    # answer tokens the trace asks for
    due: float = 0.0
    first: Optional[float] = None
    last: Optional[float] = None
    tokens: int = 0

    @property
    def answered(self) -> bool:
        return self.last is not None and self.tokens >= self.want


class Recorder:
    """Spans of stage-fn calls and the inputs and outputs of model calls,
    kept in memory for the window."""

    def __init__(self):
        self.lock = threading.Lock()
        self.current = 0         # query id of an unprefixed node
        self.adds: List[np.ndarray] = []
        self.clear()

    def clear(self):
        self.spans: List[Span] = []
        self.lm: Dict[str, list] = {}
        self.embed: list = []
        self.rerank: list = []
        self.search: list = []
        self.missing = 0

    def qid(self, node_id: str) -> int:
        head, sep, _ = node_id.partition("/")
        if sep and head[:1] == "q" and head[1:].isdigit():
            return int(head[1:])
        return self.current


def instrument(pipe, rec: Recorder, roles: Dict[str, str]):
    """Record every model call the stage fns make, at the pipeline's
    public model boundaries.  ``roles``: generating role -> Pipeline
    attribute of its agent."""
    embed = pipe.embedder.embed

    def embed_rec(token_lists):
        out = embed(token_lists)
        rec.embed.append((list(token_lists), out))
        return out

    score = pipe.reranker.score

    def score_rec(query_ids, chunk_ids_list):
        out = score(query_ids, chunk_ids_list)
        rec.rerank.append((list(query_ids), list(chunk_ids_list), out))
        return out

    db = pipe.db
    search, add = db.search, db.add

    def search_rec(queries, k, use_pallas=None):
        n0 = len(db)
        vals, ids = (search(queries, k) if use_pallas is None
                     else search(queries, k, use_pallas=use_pallas))
        rec.search.append((np.asarray(queries), n0, len(db), vals, ids))
        return vals, ids

    def add_rec(vectors, ids=None):
        v = np.asarray(vectors, np.float32)
        add(v, ids)
        rec.adds.append(v)

    pipe.embedder.embed, pipe.reranker.score = embed_rec, score_rec
    db.search, db.add = search_rec, add_rec

    for role, attr in roles.items():
        agent = getattr(pipe, attr)
        gen, gen_batch = agent.generate, agent.generate_batch

        def gen_rec(prompt_ids, max_new=32, stop_at_eos=True,
                    _gen=gen, _role=role):
            out = _gen(prompt_ids, max_new, stop_at_eos)
            rec.lm.setdefault(_role, []).append(
                (list(prompt_ids), list(out.token_ids)))
            return out

        def gen_batch_rec(prompts, max_new=32, _gen=gen_batch, _role=role):
            out = _gen(prompts, max_new)
            # generate_batch left-crops every prompt to the shortest
            n = min(len(p) for p in prompts)
            calls = rec.lm.setdefault(_role, [])
            calls.extend((list(p)[-n:], list(g.token_ids))
                         for p, g in zip(prompts, out))
            return out

        agent.generate, agent.generate_batch = gen_rec, gen_batch_rec


def wrap_stage_fns(fns: dict, rec: Recorder, db, annotate: bool) -> dict:
    import jax

    def wrap(stage, fn):
        def run(node, batch):
            members = node.payload.get("members") or ()
            ids = [m.id for m in members] or [node.id]
            n_valid = len(db) if stage == "vsearch" else None
            ctx = (jax.profiler.TraceAnnotation(f"stage:{stage}")
                   if annotate else contextlib.nullcontext())
            t0 = time.monotonic()
            with ctx:
                out = fn(node, batch)
            t1 = time.monotonic()
            tokens = missing = 0
            if stage.endswith("_decode"):
                got = ([out.get(i) for i in ids] if isinstance(out, dict)
                       else [out])
                tokens = sum(len(g or ()) for g in got)
                missing = sum(1 for g in got if not g)
            with rec.lock:
                rec.missing += missing
                rec.spans.append(Span(stage, t0, t1,
                                      sorted({rec.qid(i) for i in ids}),
                                      len(members) or 1, tokens, n_valid))
            return out
        return run

    return {s: (f if s == "__io__" else wrap(s, f)) for s, f in fns.items()}


class Tracer:
    """Profiler trace of a few seconds in the middle of the window,
    started and stopped from timer threads.  ``finish`` keeps the device
    op events (``devices``) and, where ``hero``, the program's ``hero:``
    span annotations (``hero``) for the span readers."""

    def __init__(self, seconds: float, hero: bool):
        self.length = min(TRACE_SECONDS, max(1.0, 0.4 * seconds))
        self.offset = max(0.0, (seconds - self.length) / 2)
        self.lock = threading.Lock()
        self.dir: Optional[str] = None
        self.t0 = self.t1 = None
        self.timers: List[threading.Timer] = []
        self.want_hero = hero
        self.devices: Dict[str, list] = {}
        self.hero: List[list] = []

    def arm(self):
        self.timers = [threading.Timer(self.offset, self.start),
                       threading.Timer(self.offset + self.length, self.stop)]
        for t in self.timers:
            t.start()

    def start(self):
        import jax
        with self.lock:
            if self.dir is None:
                self.dir = tempfile.mkdtemp(prefix="chipbench-trace-")
                jax.profiler.start_trace(self.dir)
                self.t0 = time.monotonic()

    def stop(self):
        import jax
        with self.lock:
            if self.dir is not None and self.t1 is None:
                self.t1 = time.monotonic()
                jax.profiler.stop_trace()

    def finish(self) -> Optional[dict]:
        for t in self.timers:
            t.cancel()
        for t in self.timers:
            t.join()
        self.stop()
        if self.dir is None:
            return None
        try:
            files = sorted(Path(self.dir).rglob("*.xplane.pb"))
            if not files:
                return None
            events = trace_reduce.load(str(files[-1]))
            self.devices = events["devices"]
            if self.want_hero:
                self.hero = span_reduce.load_hero(str(files[-1]))
            for line in trace_reduce.summary(events):
                print(line, file=sys.stderr)
            return trace_reduce.reduce(events, self.t1 - self.t0)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


# -- the run -----------------------------------------------------------------

def _query_trace(q: dict):
    from repro.rag import QueryTrace
    return QueryTrace(**q)


def fill_store(db, n_rows: int, seed: int, chunk: int = 32) -> np.ndarray:
    """Seeded unit vectors into the store through its public ``add``, one
    write program at a time (a store write copies the whole store, so
    queued writes would each hold a copy)."""
    import jax

    rng = np.random.default_rng([seed, 2])
    filler = rng.standard_normal((n_rows, db.dim), dtype=np.float32)
    filler /= np.linalg.norm(filler, axis=1, keepdims=True)
    for s in range(0, n_rows, chunk):
        db.add(filler[s:s + chunk])
        jax.block_until_ready(db._vecs)
    return filler


def run(cell: Cell, seed: int, seconds: float, trace: bool, *,
        t_start: float, device, pipe_hook: Optional[Callable] = None,
        keep: Optional[dict] = None,
        record_spans: Optional[bool] = None) -> dict:
    """Set-up, window, readers and comparison; -> the result line.
    ``pipe_hook(pipe)`` runs on the built pipeline before the stage fns
    are made (tests break the timed path there); ``keep``, if given,
    receives what the comparison read (the control re-reads it) and the
    readers' ``ctx``.  The program's span recorder is on, annotating the
    profiler trace, from the warm-up to the end of the window where
    ``record_spans`` (by default: where ``trace``), and off otherwise;
    ``spanrun.py`` sets it apart from ``trace`` to measure its cost."""
    import jax
    from jax import monitoring

    from repro.api import HeroSession, SessionOptions
    from repro.launch import serve
    from repro.launch.compile_cache import enable_compile_cache
    from repro.rag import default_means
    from repro.serving import spans

    cfg, mix = cell.config, cell.mix
    record = trace if record_spans is None else record_spans
    compiles = [0]

    def on_event(event, secs, **kw):
        if event == COMPILE_EVENT:
            compiles[0] += 1

    monitoring.register_event_duration_secs_listener(on_event)

    # -- set-up ---------------------------------------------------------------
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    queries, arrivals = traffic.schedule(mix, seconds)
    warm = traffic.warmup_queries(mix)
    pipe = serve.build_pipeline(seed=seed, **cfg["build"])
    jax.block_until_ready(pipe.models)
    for role, m in cfg["models"].items():
        check_widths(role, m, pipe.models[role][0])
    headroom = traffic.chunk_rows(queries + warm)
    n0 = len(pipe.db)
    n_fill = pipe.db.capacity - n0 - headroom
    if n_fill <= 0:
        raise RuntimeError(f"the traffic may add {headroom} rows, more than "
                           f"the store's {pipe.db.capacity}")
    filler = fill_store(pipe.db, n_fill, seed)
    if pipe_hook is not None:
        pipe_hook(pipe)
    if record:
        spans.enable(annotate=True)
    else:
        spans.disable()
    spans.clear()
    rec = Recorder()
    instrument(pipe, rec, cfg["generating_roles"])
    fns = wrap_stage_fns(serve.stage_fns(pipe), rec, pipe.db, trace)
    means = default_means([_query_trace(q) for q in queries + warm])
    sess = HeroSession(world=cfg["world"], family=cfg["family"],
                       backend="live", means=means,
                       options=SessionOptions(**cfg["session"]),
                       stage_fns=fns)
    wf, mode = mix["workflow"], mix["mode"]
    timeout = seconds + 240.0
    for q in warm:
        sess.submit(_query_trace(q), wf=wf)
    sess.run(mode=mode, timeout=timeout)
    jax.block_until_ready(pipe.models)
    setup_s = time.monotonic() - t_start
    rec.clear()
    spans.clear()
    # the store's element size as the search program takes it
    store_itemsize = jax.tree.leaves(
        pipe.db.lowered_search(1, 1).args_info)[1].dtype.itemsize

    # -- window ---------------------------------------------------------------
    recs = [Query(i, q["answer_tokens"]) for i, q in enumerate(queries)]

    def on_token(r: Query):
        def cb(h, tokens, t):
            now = time.monotonic()
            if r.first is None:
                r.first = now
            r.last = now
            r.tokens += int(tokens)
        return cb

    tracer = Tracer(seconds, record) if trace else None
    events: list = []
    c0 = compiles[0]
    t0 = time.monotonic()
    if mix["loop"] == "open":
        for r, q, a in zip(recs, queries, arrivals):
            sess.submit(_query_trace(q), wf=wf, arrival_time=a,
                        on_token=on_token(r))
        if tracer:
            tracer.arm()
        t0 = time.monotonic()
        for r, a in zip(recs, arrivals):
            r.due = t0 + a
        sess.run(mode=mode, timeout=timeout)
        events += sess.last_run.events
        due = recs
    else:
        if tracer:
            tracer.arm()
        t_end = t0 + seconds
        due = []
        for r, q in zip(recs, queries):
            if time.monotonic() >= t_end:
                break
            rec.current = r.qid
            sess.submit(_query_trace(q), wf=wf, on_token=on_token(r))
            r.due = time.monotonic()
            sess.run(mode=mode, timeout=timeout)
            events += sess.last_run.events
            due.append(r)
        else:
            raise RuntimeError(f"the closed loop ran out of its "
                               f"{len(queries)} queries inside the window")
    jax.block_until_ready(pipe.models)
    t1 = time.monotonic()
    recorded, counters = spans.drain()
    spans.disable()
    window_compiles = compiles[0] - c0
    reduced = tracer.finish() if tracer else None
    stats = device.memory_stats() or {}

    from repro.core.events import EV_RETRY, EV_STRAGGLER
    ctx = SimpleNamespace(
        setup_s=setup_s, queries=due, spans=rec.spans,
        redispatches=sum(1 for e in events
                         if e[1] in (EV_STRAGGLER, EV_RETRY)),
        window_compiles=window_compiles,
        trace=reduced, trace_window=(tracer.t0, tracer.t1) if tracer
        else None, window=(t0, t1),
        # the program's own spans and counters; None with the recorder off
        program_spans=recorded if record else None,
        counters=counters if record else None,
        devices=tracer.devices if tracer else {},
        hero=tracer.hero if tracer else [],
        clock=(span_reduce.clock(tracer.hero, recorded) if tracer
               else None),
        config=cfg, store_dim=pipe.db.dim,
        store_itemsize=store_itemsize,
        # no peak, and so no share of one, off the chip (CPU rehearsals)
        peaks=(peaks.peaks(device.device_kind) if device.platform == "tpu"
               else None))
    metric_entries = cell.layer_metrics if trace else cell.metrics
    metrics = {}
    for m in metric_entries:
        v = reader(m["name"])(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}

    # -- the comparison, with the program's state freed -----------------------
    store = np.asarray(pipe.db._vecs[:len(pipe.db)])
    del sess, fns, pipe
    gc.collect()
    numbers, parts = compare(cfg, seed, rec, store, n0, filler)
    for name, v in parts.items():
        print(f"part {name}: {v!r}", file=sys.stderr)
    if keep is not None:
        keep.update(rec=rec, store=store, queries=due, window=(t0, t1),
                    parts=parts, ctx=ctx)
    numbers["unanswered"] = sum(1 for r in due if not r.answered)
    numbers["missing_outputs"] = rec.missing
    correct, table = checks.judge(numbers, cfg["limits"])

    out = {"correct": correct, "attempted": len(due),
           "failed": numbers["unanswered"], "metrics": metrics,
           "device": {"platform": device.platform,
                      "kind": device.device_kind,
                      "count": len(jax.devices()),
                      "memory_peak_bytes": stats.get("peak_bytes_in_use")}}
    if reduced is not None:
        out["device"]["busy_s"] = reduced["busy_s"]
        out["device"]["window_s"] = reduced["window_s"]
        out["breakdown"] = {"device_ops": reduced["device_ops"],
                            "idle_gaps": reduced["idle_gaps"]}
    out["checks"] = table
    return out


def compare(cfg: dict, seed: int, rec: Recorder, store: np.ndarray,
            n0: int, filler: np.ndarray) -> Dict[str, Dict[str, float]]:
    """-> (numbers compared but the query counts, their parts); the
    filler went into the store from row ``n0`` on."""
    models, api = cfg["models"], cfg["api"]
    parts: Dict[str, float] = {}
    for role in cfg["generating_roles"]:
        ref = make_reference(models[role], seed)
        parts[f"{role}_logit_gap"] = checks.lm_gap(ref, rec.lm.get(role, []))
        del ref
    ref = make_reference(models["embed"], seed)
    distinct, index = checks.embed_inputs(rec.embed, api["embed_max_tokens"])
    parts["embed_err"] = checks.embed_err(
        ref.embed(distinct), index, rec.embed, api["embed_max_tokens"])
    del ref
    ref = make_reference(models["rerank"], seed)
    pairs, distinct, index = checks.rerank_pairs(
        rec.rerank, api["sep_token"], api["rerank_max_tokens"])
    scores, scales = ref.rerank(distinct, api["sep_token"])
    parts["rerank_err"] = checks.rerank_err(scores, scales, index, pairs,
                                            rec.rerank)
    del ref
    parts["vsearch_err"] = checks.vsearch_err(store, rec.search)
    numbers = {
        "decode_logit_gap": max(parts[f"{r}_logit_gap"]
                                for r in cfg["generating_roles"]),
        "retrieval_err": max(parts["embed_err"], parts["rerank_err"],
                             parts["vsearch_err"]),
        "store_bad_rows": checks.store_bad_rows(store[n0:], filler,
                                                rec.adds)}
    return numbers, parts
