"""The comparison that decides ``correct``: what the timed path produced,
recorded at the model boundaries during the window, against the plain
reference (``reference.py``) after the window.

Numbers compared, each against its limit in the configuration file:

- ``decode_logit_gap``: over every stream that every decode call of
  either generating model served, the widest gap by which a served
  token's reference logit lies below the reference's best at that
  position (the reference runs once over each distinct prompt with its
  served tokens);
- ``retrieval_err``: the largest of three errors, each relative to the
  scale of its output: an embedding element against the reference's
  (unit vectors), a rerank score against the reference's over
  |h| |w|, and a vector search's score error of a returned row plus how
  far its exact score lies below the exact top-k at its rank (float64
  over the store rows the search could see; unit vectors);
- ``store_bad_rows``: stored rows that are not what was added, in any
  order (exact);
- ``unanswered``: queries due in the window whose answer did not stream
  in full; ``missing_outputs``: decode streams that got no tokens.

The parts of the two folded numbers are printed beside them.
"""
from __future__ import annotations

from collections import Counter
from typing import Dict, List, Sequence

import numpy as np


def crop(ids: Sequence[int], n: int) -> tuple:
    return tuple(ids)[:n]


def logit_gaps(logits: np.ndarray, prompt_len: int, served) -> np.ndarray:
    """Gap of each served token below the best logit at its position."""
    rows = logits[prompt_len - 1: prompt_len - 1 + len(served)]
    served = np.asarray(served)
    return rows.max(axis=1) - rows[np.arange(len(served)), served]


def lm_streams(calls) -> Dict[tuple, List[list]]:
    """(prompt, served[:-1]) -> the served token lists that need it."""
    out: Dict[tuple, List[list]] = {}
    for prompt, toks in calls:
        if toks:
            out.setdefault(tuple(prompt) + tuple(toks[:-1]), []).append(
                (len(prompt), toks))
    return out


def lm_gap(ref, calls) -> float:
    streams = lm_streams(calls)
    keys = sorted(streams, key=len)
    worst = 0.0
    for key, logits in zip(keys, ref.logits(keys)):
        for plen, toks in streams[key]:
            worst = max(worst, float(logit_gaps(logits, plen, toks).max()))
    return worst


def control_lm_gap(ref, ctl, calls) -> float:
    """The control's reading: at the same positions, the gap of the token
    the fp8 control puts first."""
    streams = lm_streams(calls)
    keys = sorted(streams, key=len)
    worst = 0.0
    for key, r, c in zip(keys, ref.logits(keys), ctl.logits(keys)):
        for plen, toks in streams[key]:
            picks = c[plen - 1: plen - 1 + len(toks)].argmax(axis=1)
            worst = max(worst, float(logit_gaps(r, plen, picks).max()))
    return worst


def embed_inputs(calls, max_tokens: int):
    distinct = sorted({crop(t, max_tokens) for toks, _ in calls
                       for t in toks}, key=len)
    return distinct, {t: i for i, t in enumerate(distinct)}


def embed_err(ref_vecs: np.ndarray, index, calls, max_tokens: int) -> float:
    worst = 0.0
    for toks, out in calls:
        want = ref_vecs[[index[crop(t, max_tokens)] for t in toks]]
        worst = max(worst, float(np.abs(np.asarray(out, np.float64)
                                        - want).max()))
    return worst


def rerank_pairs(calls, sep: int, max_tokens: int):
    pairs = [[crop(list(q) + [sep] + list(c), max_tokens) for c in chunks]
             for q, chunks, _ in calls]
    distinct = sorted({p for ps in pairs for p in ps}, key=len)
    return pairs, distinct, {p: i for i, p in enumerate(distinct)}


def rerank_err(ref_scores, ref_scales, index, pairs, calls) -> float:
    worst = 0.0
    for ps, (_, _, out) in zip(pairs, calls):
        i = [index[p] for p in ps]
        err = np.abs(np.asarray(out, np.float64) - ref_scores[i])
        worst = max(worst, float((err / ref_scales[i]).max()))
    return worst


def search_err(exact: np.ndarray, n_lo: int, n_hi: int, vals, ids) -> float:
    """One search: ``exact`` are the float64 scores of every stored row
    for its query; the search saw between ``n_lo`` and ``n_hi`` rows."""
    vals, ids = np.asarray(vals, np.float64), np.asarray(ids)
    best = np.inf
    for n in {n_lo, n_hi}:
        if ids.min() < 0 or ids.max() >= n:
            continue
        k = len(ids)
        top = np.sort(np.partition(exact[:n], n - k)[n - k:])[::-1]
        got = exact[ids]
        best = min(best, float(np.max(np.abs(vals - got)
                                      + np.maximum(top - got, 0.0))))
    return best


def vsearch_err(store: np.ndarray, calls) -> float:
    """``calls``: (queries (q, d), n_lo, n_hi, vals (q, k), ids (q, k))."""
    store64 = store.astype(np.float64)
    exact: Dict[bytes, np.ndarray] = {}
    worst = 0.0
    for qs, n_lo, n_hi, vals, ids in calls:
        for q, v, i in zip(np.asarray(qs, np.float32), vals, ids):
            key = q.tobytes()
            if key not in exact:
                exact[key] = store64 @ q.astype(np.float64)
            worst = max(worst, search_err(exact[key], n_lo, n_hi, v, i))
    return worst


def store_bad_rows(store: np.ndarray, filler: np.ndarray,
                   added: List[np.ndarray]) -> int:
    """Rows that differ from the filler, plus rows of the rest that do not
    match, as a multiset, the rows the window's adds wrote."""
    n_fill = len(filler)
    bad = int(np.any(store[:n_fill] != filler, axis=1).sum())
    want = (np.concatenate(added) if added
            else np.zeros((0, store.shape[1]), store.dtype))
    have = store[n_fill:]
    if len(have) != len(want):
        return bad + abs(len(have) - len(want)) + min(len(have), len(want))
    c = Counter(r.tobytes() for r in have)
    c.subtract(r.tobytes() for r in want)
    return bad + sum(v for v in c.values() if v > 0)


def judge(numbers: Dict[str, float], limits: Dict[str, float]):
    """-> (correct, {name: {"value": v, "limit": l}}); a number that came
    out NaN, or that has no limit, is not correct."""
    table = {k: {"value": numbers[k], "limit": limits.get(k)}
             for k in numbers}
    ok = all(t["limit"] is not None and t["value"] <= t["limit"]
             for t in table.values())
    return ok, table
