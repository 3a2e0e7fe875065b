"""Readings that the limits of ``correct`` are set from, on the chip.

    python3 benchmarks/chip/control.py --workload <cell> --seconds <s> \
        --seeds <n> [<n> ...]

For each seed, in this one process: one run of the cell at its own load
(set-up, a window of ``--seconds``, the program's numbers as the benchmark
compares them), then the control on the same recorded inputs: each
model's reference (the module its configuration entry names) computed in
fp8 e4m3 (weights per output channel, matmul inputs per token) in the
program's place, and the store search with the store and the query
rounded to e4m3 per row.  One JSON line per seed: ``program`` and
``control`` readings by number.  The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parent


def fp8_search(store, calls):
    """The search's control: top-k over rows and query rounded to e4m3
    (one scale per row), with float32 products."""
    import jax.numpy as jnp

    import checks
    import reference

    rounded = np.asarray(reference.fp8(jnp.asarray(store, jnp.float32),
                                       (-1,)))
    store64 = store.astype(np.float64)
    worst = 0.0
    for qs, n_lo, n_hi, vals, ids in calls:
        for q, v in zip(np.asarray(qs, np.float32), vals):
            qr = np.asarray(reference.fp8(jnp.asarray(q[None]), (-1,)))[0]
            s = rounded[:n_lo] @ qr
            k = len(v)
            top = np.argpartition(s, n_lo - k)[n_lo - k:]
            top = top[np.argsort(-s[top], kind="stable")]
            exact = store64 @ q.astype(np.float64)
            worst = max(worst, checks.search_err(exact, n_lo, n_lo, s[top],
                                                 top))
    return worst


def altered_token_gap(ref, calls) -> float:
    """The fault "a token altered where it is produced": the least gap a
    served token reads when it is replaced by the next token id, over
    every served position (the reading of the mildest such fault)."""
    import checks

    streams = checks.lm_streams(calls)
    keys = sorted(streams, key=len)
    least = np.inf
    for key, logits in zip(keys, ref.logits(keys)):
        for plen, toks in streams[key]:
            bumped = [(t + 1) % logits.shape[1] for t in toks]
            least = min(least, float(checks.logit_gaps(logits, plen,
                                                       bumped).min()))
    return least


def control_numbers(cfg: dict, seed: int, rec, store) -> dict:
    """The control's reading of each number compared, with its parts."""
    import checks
    from harness import make_reference

    models, api = cfg["models"], cfg["api"]
    out = {}
    for role in cfg["generating_roles"]:
        ref = make_reference(models[role], seed)
        ctl = make_reference(models[role], seed, quant="fp8")
        calls = rec.lm.get(role, [])
        out[f"{role}_logit_gap"] = checks.control_lm_gap(ref, ctl, calls)
        out[f"{role}_logit_gap.token_altered"] = altered_token_gap(ref,
                                                                   calls)
    distinct, _ = checks.embed_inputs(rec.embed, api["embed_max_tokens"])
    r = make_reference(models["embed"], seed).embed(distinct)
    c = make_reference(models["embed"], seed, quant="fp8").embed(distinct)
    out["embed_err"] = float(np.abs(r - c).max())
    _, distinct, _ = checks.rerank_pairs(rec.rerank, api["sep_token"],
                                         api["rerank_max_tokens"])
    rs, scale = make_reference(models["rerank"], seed).rerank(
        distinct, api["sep_token"])
    cs, _ = make_reference(models["rerank"], seed, quant="fp8").rerank(
        distinct, api["sep_token"])
    out["rerank_err"] = float((np.abs(rs - cs) / scale).max())
    out["vsearch_err"] = fp8_search(store, rec.search)
    out["decode_logit_gap"] = max(out[f"{role}_logit_gap"]
                                  for role in cfg["generating_roles"])
    out["retrieval_err"] = max(out["embed_err"], out["rerank_err"],
                               out["vsearch_err"])
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(BENCH), str(BENCH.parents[1] / "src")]
    import jax

    import harness

    cell = harness.load_cell(args.workload)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print("control.py: needs a TPU", file=sys.stderr)
        return 2
    for seed in args.seeds:
        keep: dict = {}
        out = harness.run(cell, seed, args.seconds, False,
                          t_start=time.monotonic(), device=dev, keep=keep)
        ctl = control_numbers(cell.config, seed, keep["rec"], keep["store"])
        print(json.dumps({
            "seed": seed, "correct": out["correct"],
            "attempted": out["attempted"],
            "metrics": {k: v["value"] for k, v in out["metrics"].items()},
            "program": {k: v["value"] for k, v in out["checks"].items()},
            "control": ctl}), flush=True)
        del keep
    return 0


if __name__ == "__main__":
    sys.exit(main())
