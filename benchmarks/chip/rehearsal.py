"""A cell's run on the CPU at reduced widths, for the tests: the program
builds 2-layer float32 stand-ins of each stage model, the top-k kernel
runs in Pallas's interpret mode, and the device check is skipped.  No
number it gives is a device number."""
from __future__ import annotations

import copy
import functools
import time
from typing import Optional

import harness

REDUCED = {"num_layers": 2, "d_model": 64, "num_heads": 4,
           "num_kv_heads": 4, "head_dim": 16, "d_ff": 128,
           "vocab_size": 256, "dtype": "float32"}


# the stand-ins compute in float32, so the program meets the reference to
# float32 rounding; these limits sit far above that and far below what a
# wrong token, embedding or search result reads
LIMITS = {"decode_logit_gap": 1e-3, "retrieval_err": 1e-5}


def reduced_cell(name: str, config: Optional[dict] = None,
                 **mix) -> harness.Cell:
    """Cell ``name`` at reduced widths, under ``config`` in place of its
    own configuration where given, with ``mix`` over its traffic."""
    cell = harness.load_cell(name)
    cfg = copy.deepcopy(config or cell.config)
    cfg["build"] = dict(cfg["build"], reduced_widths=True)
    cfg["limits"] = dict(cfg["limits"], **LIMITS)
    for m in cfg["models"].values():
        m.update(REDUCED)
    cell.config = cfg
    cell.mix = dict(cell.mix, **mix)
    return cell


def interpret_topk(pipe):
    pipe.db.search = functools.partial(pipe.db.search, use_pallas=True)


def run(cell: harness.Cell, seed: int, seconds: float, trace: bool,
        pipe_hook=None):
    import jax

    def hook(pipe):
        interpret_topk(pipe)
        if pipe_hook is not None:
            pipe_hook(pipe)

    return harness.run(cell, seed, seconds, trace,
                       t_start=time.monotonic(), device=jax.devices()[0],
                       pipe_hook=hook)
