"""The timed path broken underneath a whole run (CPU, reduced widths):
each fault the cells can have turns ``correct`` false through the number
meant to catch it."""
import numpy as np
import pytest

import rehearsal


def alter_token(pipe):
    gen = pipe.chat.generate_batch

    def run(prompts, max_new=32):
        out = gen(prompts, max_new)
        out[0].token_ids[-1] = (out[0].token_ids[-1] + 1) % 256
        return out
    pipe.chat.generate_batch = run


def drop_half_batch(pipe):
    gen = pipe.chat.generate_batch

    def run(prompts, max_new=32):
        out = gen(prompts, max_new)
        return out[:len(out) // 2]
    pipe.chat.generate_batch = run


def alter_embedding(pipe):
    embed = pipe.embedder.embed

    def run(token_lists):
        out = embed(token_lists)
        return out + np.float32(1e-3)
    pipe.embedder.embed = run


def alter_search(pipe):
    search = pipe.db.search

    def run(queries, k, use_pallas=None):
        vals, ids = search(queries, k, use_pallas=True)
        return vals, (ids + 1) % len(pipe.db)
    pipe.db.search = run


@pytest.mark.parametrize("fault,number", [
    (alter_token, "decode_logit_gap"),
    (drop_half_batch, "missing_outputs"),
    (alter_embedding, "retrieval_err"),
    (alter_search, "retrieval_err"),
])
def test_fault_is_caught(fault, number):
    cell = rehearsal.reduced_cell("qwen3-w2-poisson", rate_qps=2.0)
    out = rehearsal.run(cell, 23, 3.0, False, pipe_hook=fault)
    assert out["correct"] is False
    c = out["checks"][number]
    assert not c["value"] <= c["limit"], out["checks"]
