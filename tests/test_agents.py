"""LMAgent's generation, every step enqueued and one fetch per call,
against a stepwise oracle: the host loop that fetches each token before
it decides on the next step, at reduced widths on the CPU."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_family, reduced
from repro.core.events import (CT_LM_FETCHES, CT_LM_STEPS, SP_LM_CALL,
                               SP_LM_FETCH, SP_LM_STEP)
from repro.models import build_model
from repro.rag import agents
from repro.rag.agents import LMAgent
from repro.rag.embedder import CALL_WIDTH
from repro.serving import spans

MAX_LEN = 64
PROMPT_LEN = 6


@pytest.fixture(scope="module")
def lm():
    cfg = reduced(get_family("qwen3")["search"])
    return cfg, build_model(cfg).init(jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def agent(lm):
    return LMAgent(*lm, max_len=MAX_LEN)


@pytest.fixture(autouse=True)
def recorder():
    spans.disable()
    spans.clear()
    yield
    spans.disable()
    spans.clear()


class Stepwise:
    """The oracle: a jitted prefill, then one jitted decode step per token
    with a fetch to the host after each, stopping before a step when
    ``stop_at_eos`` and row 0's last token is ``agents.EOS`` (read at
    call time).  Same width, padding rows and left-cropping as the
    agent."""

    def __init__(self, agent: LMAgent):
        model, max_len = agent.model, agent.max_len

        def prefill(params, tokens):
            cache = model.init_cache(tokens.shape[0], max_len)
            logits, cache = model.prefill(params, {"tokens": tokens}, cache)
            return jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32), cache

        def decode(params, tok, cache):
            logits, cache = model.decode_step(params, tok[:, None], cache)
            return jnp.argmax(logits, axis=-1).astype(jnp.int32), cache

        self.params = agent.params
        self.prefill, self.decode = jax.jit(prefill), jax.jit(decode)

    def run(self, prompts, max_new, stop_at_eos):
        length = min(len(p) for p in prompts)
        cropped = [list(p)[-length:] for p in prompts]
        outs = []
        for i in range(0, len(cropped), CALL_WIDTH):
            group = cropped[i:i + CALL_WIDTH]
            n = len(group)
            rows = group + [group[0]] * (CALL_WIDTH - n)
            tok, cache = self.prefill(self.params,
                                      jnp.asarray(rows, jnp.int32))
            seqs = [[int(t)] for t in np.asarray(tok)[:n]]
            for _ in range(max_new - 1):
                if stop_at_eos and seqs[0][-1] == agents.EOS:
                    break
                tok, cache = self.decode(self.params, tok, cache)
                for seq, t in zip(seqs, np.asarray(tok)[:n]):
                    seq.append(int(t))
            outs += seqs
        return outs


@pytest.fixture(scope="module")
def oracle(agent):
    return Stepwise(agent)


def prompts_of(n: int, vocab: int):
    """``n`` distinct prompts, the first ``PROMPT_LEN`` tokens long and the
    rest longer, so ``generate_batch`` crops them."""
    rng = np.random.default_rng(n)
    lengths = [PROMPT_LEN] + [PROMPT_LEN + int(k)
                              for k in rng.integers(0, 4, n - 1)]
    return [rng.integers(4, vocab, m).tolist() for m in lengths]


def generated(agent: LMAgent, prompts, max_new, stop_at_eos):
    if len(prompts) == 1:
        return [agent.generate(prompts[0], max_new, stop_at_eos).token_ids]
    if stop_at_eos:
        # several streams that stop at row 0's EOS: the call that
        # generate and generate_batch share, one group
        n = min(len(p) for p in prompts)
        return agent._run([p[-n:] for p in prompts], max_new, True)
    return [g.token_ids for g in agent.generate_batch(prompts, max_new)]


@pytest.mark.parametrize("max_new", [1, 2, 8, 13])
@pytest.mark.parametrize("n", [1, 3, 8, 11])
def test_one_fetch_matches_stepwise(agent, oracle, n, max_new):
    prompts = prompts_of(n, agent.cfg.vocab_size)
    want = oracle.run(prompts, max_new, False)
    assert all(len(seq) == max(max_new, 1) for seq in want)
    assert generated(agent, prompts, max_new, False) == want


@pytest.mark.parametrize("max_new", [1, 2, 8, 13])
@pytest.mark.parametrize("n", [1, 3, 8])
def test_one_fetch_stops_at_eos_like_stepwise(agent, oracle, monkeypatch,
                                              n, max_new):
    prompts = prompts_of(n, agent.cfg.vocab_size)
    # row 0's token at step 2 stands in for EOS
    monkeypatch.setattr(agents, "EOS", oracle.run(prompts, 8, False)[0][2])
    want = oracle.run(prompts, max_new, True)
    if max_new > 3:
        assert len(want[0]) <= 3
    assert generated(agent, prompts, max_new, True) == want


def test_serving_calls_share_one_program_each(lm):
    agent = LMAgent(*lm, max_len=MAX_LEN)
    prompt = prompts_of(1, lm[0].vocab_size)[0]
    agent.generate(prompt, max_new=2, stop_at_eos=False)   # the warm-up
    for max_new in range(1, 9):
        for stop in (False, True):
            agent.generate(prompt, max_new=max_new, stop_at_eos=stop)
        agent.generate_batch([prompt] * 3, max_new=max_new)
    assert agent._prefill._cache_size() == agent._decode._cache_size() == 1


def test_one_fetch_per_call_whatever_the_steps(agent):
    prompt = prompts_of(1, agent.cfg.vocab_size)[0]
    spans.enable()
    agent.generate(prompt, max_new=8, stop_at_eos=False)
    agent.generate_batch([prompt] * 11, max_new=5)
    recorded, counters = spans.drain()
    by = {name: [s for s in recorded if s.name == name]
          for name in (SP_LM_CALL, SP_LM_STEP, SP_LM_FETCH)}
    assert counters[CT_LM_FETCHES] == 3
    assert [len(v) for v in by.values()] == [3, 3, 3]
    calls = {s.id for s in by[SP_LM_CALL]}
    steps = {s.id for s in by[SP_LM_STEP]}
    assert {s.parent for s in by[SP_LM_STEP]} == calls
    assert {s.parent for s in by[SP_LM_FETCH]} == steps
    assert counters[CT_LM_STEPS] == 7 + 2 * 4

