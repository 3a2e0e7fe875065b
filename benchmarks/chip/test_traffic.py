"""Traffic generation: a mix file fixes the schedule of every run."""
import json
from pathlib import Path

import pytest

import traffic

MIXES = Path(__file__).parent / "traffic"


def _mix(name):
    return json.loads((MIXES / f"{name}.json").read_text())


def _shape(q):
    return tuple(sorted(q.items()))


@pytest.mark.parametrize("name", ["w2-poisson", "w2-single"])
def test_the_schedule_is_fixed_by_the_mix(name):
    mix = _mix(name)
    a, arr_a = traffic.schedule(mix, 20.0)
    b, arr_b = traffic.schedule(mix, 20.0)
    assert [_shape(q) for q in a] == [_shape(q) for q in b]
    assert arr_a == arr_b
    c, _ = traffic.schedule(dict(mix, pool_seed=mix["pool_seed"] + 1), 20.0)
    assert [_shape(q) for q in a] != [_shape(q) for q in c]


def test_open_loop_offers_the_mix_rate_over_the_window():
    mix = _mix("w2-poisson")
    q, arr = traffic.schedule(mix, 50.0)
    assert len(q) == round(mix["rate_qps"] * 50.0)
    assert arr[0] == 0.0 and max(arr) < 50.0
    assert arr == sorted(arr)


def test_every_dataset_is_drawn():
    q, _ = traffic.schedule(_mix("w2-single"), 0)
    assert {x["dataset"] for x in q} == set(traffic.DATASETS)


def test_chunk_rows_bounds_the_adds():
    q, _ = traffic.schedule(_mix("w2-single"), 0)
    assert traffic.chunk_rows(q) == sum(x["n_chunks"] for x in q)


def test_draw_matches_the_program_generator():
    """The copied generator draws what the program's sample_traces draws
    from the same stream."""
    import numpy as np

    from repro.rag import sample_traces

    for name in traffic.DATASETS:
        want = sample_traces(name, 5, seed=9)
        rng = np.random.default_rng(9)
        got = [traffic.draw_query(name, rng) for _ in range(5)]
        assert all(getattr(w, k) == v for w, g in zip(want, got)
                   for k, v in g.items())
