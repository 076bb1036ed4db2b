"""The seeded open-loop schedule and its length draws."""
import numpy as np
import pytest

from bench import common, traffic as gen

MIX = common.traffic_file("chat")


def test_same_seed_same_schedule():
    a = gen.serve_schedule(MIX, 2 ** 31 + 5, 51.0, 111.0, 32256)
    b = gen.serve_schedule(MIX, 2 ** 31 + 5, 51.0, 111.0, 32256)
    assert [(r.due_s, r.prompt, r.max_new_tokens) for r in a] == \
        [(r.due_s, r.prompt, r.max_new_tokens) for r in b]


def test_seeds_replay_the_same_work_with_their_own_tokens():
    a = gen.serve_schedule(MIX, 1, 51.0, 111.0, 32256, rate=0.5)
    b = gen.serve_schedule(MIX, 2, 51.0, 111.0, 32256, rate=0.5)
    assert [(r.due_s, len(r.prompt), r.max_new_tokens) for r in a] == \
        [(r.due_s, len(r.prompt), r.max_new_tokens) for r in b]
    assert sum(r.due_s < 51.0 for r in a) == 26
    assert [r.prompt for r in a] != [r.prompt for r in b]
    other = dict(MIX, schedule_seed=MIX["schedule_seed"] + 1)
    c = gen.serve_schedule(other, 1, 51.0, 111.0, 32256, rate=0.5)
    assert [len(r.prompt) for r in c] != [len(r.prompt) for r in a]
    assert sorted(len(r.prompt) for r in c if r.due_s < 51.0) == \
        sorted(len(r.prompt) for r in a if r.due_s < 51.0)


@pytest.mark.parametrize("seed", [0, 7, 2 ** 31 + 11])
def test_bounds_and_rate(seed):
    s = gen.serve_schedule(MIX, seed, 100.0, 300.0, 32256, rate=1.5)
    assert all(0 <= r.due_s < 300.0 for r in s)
    assert [r.due_s for r in s] == sorted(r.due_s for r in s)
    assert sum(r.due_s < 100.0 for r in s) == 150
    assert all(MIX["prompt"]["min"] <= len(r.prompt) <= MIX["prompt"]["max"]
               for r in s)
    assert all(MIX["output"]["min"] <= r.max_new_tokens
               <= MIX["output"]["max"] for r in s)
    assert all(0 <= t < 32256 for r in s for t in r.prompt)
    assert len(s) == 450


def test_length_quantiles():
    n = 1001
    x = gen.lognormal_lengths(n, {"median": 1024, "sigma": 1.0,
                                  "min": 128, "max": 3584})
    assert np.median(x) == 1024
    assert x.min() == 128 and x.max() == 3584
    gaps = gen.poisson_gaps(n, 2.0)
    assert np.mean(gaps) == pytest.approx(0.5, rel=0.01)


def test_reachable_buckets():
    assert gen.prefill_buckets(MIX, 16, 4096) == [128, 256, 512, 1024,
                                                  2048, 4096]
    assert gen.prefill_buckets({"prompt": {"min": 8, "max": 60}}, 16, 80) \
        == [16, 32, 64]
    assert gen.row_buckets(8) == [1, 2, 4, 8]
    assert gen.row_buckets(6) == [1, 2, 4, 6]
