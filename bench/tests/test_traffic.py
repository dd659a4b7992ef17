"""The traffic generator: a seed fixes the inputs; every seed offers the
same sizes at the same times with other token ids; lengths keep to their
clamps."""

import numpy as np
import pytest

from bench.harness import spec, traffic

MIX = spec.traffic("chat")
SEEDS = [0, 7, 2 ** 31 + 11, 3_000_000_001]


def test_same_seed_same_schedule():
    a = traffic.serve_schedule(MIX, 2 ** 31 + 11, 30, 33278)
    b = traffic.serve_schedule(MIX, 2 ** 31 + 11, 30, 33278)
    assert np.array_equal(a["due"], b["due"]) and a["max_new"] == b["max_new"]
    assert all(np.array_equal(x, y) for x, y in zip(a["prompts"], b["prompts"]))


def test_seeds_share_the_schedule_not_the_tokens():
    runs = [traffic.serve_schedule(MIX, s, 30, 33278) for s in SEEDS]
    ref = runs[0]
    for r in runs[1:]:
        assert np.array_equal(r["due"], ref["due"]) and r["max_new"] == ref["max_new"]
        assert [len(p) for p in r["prompts"]] == [len(p) for p in ref["prompts"]]
        assert not np.array_equal(r["prompts"][0], ref["prompts"][0])
    assert np.allclose(np.diff(ref["due"]), ref["gaps"][:-1])
    assert np.sum(ref["gaps"]) == pytest.approx(len(ref["gaps"]) / MIX["rate_rps"])


@pytest.mark.parametrize("seed", SEEDS)
def test_lengths_keep_their_clamps(seed):
    s = traffic.serve_schedule(MIX, seed, 60, 33278)
    pl, ol = MIX["prompt_len"], MIX["output_len"]
    lens = np.array([len(p) for p in s["prompts"]])
    assert lens.min() >= pl["min"] and lens.max() <= pl["max"]
    assert min(s["max_new"]) >= ol["min"] and max(s["max_new"]) <= ol["max"]
    assert all(p.min() >= 0 and p.max() < 33278 for p in s["prompts"])
    assert np.all(np.diff(s["due"]) >= 0) and s["due"][0] == 0.0
    assert len(s["due"]) == int(np.ceil(MIX["rate_rps"] * 60))


def test_train_tokens_are_seeded_and_in_range():
    job = spec.traffic("train")
    job = dict(job, seq=12)
    a = np.asarray(traffic.zipf_markov_tokens(job, 2 ** 31 + 5, 3, 4, 500))
    b = np.asarray(traffic.zipf_markov_tokens(job, 2 ** 31 + 5, 3, 4, 500))
    c = np.asarray(traffic.zipf_markov_tokens(job, 2 ** 31 + 6, 3, 4, 500))
    assert a.shape == (3, 4, 13) and np.array_equal(a, b) and not np.array_equal(a, c)
    assert a.min() >= 0 and a.max() < 500
    rows = a.reshape(12, 13)
    assert len({tuple(r) for r in rows}) == 12          # rows all differ
