"""The traffic generator: one seed gives the same traffic twice, every seed
the same lengths in another order, at the stated medians and clips."""
import numpy as np
import pytest

from bench import traffic

ROLL = traffic.load("grpo_rollout_gsm8k")
BATCH = traffic.load("rl_batch_gsm8k")


def _groups(seed, n):
    it = traffic.rollout_groups(ROLL, seed, 151936)
    return [next(it) for _ in range(n)]


def test_rollout_same_seed_same_traffic():
    a, b = _groups(2 ** 33 + 5, 80), _groups(2 ** 33 + 5, 80)
    for (pa, ba), (pb, bb) in zip(a, b):
        assert np.array_equal(pa, pb) and ba == bb
    c = _groups(7, 80)
    assert any(not np.array_equal(x[0], y[0]) for x, y in zip(a, c))


def test_rollout_lengths_are_the_same_multiset_for_every_seed():
    n = ROLL["block_groups"]
    lens = [sorted(len(p) for p, _ in _groups(s, n)) for s in (1, 99)]
    assert lens[0] == lens[1]
    budgets = [sorted(b for _, bs in _groups(s, n) for b in bs)
               for s in (1, 99)]
    assert budgets[0] == budgets[1]


@pytest.mark.parametrize("key", ["prompt_len", "response_len"])
def test_lengths_match_stated_median_and_clips(key):
    spec = ROLL[key]
    x = traffic.quantile_lengths(spec, 4000)
    assert abs(np.median(x) - spec["median"]) <= 1
    # the upper clip binds for both; the lower only guards the tail
    assert x.min() >= spec["min"] and x.max() == spec["max"]
    # p95 of the response budget is about exp(log(256) + 1.645 * 0.5)
    if key == "response_len":
        assert 560 <= np.percentile(x, 95) <= 600


def test_group_members_share_the_prompt_and_avoid_special_ids():
    for prompt, budgets in _groups(3, 30):
        assert len(budgets) == ROLL["group_size"]
        assert prompt.min() >= ROLL["first_token_id"]


def test_rl_batches_same_seed_and_shapes():
    small = dict(BATCH, pad_to=BATCH["pad_to"])
    a = traffic.rl_batches(small, 5, 2, 16, 1000)
    b = traffic.rl_batches(small, 5, 2, 16, 1000)
    for x, y in zip(a, b):
        for k in x:
            assert np.array_equal(x[k], y[k]), k
    t = a[0]
    assert t["tokens"].shape == (16, 1344)
    assert t["response_mask"].shape == (16, 1343)
    # every row's mask covers exactly its response, offsets only there
    P_plus_R = t["lengths"]
    assert np.array_equal((t["tokens"] > 0).sum(1), P_plus_R)
    assert np.all(t["behav_offset"][t["response_mask"] == 0] == 0)
    assert set(np.unique(t["versions"])) <= set(range(5))
    # same real-token count on every seed: the lengths are a fixed multiset
    c = traffic.rl_batches(small, 6, 1, 16, 1000)[0]
    assert c["lengths"].sum() == t["lengths"].sum()
    with pytest.raises(ValueError):
        traffic.rl_batches(small, 5, 1, 15, 1000)
