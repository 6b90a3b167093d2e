"""The schedule is the mix's data: the same list every time, whatever
``--seed`` is, and its lengths are the distribution's quantiles."""

import math

import numpy as np
import pytest

from benchmark import traffic
from benchmark.spec import Layout

CHAT = Layout().mix("serve-chat")
OFFLINE = Layout().mix("serve-offline")


def test_schedule_is_the_same_list_twice():
    assert traffic.schedule(CHAT) == traffic.schedule(CHAT)
    assert traffic.schedule(OFFLINE) == traffic.schedule(OFFLINE)


def test_seed_changes_token_ids_and_nothing_of_the_schedule():
    # schedule() takes no seed at all; the seed reaches only token_ids
    a = traffic.token_ids(1, 3, 50, 50257)
    b = traffic.token_ids(2 ** 31 + 5, 3, 50, 50257)
    assert a.shape == b.shape == (50,) and not np.array_equal(a, b)
    assert np.array_equal(a, traffic.token_ids(1, 3, 50, 50257))
    assert a.min() >= 0 and a.max() < 50257 and a.dtype == np.int32


@pytest.mark.parametrize("which", ["prompt", "answer"])
def test_lengths_are_the_stated_multiset(which):
    reqs = traffic.schedule(CHAT)
    got = sorted(getattr(r, which + "_len") for r in reqs)
    dist = CHAT[which]
    assert got == traffic.lengths(dist, len(reqs))  # quantiles, not draws
    assert got[0] >= dist["min"] and got[-1] <= dist["max"]
    # the middle quantile of a log-normal is its median
    assert abs(got[len(got) // 2] - dist["median"]) <= 0.03 * dist["median"]


def test_quantiles_by_hand():
    assert traffic.quantiles({"dist": "uniform", "min": 0, "max": 8}, 4) \
        == [1.0, 3.0, 5.0, 7.0]
    q = traffic.quantiles({"dist": "exponential", "mean": 2.0}, 2)
    assert q == pytest.approx([-2 * math.log(0.75), -2 * math.log(0.25)])
    q = traffic.quantiles({"dist": "lognormal", "median": 100,
                           "sigma": 1.0}, 3)
    assert q[1] == pytest.approx(100.0)
    assert q[0] * q[2] == pytest.approx(100.0 ** 2)  # symmetric in the log


def test_open_loop_rate_burst_and_fit():
    reqs = traffic.schedule(CHAT)
    due = [r.due_s for r in reqs]
    assert due == sorted(due) and due[0] == 0.0
    assert len(reqs) == round(CHAT["rate_rps"] * CHAT["horizon_s"])
    b = CHAT["burst"]
    inside = [t for t in due if b["at_s"] <= t < b["at_s"] + b["within_s"]]
    assert len(inside) == b["requests"]
    assert all(r.prompt_len + r.answer_len <= CHAT["max_length"]
               for r in reqs)
    # the schedule covers the lead-in and the longest window there is
    assert due[-1] >= CHAT["lead_in_s"] + 51 - 2.0 / CHAT["rate_rps"]


def test_buckets_used_are_a_subset_of_the_mix():
    for mix in (CHAT, OFFLINE):
        used = traffic.buckets_used(mix, traffic.schedule(mix))
        assert set(used) <= set(mix["prefill_buckets"]) and used
    with pytest.raises(ValueError):
        traffic.bucket_for([8, 16], 17)


def test_percentile_is_numpys():
    xs = [3.0, 1.0, 4.0, 1.0, 5.0, 9.0, 2.0, 6.0]
    for q in (0, 50, 95, 100):
        assert traffic.percentile(xs, q) == pytest.approx(np.percentile(xs, q))
