"""The one traffic generator: deterministic for a seed, the same work for
every seed, the stated lengths and rate."""
import json
import os
from collections import Counter

import numpy as np
import pytest

from benchlib import traffic

CHAT = json.load(open(os.path.join(os.path.dirname(__file__), "..",
                                   "traffic", "chat.json")))
BIG_SEED = 2 ** 31 + 12345


def test_same_seed_same_schedule():
    a = traffic.schedule(CHAT, 2.5, 51, BIG_SEED, 49152)
    b = traffic.schedule(CHAT, 2.5, 51, BIG_SEED, 49152)
    assert [(r.t_due, r.prompt, r.max_tokens) for r in a] == \
        [(r.t_due, r.prompt, r.max_tokens) for r in b]


def test_seeds_permute_one_multiset():
    a = traffic.schedule(CHAT, 2.5, 51, 1, 49152)
    b = traffic.schedule(CHAT, 2.5, 51, 2, 49152)
    assert Counter(len(r.prompt) for r in a) == \
        Counter(len(r.prompt) for r in b)
    assert Counter(r.max_tokens for r in a) == Counter(r.max_tokens for r in b)
    assert [r.t_due for r in a] != [r.t_due for r in b]
    assert [r.prompt for r in a] != [r.prompt for r in b]


@pytest.mark.parametrize("rate,seconds", [(2.5, 51), (0.7, 10), (40, 3)])
def test_rate_and_window(rate, seconds):
    s = traffic.schedule(CHAT, rate, seconds, 7, 1000)
    assert len(s) == round(rate * seconds)
    due = [r.t_due for r in s]
    assert due[0] == 0.0 and all(0 <= t < seconds for t in due)
    assert due == sorted(due)


def test_lengths_follow_the_mix():
    s = traffic.schedule(CHAT, 10, 100, 3, 50304)
    p = np.array([len(r.prompt) for r in s])
    o = np.array([r.max_tokens for r in s])
    assert p.min() >= 32 and p.max() <= 1536
    assert o.min() >= 8 and o.max() <= 384
    assert abs(np.median(p) - 384) <= 8 and abs(np.median(o) - 96) <= 3
    # lognormal sigma: the quartiles sit at median * exp(+-0.6745 sigma)
    q1, q3 = np.percentile(p, [25, 75])
    assert abs(np.log(q3 / q1) / (2 * 0.6745) - 0.8) < 0.05
    assert all(0 <= t < 50304 for r in s for t in r.prompt)


def test_unique_prompts_share_no_block():
    s = traffic.schedule(CHAT, 5, 20, 9, 49152)
    firsts = {tuple(r.prompt[:16]) for r in s}
    assert len(firsts) == len(s)


def test_shared_prefix_and_bursts():
    mix = dict(CHAT, arrivals={"kind": "gamma", "cv": 2.0},
               shared_prefix={"docs": 4, "length": 64, "zipf_s": 1.0})
    s = traffic.schedule(mix, 4, 30, 5, 1000)
    docs = traffic.documents(mix, 5, 1000)
    assert len(docs) == 4
    assert all(any(r.prompt[:64] == d for d in docs) for r in s)
    g = np.diff([r.t_due for r in s])
    assert g.std() / g.mean() > 1.3            # bursty
