"""The traffic generator: seeds permute one fixed multiset of work, and the
lengths and gaps keep to the mix's parameters."""
import json

import numpy as np
import pytest

from bench import spec, traffic


def _mix(name):
    return spec.load_json(spec.BENCH_DIR / "traffic" / f"{name}.json")


@pytest.mark.parametrize("name,rate", [("chat-bursty", 4.0),
                                       ("decode-long", None)])
def test_same_seed_same_traffic(name, rate):
    a = traffic.generate(_mix(name), 2 ** 33 + 5, 40, 49155, rate=rate)
    b = traffic.generate(_mix(name), 2 ** 33 + 5, 40, 49155, rate=rate)
    assert [(p.due_s, p.max_new, p.prompt.tolist()) for p in a] == \
        [(p.due_s, p.max_new, p.prompt.tolist()) for p in b]


@pytest.mark.parametrize("name,rate", [("chat-bursty", 4.0),
                                       ("decode-long", None)])
def test_seeds_share_one_multiset(name, rate):
    a = traffic.generate(_mix(name), 1, 40, 49155, rate=rate)
    b = traffic.generate(_mix(name), 2, 40, 49155, rate=rate)
    assert sorted(len(p.prompt) for p in a) == \
        sorted(len(p.prompt) for p in b)
    assert sorted(p.max_new for p in a) == sorted(p.max_new for p in b)
    assert [len(p.prompt) for p in a] != [len(p.prompt) for p in b]
    if rate:
        # the same gaps in another order; each order's first gap is spent
        # before the first request, which is due at 0
        ga = np.round(np.diff([p.due_s for p in a]), 9)
        gb = np.round(np.diff([p.due_s for p in b]), 9)
        assert np.isin(ga, gb).sum() >= len(ga) - 1


def test_chat_distributions_respect_parameters():
    mix = _mix("chat-bursty")
    rate, seconds = 4.0, 200
    ps = traffic.generate(mix, 3, seconds, 49155, rate=rate)
    n = len(ps)
    assert n == int(np.ceil(rate * (mix["ramp_s"] + seconds + mix["tail_s"])))
    plen = np.array([len(p.prompt) for p in ps])
    outs = np.array([p.max_new for p in ps])
    pt, ot = mix["prompt_tokens"], mix["output_tokens"]
    assert plen.min() >= pt["min"] and plen.max() <= pt["max"]
    assert outs.min() >= ot["min"] and outs.max() <= ot["max"]
    assert abs(np.median(plen) / pt["median"] - 1) < 0.15
    assert abs(np.median(outs) / ot["median"] - 1) < 0.15
    due = np.array([p.due_s for p in ps])
    assert due[0] == 0 and np.all(np.diff(due) >= 0)
    gaps = np.diff(due)
    # mean gap 1/rate; gamma shape 0.25 gives a coefficient of variation
    # of 2, burstier than Poisson's 1
    assert abs(gaps.mean() * rate - 1) < 0.05
    assert 1.5 < gaps.std() / gaps.mean() < 2.5
    assert all(0 <= p.prompt.min() and p.prompt.max() < 49155 for p in ps)


def test_backlog_is_due_at_once_and_uniform():
    mix = _mix("decode-long")
    ps = traffic.generate(mix, 9, 40, 49155)
    assert len(ps) == mix["requests"]
    assert all(p.due_s == 0 for p in ps)
    plen = [len(p.prompt) for p in ps]
    outs = [p.max_new for p in ps]
    assert min(plen) >= 32 and max(plen) <= 128
    assert min(outs) >= 512 and max(outs) <= 1536
    assert 900 < np.mean(outs) < 1150


def test_large_and_negative_seeds():
    mix = _mix("chat-bursty")
    for seed in (2 ** 31 + 11, 2 ** 40, -3):
        assert traffic.generate(mix, seed, 10, 100, rate=2.0)
