import collections

import numpy as np

from benchmark.lib import traffic

MIXES = ("serve-decode-sat", "serve-chat-paced")


def _key(work):
    return [(int(p), int(o), tuple(ids[:4])) for p, o, ids in
            zip(work["prompt_len"], work["max_new"], work["prompts"])]


def test_same_seed_same_requests():
    for name in MIXES:
        mix = traffic.load(name)
        a = traffic.requests(mix, 2**31 + 5, 50304)
        b = traffic.requests(mix, 2**31 + 5, 50304)
        assert _key(a) == _key(b)
        if a["due"] is not None:
            assert np.array_equal(a["due"], b["due"])


def test_every_seed_offers_the_same_multiset_in_every_block():
    for name in MIXES:
        mix = traffic.load(name)
        a = traffic.requests(mix, 1, 50304)
        b = traffic.requests(mix, 2**31 + 99, 50304)
        assert _key(a) != _key(b)
        n = mix["block"]
        for blk in range(mix["blocks"]):
            sl = slice(blk * n, (blk + 1) * n)
            # the same requests (pairs), every combination equally often
            ca = collections.Counter(zip(a["prompt_len"][sl].tolist(),
                                         a["max_new"][sl].tolist()))
            cb = collections.Counter(zip(b["prompt_len"][sl].tolist(),
                                         b["max_new"][sl].tolist()))
            assert ca == cb and len(set(ca.values())) == 1
            assert len(ca) == len(mix["prompt_lens"]) * len(
                mix["output_lens"])


def test_every_seed_offers_the_same_gaps_in_every_block():
    ga = traffic.stratified_exponential_gaps(0.6, 48, 4,
                                             np.random.default_rng(1))
    gb = traffic.stratified_exponential_gaps(0.6, 48, 4,
                                             np.random.default_rng(2))
    assert not np.array_equal(ga, gb)
    for blk in range(4):
        sl = slice(blk * 48, (blk + 1) * 48)
        assert np.allclose(np.sort(ga[sl]), np.sort(gb[sl]))
        assert abs(ga[sl].mean() - 1 / 0.6) < 1e-9


def test_gaps_have_the_offered_rate():
    mix = traffic.load("serve-chat-paced")
    due = traffic.requests(mix, 3, 50304)["due"]
    rate = (len(due) - 1) / (due[-1] - due[0])
    assert abs(rate - mix["rate_rps"]) / mix["rate_rps"] < 0.02
    assert due[0] == 0.0 and np.all(np.diff(due) > 0)


def test_prompts_have_their_lengths_and_stay_in_vocabulary():
    work = traffic.requests(traffic.load("serve-decode-sat"), 7, 1000)
    for n, ids in zip(work["prompt_len"], work["prompts"]):
        assert len(ids) == n and ids.min() >= 0 and ids.max() < 1000
