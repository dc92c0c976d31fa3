"""The one traffic generator: a mix is data; a seed orders the mix's
pool and draws the ids, and changes nothing else."""
import json
import os

import numpy as np

from conftest import BENCH

from benchmark.lib import traffic


def _mix(name):
    with open(os.path.join(BENCH, "workloads", name + ".json")) as f:
        return json.load(f)["traffic_params"]


def test_every_seed_gets_the_same_sizes_in_another_order():
    tp = _mix("opt6.7b_chat_c16")
    a, b = traffic.order(tp, 1), traffic.order(tp, 2 ** 31 + 5)
    assert sorted(a) == sorted(b) == sorted(traffic.pool(tp))
    assert a != b
    assert traffic.order(tp, 1) == a


def test_lengths_keep_to_the_mix():
    for name in ("opt6.7b_chat_c16", "opt6.7b_doc_c8"):
        tp = _mix(name)
        pool = traffic.pool(tp)
        assert len(pool) == tp["pool_requests"]
        for plen, olen in pool:
            assert tp["prompt"]["min"] <= plen <= tp["prompt"]["max"]
            assert plen <= tp["max_prompt_tokens"]
            assert tp["output"]["min"] <= olen <= tp["output"]["max"]
            assert olen <= tp["max_new_tokens"]
    chat = [p for p, _ in traffic.pool(_mix("opt6.7b_chat_c16"))]
    assert 150 < np.median(chat) < 400            # median 256, 64 draws


def test_token_ids_come_from_the_seed_and_the_request():
    a = traffic.prompt_tokens(7, 3, 100, 50272)
    assert a.dtype == np.int32 and a.min() >= 1 and a.max() < 50272
    assert (traffic.prompt_tokens(7, 3, 100, 50272) == a).all()
    assert (traffic.prompt_tokens(7, 4, 100, 50272) != a).any()
    assert (traffic.prompt_tokens(2 ** 31 + 7, 3, 100, 50272) != a).any()


def test_fixed_and_unknown_distributions():
    rng = np.random.default_rng(0)
    assert traffic.draw_lengths({"dist": "fixed", "value": 9}, 3,
                                rng).tolist() == [9, 9, 9]
    try:
        traffic.draw_lengths({"dist": "zipf"}, 3, rng)
    except ValueError:
        pass
    else:
        raise AssertionError("an unknown distribution was accepted")
