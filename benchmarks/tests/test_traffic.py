"""The generators: the seed changes tokens and order, never the amount
of work; and the rule for token arrivals."""

import json
import os
from collections import Counter

import numpy as np

from benchmarks.traffic_kinds import serve_closed_cycle as serve
from benchmarks.traffic_kinds import train_packed as train

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def mix(name):
    with open(os.path.join(HERE, "traffic", name + ".json")) as f:
        return json.load(f)


def test_serve_cycle_same_multisets_for_every_seed():
    tr = mix("serve_chat")
    assert Counter(l for l, _ in tr["cycle"]) == {256: 4, 1024: 8, 2048: 4}
    assert Counter(b for _, b in tr["cycle"]) == {128: 4, 192: 8, 256: 4}
    want = Counter(map(tuple, tr["cycle"]))
    orders = set()
    for seed in (0, 7, 2**31 + 5, 3_000_000_011):
        assert sorted(serve.roles(tr, seed)) == list(range(16))
        for k in range(3):
            cyc = serve.make_cycle(tr, 92544, seed, k)
            assert Counter((len(p), b) for p, b in cyc) == want
            orders.add(tuple((len(p), b) for p, b in cyc))
    assert len(orders) == 12                    # the order does change


def test_serve_every_seed_offers_one_schedule_under_other_names():
    """Role r sends entries r, r+1, ... of the cycle whatever the seed."""
    tr = mix("serve_chat")
    for seed in (3, 2**31 + 77):
        r = serve.roles(tr, seed)
        for k in range(4):
            cyc = serve.make_cycle(tr, 92544, seed, k)
            for caller, (p, b) in enumerate(cyc):
                assert [len(p), b] == tr["cycle"][(r[caller] + k) % 16]


def test_serve_cycle_seeded_and_no_shared_prefix():
    tr = mix("serve_chat")
    a = serve.make_cycle(tr, 92544, 11, 0)
    b = serve.make_cycle(tr, 92544, 11, 0)
    c = serve.make_cycle(tr, 92544, 12, 0)
    assert all(np.array_equal(x[0], y[0]) for x, y in zip(a, b))
    assert not any(len(x[0]) == len(y[0]) and np.array_equal(x[0], y[0])
                   for x, y in zip(a, c))
    firsts = [int(p[0]) for k in range(6)
              for p, _ in serve.make_cycle(tr, 92544, 11, k)]
    assert len(set(firsts)) == len(firsts) == 96
    for p, _ in a:
        assert p.dtype == np.int32 and p.min() >= 0 and p.max() < 92544


def test_train_batches_same_shapes_other_tokens():
    tr = mix("train_dp4")
    g1, g2 = (train.generate(tr, 32000, s, 4) for s in (1, 2**31 + 9))
    a, a2, b = next(g1), next(g1), next(g2)
    assert a.shape == a2.shape == b.shape == (8, 4096) and a.dtype == np.int32
    assert not np.array_equal(a, a2) and not np.array_equal(a, b)
    again = next(train.generate(tr, 32000, 1, 4))
    assert np.array_equal(a, again)
    # rows all differ
    assert len({r.tobytes() for r in a}) == 8


def test_arrivals_two_tokens_in_one_step_are_one_arrival():
    arr = serve.Arrivals()
    arr.open(10.0)
    arr.submit("r", 10.5)
    arr.step_returned({"r": [5, 6]}, 12.0)     # prefill + first decode token
    assert arr.ttft_ms == [1500.0] and arr.gap_ms == [] and arr.tokens == 2
    arr.step_returned({"r": [7], "other": []}, 12.25)
    arr.step_returned({"r": [8]}, 14.25)       # somebody else was admitted
    assert arr.gap_ms == [250.0, 2000.0] and arr.tokens == 4


def test_arrivals_count_only_what_lies_inside_the_window():
    arr = serve.Arrivals()
    arr.submit("early", 1.0)
    arr.step_returned({"early": [1]}, 2.0)
    arr.submit("straddle", 2.5)
    arr.open(3.0)
    arr.step_returned({"early": [2], "straddle": [3]}, 4.0)
    # early's gap began before the window; straddle was submitted before it
    assert arr.gap_ms == [] and arr.ttft_ms == [] and arr.tokens == 2
    arr.step_returned({"early": [4], "straddle": [5]}, 4.5)
    assert arr.gap_ms == [500.0, 500.0]
    arr.finished("early")
    arr.submit("early", 5.0)                   # the id may come again
    arr.step_returned({"early": [9]}, 6.0)
    assert arr.ttft_ms == [1000.0]
