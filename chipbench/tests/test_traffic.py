import json
import os

import numpy as np

from chipbench import traffic

MIXES = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "traffic")


def mix(name):
    with open(os.path.join(MIXES, name + ".json")) as f:
        return json.load(f)


def test_open_loop_reproduces_and_differs_across_seeds():
    m = mix("chat-open")
    a = traffic.requests(m, 12345678901, 40, 32000)
    b = traffic.requests(m, 12345678901, 40, 32000)
    c = traffic.requests(m, 12345678902, 40, 32000)
    assert a == b
    assert [x[1] for x in a] != [x[1] for x in c]          # other tokens
    # the schedule is the mix's own: the same sizes at the same times
    assert [(x[0], len(x[1]), x[2]) for x in a] == [(x[0], len(x[1]), x[2]) for x in c]
    other = traffic.requests(dict(m, order_seed=25), 12345678901, 40, 32000)
    assert [x[0] for x in a] != [x[0] for x in other]


def test_the_window_holds_the_quantiles_of_the_mix():
    m = mix("chat-open")
    ramp, secs = m["ramp_s"], 40
    a = traffic.requests(m, 1, secs, 32000)
    win = [x for x in a if ramp <= x[0] < ramp + secs]
    n = round(m["rate_per_s"] * secs)
    assert len(win) == n and len(a) - n == round(m["rate_per_s"] * ramp)
    assert sorted(len(x[1]) for x in win) == traffic.quantiles(m["prompt_len"], n).tolist()
    assert sorted(x[2] for x in win) == traffic.quantiles(m["output_len"], n).tolist()
    assert all(x[0] < y[0] for x, y in zip(a, a[1:]))      # in sending order


def test_lengths_keep_to_their_clips():
    for name, lo, hi in (("chat-open", 16, 512), ("doc-single", 1024, 3072)):
        m = mix(name)
        r = traffic.requests(m, 3, 40, 32000)
        lens = [len(x[1]) for x in r]
        assert min(lens) >= lo and max(lens) <= hi
        total = [len(x[1]) + x[2] for x in r]
        assert max(total) <= m["engine"]["max_seq_len"]
        assert all(1 <= t < 32000 for x in r[:5] for t in x[1])


def test_closed_loop_has_no_due_times_and_enough_requests():
    m = mix("doc-single")
    r = traffic.requests(m, 9, 40, 32000)
    assert all(x[0] is None for x in r)
    assert len(r) >= 40 / m["least_request_s"]


def test_train_batch_rows_all_differ():
    m = mix("s512-1chip")
    ids, labels = traffic.train_batch(m, 5, 40000)
    ids2, _ = traffic.train_batch(m, 5, 40000)
    ids3, _ = traffic.train_batch(m, 6, 40000)
    assert ids.shape == (16, 512) and (ids == ids2).all() and (ids != ids3).any()
    assert len({row.tobytes() for row in ids}) == 16
    assert ids.min() >= 1 and labels.min() >= 0 and labels.max() < 40000


def test_quantiles_of_the_lognormal():
    q = traffic.quantiles({"dist": "lognormal", "median": 64, "sigma": 0.8, "min": 16, "max": 512}, 1001)
    assert q[500] == 64 and q.min() >= 16 and q.max() <= 512
    # 84th percentile of a lognormal is median * e^sigma
    assert abs(q[841] - 64 * np.exp(0.8)) < 3
