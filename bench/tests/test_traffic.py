"""The traffic generator: deterministic for a seed, true to each mix's
declared parameters, the same work for every seed."""
import collections
import math

import numpy as np
import pytest

from bench.lib import traffic

MIXES = ["chat", "docqa_backlog"]


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_requests(name):
    mix = traffic.load(name)
    a = traffic.requests(mix, 2**31 + 12345, 45, 151936)
    b = traffic.requests(mix, 2**31 + 12345, 45, 151936)
    assert len(a) == len(b)
    for x, y in zip(a, b):
        assert x.due_s == y.due_s and x.max_new_tokens == y.max_new_tokens
        np.testing.assert_array_equal(x.prompt, y.prompt)


@pytest.mark.parametrize("name", MIXES)
def test_every_seed_gets_the_same_schedule_other_tokens(name):
    mix = traffic.load(name)
    a = traffic.requests(mix, 1, 45, 151936)
    b = traffic.requests(mix, 2**33 + 7, 45, 151936)
    assert [(r.due_s, len(r.prompt), r.max_new_tokens) for r in a] == \
        [(r.due_s, len(r.prompt), r.max_new_tokens) for r in b]
    assert not all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    # the order is shuffled, not sorted
    lens = [len(r.prompt) for r in a]
    assert lens != sorted(lens) and lens != sorted(lens, reverse=True)


def test_chat_mix_grid_clip_and_rate():
    mix = traffic.load("chat")
    seconds = 45
    rs = traffic.requests(mix, 7, seconds, 151936)
    grid = set(mix["prompt_len"]["grid"])
    assert {len(r.prompt) for r in rs} <= grid
    outs = np.array([r.max_new_tokens for r in rs])
    lo, hi = mix["output_len"]["min"], mix["output_len"]["max"]
    assert outs.min() >= lo and outs.max() <= hi
    # the medians the mix states, before snapping and clipping
    assert abs(np.median(outs) - mix["output_len"]["median"]) <= 2
    due = np.array([r.due_s for r in rs])
    assert np.all(np.diff(due) >= 0)
    assert due[0] >= -mix["ramp_s"] and due[-1] < seconds
    n = math.ceil(mix["rate_per_s"] * (mix["ramp_s"] + seconds))
    assert len(rs) == n
    rate = (len(rs) - 1) / (due[-1] - due[0])
    assert abs(rate / mix["rate_per_s"] - 1) < 0.05
    assert all(r.prompt.dtype == np.int32 and r.prompt.max() < 151936
               for r in rs)
    assert all(len(r.prompt) + r.max_new_tokens <= mix["server"]["max_len"]
               for r in rs)


def test_backlog_mix_weights_and_uniform_outputs():
    mix = traffic.load("docqa_backlog")
    rs = traffic.requests(mix, 3, 45, 151936)
    assert len(rs) == mix["n_requests"]
    assert all(r.due_s == 0.0 for r in rs)
    counts = collections.Counter(len(r.prompt) for r in rs)
    for v, w in zip(mix["prompt_len"]["values"],
                    mix["prompt_len"]["weights"]):
        assert abs(counts[v] / len(rs) - w) <= 1.0 / len(rs) + 1e-9
    outs = np.array([r.max_new_tokens for r in rs])
    lo, hi = mix["output_len"]["min"], mix["output_len"]["max"]
    assert outs.min() == lo and outs.max() == hi
    assert abs(outs.mean() - (lo + hi) / 2) < 0.5
    assert all(len(r.prompt) + r.max_new_tokens <= mix["server"]["max_len"]
               for r in rs)


@pytest.mark.parametrize("name", ["b32", "b1"])
def test_image_mixes(name):
    mix = traffic.load(name)
    a = traffic.images(mix, 2**32 + 3)
    b = traffic.images(mix, 2**32 + 3)
    assert len(a) == mix["distinct_batches"]
    shape = (mix["batch"], mix["image_hw"], mix["image_hw"],
             mix["channels"])
    for x, y in zip(a, b):
        assert x.shape == shape and x.dtype == np.float32
        np.testing.assert_array_equal(x, y)
        np.testing.assert_array_equal(x, np.rint(x))
        assert x.min() >= 0 and x.max() <= 255
    assert not np.array_equal(a[0], a[-1])


def test_lognormal_quantiles_are_the_declared_distribution():
    spec = {"dist": "lognormal", "median": 48, "sigma": 0.8,
            "min": 1, "max": 10**6}
    v = traffic.quantiles(spec, 2001).astype(np.float64)
    assert abs(np.median(v) - 48) <= 1
    assert abs(np.std(np.log(v)) - 0.8) < 0.02
