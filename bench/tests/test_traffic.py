"""The traffic generator: seeds change the tokens and never the work."""

import numpy as np
import pytest

from bench import traffic

VOCAB = 151936


def _mix(name):
    """The mix at a fixed rate, so the checks see enough requests whatever
    rate the cell runs at."""
    return {**traffic.load_mix(name), "rate_per_s": 4.0}


def _sizes(reqs):
    return [(r.prefix_id, len(r.prompt), r.max_new) for r in reqs]


@pytest.mark.parametrize("mix", ["chat-shared", "code-complete"])
def test_open_mix_same_seed_same_schedule(mix):
    m = _mix(mix)
    a = traffic.schedule(m, 2**33 + 17, 20, VOCAB)
    b = traffic.schedule(m, 2**33 + 17, 20, VOCAB)
    for x, y in zip(a.warm + a.window, b.warm + b.window):
        assert x.due_s == y.due_s and x.max_new == y.max_new
        np.testing.assert_array_equal(x.prompt, y.prompt)


@pytest.mark.parametrize("mix", ["chat-shared", "code-complete"])
def test_open_mix_seeds_share_sizes_and_arrivals(mix):
    m = _mix(mix)
    a = traffic.schedule(m, 1, 20, VOCAB)
    b = traffic.schedule(m, 4_000_000_007, 20, VOCAB)
    assert [r.due_s for r in a.window] == [r.due_s for r in b.window]
    assert _sizes(a.window) == _sizes(b.window)
    assert all(not np.array_equal(x.prompt, y.prompt)
               for x, y in zip(a.window, b.window))
    assert len(a.window) == round(m["rate_per_s"] * 20)
    assert all(0 <= r.due_s < 20 for r in a.window)


def test_chat_mix_shapes():
    m = _mix("chat-shared")
    s = traffic.schedule(m, 5, 30, VOCAB)
    pre = m["prefix"]["tokens"]
    for r in s.window:
        assert len(r.prompt) % m["round_to"] == 0
        suffix = len(r.prompt) - pre
        assert m["prompt"]["min"] <= suffix <= m["prompt"]["max"]
        assert m["output"]["min"] <= r.max_new <= m["output"]["max"]
        assert 0 <= r.prefix_id < m["prefix"]["count"]
    # requests that draw the same system prompt share its tokens
    by_prefix = {}
    for r in s.window:
        by_prefix.setdefault(r.prefix_id, []).append(r.prompt[:pre])
    for prompts in by_prefix.values():
        for p in prompts[1:]:
            np.testing.assert_array_equal(p, prompts[0])
    # Zipf: the first system prompt is the most popular
    counts = np.bincount([r.prefix_id for r in s.window],
                         minlength=m["prefix"]["count"])
    assert counts[0] == counts.max()

