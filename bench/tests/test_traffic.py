"""The traffic generator: seeds change the tokens and never the work."""

import hashlib
import json
import pathlib

import numpy as np
import pytest

from bench import traffic

VOCAB = 151936
DATA = pathlib.Path(__file__).parent / "data"


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


def test_open_schedule_is_the_recorded_one():
    """An open mix draws byte for byte the schedule it drew before the
    closed kind was added (``data/dense_recorded.json``)."""
    recorded = json.loads((DATA / "dense_recorded.json").read_text())
    for key, (n_warm, n_window, digest) in recorded["schedules"].items():
        mix, seed = key.split("@")
        s = traffic.schedule(traffic.load_mix(mix), int(seed), 45, VOCAB)
        h = hashlib.sha256()
        for r in s.warm + s.window:
            h.update(np.float64(r.due_s).tobytes())
            h.update(np.int64(r.max_new).tobytes())
            h.update(np.int64(r.prefix_id).tobytes())
            h.update(np.asarray(r.prompt, np.int32).tobytes())
        assert (len(s.warm), len(s.window), h.hexdigest()) == (
            n_warm, n_window, digest), key


def test_closed_mix_seeds_change_tokens_not_sizes():
    m = traffic.load_mix("doc-decode")
    a = traffic.ClosedUsers(m, 1, VOCAB)
    b = traffic.ClosedUsers(m, 4_000_000_007, VOCAB)
    again = traffic.ClosedUsers(m, 1, VOCAB)
    sizes = set()
    for u in range(m["users"]):
        for k in range(4):
            x, y = a.request(u, k), b.request(u, k)
            assert (len(x.prompt), x.max_new) == (len(y.prompt), y.max_new)
            assert not np.array_equal(x.prompt, y.prompt)
            np.testing.assert_array_equal(x.prompt, again.request(u, k).prompt)
            assert len(x.prompt) % m["round_to"] == 0
            assert m["prompt"]["min"] <= len(x.prompt) <= m["prompt"]["max"]
            assert m["output"]["min"] <= x.max_new <= m["output"]["max"]
            assert x.prefix_id == -1
            sizes.add((len(x.prompt), x.max_new))
    assert len(sizes) > 8   # users and their requests differ in size
    # a user's requests come in the order of their place in its sequence
    for k in range(3):
        np.testing.assert_array_equal(a.next_request(2).prompt,
                                      a.request(2, k).prompt)
