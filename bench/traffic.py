"""Traffic generation: one general generator, driven by a mix's data file.

A mix lives in ``bench/traffic/<name>.json``.  Its ``kind`` is one of:

* ``open``: independent users, whose requests are due on a fixed schedule
  whether or not earlier ones have finished, arriving as a Poisson process
  at ``rate_per_s``;
* ``closed``: ``users`` callers that each keep one request in flight.  User
  *i* sends its first request ``i × stagger_s`` after warm-up starts and
  each next one when the last has finished, with no think time; a request
  is due when it is sent.

Sizes are data too: ``prompt`` and ``output`` are clipped lognormals
(``median``, ``sigma``, ``min``, ``max``); ``prefix`` (optional) prepends one
of ``count`` shared system prompts of ``tokens`` tokens, chosen by Zipf
(``zipf_s``); ``round_to`` rounds every prompt length up to a multiple.

The seed changes the tokens, never the work: the arrival times and the
sequence of (prefix, prompt length, output length) are drawn from the mix's
own ``base_seed`` (and, in a closed mix, the user and the request's place in
its sequence), and the run's seed draws only the token ids.  Runs with
different seeds therefore carry the same requests at the same times, so
their spread measures the system, not the draw.
"""

from __future__ import annotations

import dataclasses
import json
import pathlib
import numpy as np

MIX_DIR = pathlib.Path(__file__).resolve().parent / "traffic"
KINDS = ("open", "closed")


@dataclasses.dataclass(frozen=True)
class RequestSpec:
    """One request as the generator makes it: due ``due_s`` seconds after
    its phase starts."""

    prompt: np.ndarray  # [P] int32
    max_new: int
    due_s: float = 0.0
    prefix_id: int = -1


@dataclasses.dataclass(frozen=True)
class Schedule:
    """The requests of one open run: ``warm`` (served in set-up, not
    measured) and ``window`` (the measured phase)."""

    warm: tuple
    window: tuple


def load_mix(name: str, mix_dir: pathlib.Path = MIX_DIR) -> dict:
    with open(mix_dir / f"{name}.json") as f:
        mix = json.load(f)
    if mix.get("kind") not in KINDS:
        raise ValueError(f"traffic mix {name!r}: unknown kind {mix.get('kind')!r}")
    return mix


def _seed_words(*parts: int) -> np.random.Generator:
    """A generator keyed by any number of non-negative integers (seeds may
    exceed 32 bits)."""
    return np.random.default_rng(np.random.SeedSequence([int(p) for p in parts]))


def _lengths(rng: np.random.Generator, dist: dict, n: int,
             round_to: int = 1) -> np.ndarray:
    x = rng.lognormal(np.log(dist["median"]), dist["sigma"], n)
    x = np.clip(np.rint(x), dist["min"], dist["max"]).astype(np.int64)
    return -(-x // round_to) * round_to


def _zipf_choice(rng: np.random.Generator, count: int, s: float,
                 n: int) -> np.ndarray:
    p = np.arange(1, count + 1, dtype=np.float64) ** -s
    return rng.choice(count, size=n, p=p / p.sum())


def _prefixes(mix: dict, seed: int, vocab: int) -> list:
    """The mix's shared system prompts, drawn from the run's seed."""
    pre = mix.get("prefix")
    if not pre:
        return []
    rng = _seed_words(seed, 999)
    return [rng.integers(0, vocab, pre["tokens"], dtype=np.int32)
            for _ in range(pre["count"])]


def _requests(mix: dict, base: np.random.Generator, run: np.random.Generator,
              vocab: int, due, prefixes: list) -> tuple:
    """One request per due time: sizes drawn from ``base``, tokens from
    ``run``."""
    n = len(due)
    prompt_lens = _lengths(base, mix["prompt"], n, mix.get("round_to", 1))
    outputs = _lengths(base, mix["output"], n)
    pre = mix.get("prefix")
    prefix_ids = (_zipf_choice(base, pre["count"], pre["zipf_s"], n)
                  if pre else np.full(n, -1))
    reqs = []
    for i in range(n):
        suffix = run.integers(0, vocab, prompt_lens[i], dtype=np.int32)
        pid = int(prefix_ids[i])
        prompt = suffix if pid < 0 else np.concatenate([prefixes[pid], suffix])
        reqs.append(RequestSpec(prompt=prompt, max_new=int(outputs[i]),
                                due_s=float(due[i]), prefix_id=pid))
    return tuple(reqs)


def _open_phase(mix: dict, seed: int, vocab: int, seconds: float,
                phase: int, prefixes: list) -> tuple:
    """``round(rate × seconds)`` requests due in ``[0, seconds)``: the
    arrival times and sizes come from the mix's base seed and the phase;
    the run's seed draws the tokens."""
    n = int(round(mix["rate_per_s"] * seconds))
    base = _seed_words(mix["base_seed"], phase)
    # n Poisson arrivals conditioned on the count are n uniform order
    # statistics over the phase
    due = np.sort(base.uniform(0.0, seconds, n))
    return _requests(mix, base, _seed_words(seed, phase), vocab, due,
                     prefixes)


def schedule(mix: dict, seed: int, seconds: float, vocab: int) -> Schedule:
    """An open run's requests: ``mix["warmup_s"]`` seconds of warm traffic,
    then ``seconds`` of measured traffic."""
    prefixes = _prefixes(mix, seed, vocab)
    warm = _open_phase(mix, seed, vocab, mix["warmup_s"], 0, prefixes)
    window = _open_phase(mix, seed, vocab, seconds, 1, prefixes)
    return Schedule(warm, window)


class ClosedUsers:
    """A closed mix's users: each one's sequence of requests, drawn as they
    are sent, and the request it has in flight.  One object serves a run's
    warm-up and its window, so the users carry on from one into the other."""

    def __init__(self, mix: dict, seed: int, vocab: int):
        self.mix, self.seed, self.vocab = mix, seed, vocab
        self.count = mix["users"]
        self.prefixes = _prefixes(mix, seed, vocab)
        self.t_start = None                  # when the first phase began
        self.inflight = [None] * self.count  # the Sender's record of each
        self._sent = [0] * self.count

    def start_s(self, user: int) -> float:
        """When ``user`` sends its first request, after ``t_start``."""
        return user * self.mix["stagger_s"]

    def request(self, user: int, k: int) -> RequestSpec:
        """User ``user``'s ``k``-th request: its sizes from the mix's base
        seed, the user and ``k``; its tokens from the run's seed."""
        base = _seed_words(self.mix["base_seed"], user, k)
        run = _seed_words(self.seed, user, k)
        return _requests(self.mix, base, run, self.vocab, [0.0],
                         self.prefixes)[0]

    def next_request(self, user: int) -> RequestSpec:
        k = self._sent[user]
        self._sent[user] += 1
        return self.request(user, k)
