"""The one traffic generator: a mix file of parameters -> seeded requests.

A mix file (``bench/traffic/<name>.json``) gives

* ``loop``: ``"backlog"`` (offline: ``backlog`` requests are kept pending
  beyond the ones being served);
* ``prompt`` and ``output``: lognormal lengths, ``median`` and ``sigma``
  (of the log), clipped to ``[min, max]``;
* ``pool``: how many distinct length pairs the mix holds;
* ``in_flight`` (optional, default false): the window opens on a full
  batch already part-way through its answers.  The first ``batch``
  requests have each written a share of their output, at stratified
  shares ``(k + 0.5) / batch``: those tokens join the prompt (set-up
  prefills them) and ``max_new`` keeps the rest.  A request's context then
  ranges over its whole life from the window's first step, as in a
  backlog that has run for longer than one answer.

Every seed gets the same multiset of lengths and shares, placed at
stratified quantiles of their distributions, in a seed-dependent order
whose every power-of-two prefix still covers the quantiles evenly; the
seed also draws the token ids.  So a window sees the same amount of work
whatever the seed, and seeds differ in order and content.
"""
from __future__ import annotations

import statistics
from typing import List, NamedTuple

import numpy as np


class Request(NamedTuple):
    index: int
    prompt: np.ndarray
    max_new: int


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lengths(dist: dict, n: int) -> np.ndarray:
    """n lognormal lengths at stratified quantiles, clipped, sorted."""
    nd = statistics.NormalDist()
    z = np.array([nd.inv_cdf(q) for q in _quantiles(n)])
    x = np.round(dist["median"] * np.exp(dist["sigma"] * z))
    return np.clip(x, dist["min"], dist["max"]).astype(np.int64)


def order(n: int, seed: int, salt: int) -> np.ndarray:
    """A permutation of range(n): bit-reversed counting, XOR a seeded mask.
    Every prefix of length 2**k visits evenly spaced quantiles."""
    bits = max(1, (n - 1).bit_length())
    size = 1 << bits
    mask = int(np.random.default_rng([seed, salt]).integers(size))
    rev = np.array([int(f"{i:0{bits}b}"[::-1], 2) for i in range(size)])
    perm = rev ^ mask
    return perm[perm < n]


def generate(mix: dict, seed: int, vocab: int, batch: int
             ) -> List[Request]:
    """The ``pool`` requests of one run, in the order they are offered."""
    if mix["loop"] != "backlog":
        raise ValueError(f"unknown loop {mix['loop']!r}")
    rng = np.random.default_rng([seed, 0])
    n = int(mix["pool"])
    p = lengths(mix["prompt"], n)[order(n, seed, 2)]
    o = lengths(mix["output"], n)[order(n, seed, 3)]
    written = np.zeros(n, np.int64)
    if mix.get("in_flight", False):
        k = min(batch, n)
        share = _quantiles(k)[order(k, seed, 4)]
        # at least one token is left to serve
        written[:k] = np.minimum(np.floor(share * o[:k]), o[:k] - 1)
    prompts = [rng.integers(0, vocab, int(x)).astype(np.int32) for x in p]
    # the tokens already written, from a stream of their own
    more = np.random.default_rng([seed, 5])
    return [Request(i, np.concatenate(
                [prompts[i],
                 more.integers(0, vocab, int(written[i])).astype(np.int32)]),
                    int(o[i] - written[i]))
            for i in range(n)]
