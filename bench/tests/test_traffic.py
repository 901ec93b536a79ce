"""The generator: same work for every seed, in another order."""
import json

import numpy as np

import traffic
from conftest import BENCH


def mix(name):
    return json.loads((BENCH / "traffic" / f"{name}.json").read_text())


def test_same_multiset_other_order():
    m = dict(mix("reason"), in_flight=False)
    a = traffic.generate(m, 1, 25600, 32)
    b = traffic.generate(m, 2 ** 31 + 5, 25600, 32)
    la = sorted((len(r.prompt), r.max_new) for r in a)
    lb = sorted((len(r.prompt), r.max_new) for r in b)
    assert sorted(x for x, _ in la) == sorted(x for x, _ in lb)
    assert sorted(y for _, y in la) == sorted(y for _, y in lb)
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in b]
    again = traffic.generate(m, 1, 25600, 32)
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, again))


def test_prefix_is_stratified():
    for seed in (3, 2 ** 33 + 1):
        idx = traffic.order(512, seed, 2)
        assert sorted(idx) == list(range(512))
        # the first 64 take one quantile out of every 8
        first = np.sort(idx[:64])
        assert np.array_equal(first, np.arange(64) * 8 + first[0])


def test_in_flight_batch():
    m = mix("reason")
    assert m["in_flight"]
    seed, batch, vocab = 2 ** 33 + 7, 32, 25600
    got = traffic.generate(m, seed, vocab, batch)
    plain = traffic.generate(dict(m, in_flight=False), seed, vocab, batch)
    shares = []
    for r, p in zip(got, plain):
        # the written tokens move from the answer into the prompt
        assert len(r.prompt) + r.max_new == len(p.prompt) + p.max_new
        assert np.array_equal(r.prompt[:len(p.prompt)], p.prompt)
        assert r.max_new >= 1 and r.prompt.max() < vocab
        assert len(r.prompt) + r.max_new <= 4096
        shares.append((len(r.prompt) - len(p.prompt)) / p.max_new)
    # the first batch is part-way through at stratified shares, the rest
    # start afresh
    assert np.allclose(sorted(shares[:batch]),
                       (np.arange(batch) + 0.5) / batch, atol=1 / 1024)
    assert not any(shares[batch:])
