"""Continuous-batching serving engine: mid-decode admission equivalence,
per-request energy accounting, and per-slot seeded sampling."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import lm
from repro.nn.param import init_params
from repro.serve.engine import ServingEngine, GenRequest
from repro.serve.sampling import sample_tokens


def _cfg(num_layers=6):
    # gemma3 smoke: 5 local (ring, window 8) + 1 global layer — exercises both
    # vectorized decode cache paths
    cfg = get_config("gemma3-1b", emt_mode="analog", smoke=True)
    return cfg.replace(dtype=jnp.float32, num_layers=num_layers)


@pytest.fixture(scope="module")
def setup():
    cfg = _cfg()
    params = init_params(lm.specs(cfg), jax.random.PRNGKey(0))
    return cfg, params


def _solo_tokens(cfg, params, req, *, max_len=24, seed=7):
    """Run one request alone on a fresh single-slot engine."""
    eng = ServingEngine(cfg, params, batch_size=1, max_len=max_len, seed=seed,
                        fresh_noise=False)
    eng.submit(req)
    (res,) = eng.drain()
    return res.tokens


def test_midstream_admission_matches_solo_and_energy_splits(setup):
    """A request admitted mid-decode (other slots at different positions)
    generates exactly the tokens it generates alone at temperature 0, and the
    per-request energies sum to the engine's total."""
    cfg, params = setup
    rng = np.random.default_rng(0)
    reqs = [
        GenRequest(prompt=rng.integers(0, cfg.vocab_size, 5).astype(np.int32),
                   max_new=6),
        GenRequest(prompt=rng.integers(0, cfg.vocab_size, 3).astype(np.int32),
                   max_new=8),
        GenRequest(prompt=rng.integers(0, cfg.vocab_size, 7).astype(np.int32),
                   max_new=5),
    ]
    # frozen noise: generation is a pure function of the request, so solo and
    # staggered runs see identical EMT fluctuation (analog mode, energy > 0)
    eng = ServingEngine(cfg, params, batch_size=2, max_len=24, seed=7,
                        fresh_noise=False)
    results = []
    eng.submit(reqs[0])
    results += eng.step()            # admits r0, decodes
    results += eng.step()
    eng.submit(reqs[1])              # r1 backfills while r0 is mid-decode
    results += eng.step()
    positions = {s.rid: s.pos for _, s in eng.scheduler.active_slots()}
    assert len(positions) == 2 and len(set(positions.values())) == 2, \
        f"slots should be mid-decode at different positions: {positions}"
    eng.submit(reqs[2])              # queued until a slot retires
    results += eng.drain()

    assert sorted(r.rid for r in results) == [0, 1, 2]
    by_rid = {r.rid: r for r in results}
    for rid, req in enumerate(reqs):
        solo = _solo_tokens(cfg, params, req)
        np.testing.assert_array_equal(by_rid[rid].tokens, solo)
        assert len(by_rid[rid].tokens) == req.max_new
        assert by_rid[rid].energy_pj > 0
        assert by_rid[rid].prefill_energy_pj > 0

    # conservation: per-request energy + idle-slot waste == engine total
    total = sum(r.energy_pj for r in results) + eng.idle_energy_pj
    np.testing.assert_allclose(total, eng.total_energy_pj, rtol=1e-6)


def test_generate_backcompat_and_eos(setup):
    cfg, params = setup
    rng = np.random.default_rng(1)
    reqs = [GenRequest(prompt=rng.integers(0, cfg.vocab_size, 4)
                       .astype(np.int32), max_new=4) for _ in range(2)]
    eng = ServingEngine(cfg, params, batch_size=2, max_len=16, seed=3)
    outs1, e1 = eng.generate(reqs)
    outs2, e2 = eng.generate(reqs)      # noise clock resets: bit-identical
    for a, b in zip(outs1, outs2):
        np.testing.assert_array_equal(a, b)
    assert e1 > 0 and abs(e1 - e2) / e1 < 1e-6

    # eos stops early: use the first generated token as the eos id.  The
    # same pair is resubmitted because the analog DAC scale is per tensor
    # over the batch, so a row's tokens depend on its co-tenants: only the
    # co-tenant set generate() ran with reproduces that first token.
    eos = int(outs1[0][0])
    eng2 = ServingEngine(cfg, params, batch_size=2, max_len=16, seed=3)
    rid = eng2.submit(GenRequest(prompt=reqs[0].prompt, max_new=4,
                                 eos_id=eos))
    eng2.submit(reqs[1])
    res = {r.rid: r for r in eng2.drain()}[rid]
    assert res.done_reason == "eos" and len(res.tokens) == 1


def test_temperature_sampling_deterministic_per_seed_and_varies(setup):
    """temperature > 0 is honored: same request seed -> same tokens; different
    seeds -> different streams. Deterministic regardless of slot placement."""
    cfg, params = setup
    rng = np.random.default_rng(2)
    prompt = rng.integers(0, cfg.vocab_size, 4).astype(np.int32)

    def run(seed, batch_size=1):
        eng = ServingEngine(cfg, params, batch_size=batch_size, max_len=16,
                            seed=11, fresh_noise=False)
        eng.submit(GenRequest(prompt=prompt, max_new=8, temperature=1.5,
                              seed=seed))
        (res,) = eng.drain()
        return res.tokens

    a1, a2 = run(seed=123), run(seed=123)
    np.testing.assert_array_equal(a1, a2)
    b = run(seed=456)
    assert not np.array_equal(a1, b), "different sampling seeds must diverge"
    # slot-placement independence: same request in a wider batch
    np.testing.assert_array_equal(a1, run(seed=123, batch_size=2))
    # and it actually sampled something non-greedy somewhere
    eng = ServingEngine(cfg, params, batch_size=1, max_len=16, seed=11,
                        fresh_noise=False)
    eng.submit(GenRequest(prompt=prompt, max_new=8, temperature=0.0))
    (res,) = eng.drain()
    assert not np.array_equal(res.tokens, a1)


def test_energy_conservation_paged(setup):
    """Per-request + idle == total still holds under paged serving when
    requests hold different block counts (mixed prompt buckets / decode
    budgets), and every request is billed a positive energy."""
    cfg, params = setup
    rng = np.random.default_rng(4)
    # bucket 4 vs bucket 8 prompts, short vs long decode: 2 vs 4 global blocks
    reqs = [
        GenRequest(prompt=rng.integers(0, cfg.vocab_size, 3).astype(np.int32),
                   max_new=3),
        GenRequest(prompt=rng.integers(0, cfg.vocab_size, 8).astype(np.int32),
                   max_new=8),
        GenRequest(prompt=rng.integers(0, cfg.vocab_size, 5).astype(np.int32),
                   max_new=5),
    ]
    eng = ServingEngine(cfg, params, batch_size=2, max_len=24, seed=7,
                        fresh_noise=False, paged=True, block_size=4)
    results = eng.serve(reqs, stagger=2)
    assert sorted(r.rid for r in results) == [0, 1, 2]
    counts = {r.rid: len(r.tokens) for r in results}
    assert counts == {0: 3, 1: 8, 2: 5}
    for r in results:
        assert r.energy_pj > 0 and r.prefill_energy_pj > 0
    total = sum(r.energy_pj for r in results) + eng.idle_energy_pj
    np.testing.assert_allclose(total, eng.total_energy_pj, rtol=1e-6)
    # all blocks back and zeroed once everything retired
    eng.kv.check()
    assert eng.kv.pool_g.num_free == eng.kv.pool_g.num_blocks


def test_retired_slot_region_zeroed(setup):
    """Regression for the latent backfill bug: a retired slot's contiguous
    cache region must be zeroed at retirement, not merely overwritten by the
    next admission's full-region scatter (partial/paged inserts would
    otherwise read the previous request's stale K/V)."""
    cfg, params = setup
    rng = np.random.default_rng(6)
    eng = ServingEngine(cfg, params, batch_size=2, max_len=16, seed=3,
                        fresh_noise=False)
    eng.submit(GenRequest(prompt=rng.integers(0, cfg.vocab_size, 5)
                          .astype(np.int32), max_new=3))
    eng.drain()
    for name, blk in eng.cache.items():
        for key, arr in blk.items():
            assert float(jnp.abs(arr[0]).max()) == 0.0, \
                f"stale data left in slot 0 of {name}/{key} after retirement"


def test_sample_tokens_unit():
    logits = jnp.asarray(np.random.default_rng(0).normal(size=(4, 32)),
                         jnp.float32)
    seeds = jnp.asarray([1, 2, 3, 4], jnp.uint32)
    pos = jnp.zeros(4, jnp.int32)
    greedy = np.argmax(np.asarray(logits), -1)

    def run(t, k=0, p=1.0):
        return np.asarray(sample_tokens(
            logits, jnp.full(4, t, jnp.float32), jnp.full(4, k, jnp.int32),
            jnp.full(4, p, jnp.float32), seeds, pos))

    np.testing.assert_array_equal(run(0.0), greedy)          # temp 0 = argmax
    np.testing.assert_array_equal(run(5.0, k=1), greedy)     # top-k=1 = argmax
    np.testing.assert_array_equal(run(5.0, p=1e-6), greedy)  # tiny nucleus
    np.testing.assert_array_equal(run(2.0), run(2.0))        # deterministic
    # position advances the stream
    moved = np.asarray(sample_tokens(
        logits, jnp.full(4, 5.0, jnp.float32), jnp.zeros(4, jnp.int32),
        jnp.ones(4, jnp.float32), seeds, pos + 1))
    assert not np.array_equal(run(5.0), moved)
