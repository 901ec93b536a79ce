"""Counter-hash RNG: determinism, marginals, plane independence."""
import jax.numpy as jnp
import numpy as np

from repro.core import hashrng
from repro.core.device import DeviceModel, four_state_device


def test_deterministic_and_coordinate_stable():
    a = hashrng.tile_uniform_bits(7, 0, 0, (64, 64))
    b = hashrng.tile_uniform_bits(7, 0, 0, (64, 64))
    assert bool(jnp.all(a == b))
    # a shifted-origin tile reproduces the overlapping region exactly
    big = hashrng.tile_uniform_bits(7, 0, 0, (64, 64))
    sub = hashrng.tile_uniform_bits(7, 16, 32, (16, 16))
    assert bool(jnp.all(big[16:32, 32:48] == sub))


def test_seed_and_plane_change_stream():
    a = hashrng.tile_uniform_bits(1, 0, 0, (32, 32))
    b = hashrng.tile_uniform_bits(2, 0, 0, (32, 32))
    c = hashrng.tile_uniform_bits(1, 0, 0, (32, 32), plane=1)
    assert float(jnp.mean((a == b).astype(jnp.float32))) < 0.01
    assert float(jnp.mean((a == c).astype(jnp.float32))) < 0.01


def test_uniformity():
    bits = hashrng.tile_uniform_bits(3, 0, 0, (256, 256))
    u = np.asarray(bits).astype(np.float64) / 2**32
    assert abs(u.mean() - 0.5) < 0.01
    assert abs(u.std() - np.sqrt(1 / 12)) < 0.01
    # bit balance on low bit
    assert abs(np.mean(np.asarray(bits) & 1) - 0.5) < 0.01


def test_state_probabilities_two_and_four():
    for dev in (DeviceModel(), four_state_device()):
        offs = hashrng.tile_state_offsets(11, 0, 0, (512, 512),
                                          dev.state_offsets, dev.state_probs)
        offs = np.asarray(offs)
        for target, p in zip(dev.state_offsets, dev.state_probs):
            frac = np.mean(np.isclose(offs, target, atol=1e-6))
            assert abs(frac - p) < 0.01, (target, frac, p)
        # empirical moments ~ (0, 1)
        assert abs(offs.mean()) < 0.01
        assert abs(offs.std() - 1.0) < 0.01


def test_bits_to_state_matches_direct_cast():
    """The kernel-safe conversion (two exact 16-bit halves) gives the same
    u as a direct uint32 -> float32 cast, so states stay bit-identical."""
    rng = np.random.default_rng(0)
    edge = np.array([0, 1, 2**16 - 1, 2**16, 2**24 - 1, 2**24, 2**24 + 1,
                     2**24 + 3, 2**31 - 1, 2**31, 2**32 - 129, 2**32 - 128,
                     2**32 - 1], np.uint64)
    words = np.concatenate([edge, rng.integers(0, 2**32, 1 << 16,
                                               dtype=np.uint64)])
    words = words.astype(np.uint32)
    for dev in (DeviceModel(), four_state_device()):
        got = np.asarray(hashrng.bits_to_state(jnp.asarray(words),
                                               dev.state_probs))
        u = words.astype(np.float32) * np.float32(1.0 / 2**32)
        want = np.zeros(words.shape, np.int32)
        cum = 0.0
        for i, p in enumerate(dev.state_probs[:-1]):
            cum += p
            want = np.where(u >= np.float32(cum), i + 1, want)
        np.testing.assert_array_equal(got, want)
