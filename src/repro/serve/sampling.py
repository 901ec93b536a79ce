"""Per-slot seeded sampling: temperature / top-k / top-p over a (B, V) logit row.

Randomness discipline mirrors the per-layer noise planes in
``repro.core.emt_linear``: every draw is a pure counter-hash of

    (request_seed, request_position, vocab_column)

via :mod:`repro.core.hashrng` — no stateful PRNG.  Consequences:

* **deterministic per request** — the tokens a request samples depend only on
  its own seed and how many tokens it has generated, never on which slot it
  landed in, what else is in the batch, or the engine's global step;
* **independent across slots** — two different request seeds index disjoint
  hash streams, so co-scheduled requests do not share randomness.

``temperature == 0`` rows short-circuit to argmax (greedy), making greedy
requests bit-identical to the pre-continuous-batching engine.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import hashrng

# distinct hash plane for sampling draws (layer noise planes are crc32-derived;
# a collision would be harmless anyway — the seed domains differ)
SAMPLING_PLANE = 0x5A3D17


def gumbel_noise(seeds, positions, vocab: int):
    """(B,)x(B,) request seeds/positions -> (B, vocab) Gumbel(0,1) samples."""
    rows = jnp.asarray(positions).astype(jnp.uint32)[:, None]
    cols = jnp.arange(vocab, dtype=jnp.uint32)[None, :]
    bits = hashrng.hash_counters(jnp.asarray(seeds).astype(jnp.uint32)[:, None],
                                 rows, cols, plane=SAMPLING_PLANE)
    # u in (0, 1) at 23-bit precision: float32 has a 24-bit mantissa, so
    # converting wider counters rounds the top values up to exactly 1.0 and
    # makes the Gumbel +inf (breaking top-k/top-p masks with NaN). 23 bits
    # leaves room for the half-offset (max = (2^23-1)+0.5, exactly
    # representable), keeping u strictly inside (0, 1).
    u = ((bits >> 9).astype(jnp.float32) + 0.5) * (1.0 / 8388608.0)
    return -jnp.log(-jnp.log(u))


def greedy_tokens(logits):
    """(B, V) logits -> (B,) int32 argmax: the temperature-0 token."""
    return jnp.argmax(logits.astype(jnp.float32), axis=-1).astype(jnp.int32)


def sample_tokens(logits, temperature, top_k, top_p, seeds, positions):
    """Sample one token per row. All args (B,)-shaped except logits (B, V).

    temperature: 0 -> greedy argmax; >0 -> softmax sampling at that temperature.
    top_k:       0 -> disabled; k>0 -> restrict to the k highest logits.
    top_p:       >=1 (or <=0) -> disabled; else nucleus sampling mass.
    seeds:       per-request sampling seed (uint32).
    positions:   per-request generated-token counter (drives the hash stream).
    """
    B, V = logits.shape
    lf = logits.astype(jnp.float32)
    greedy = greedy_tokens(lf)
    t = jnp.asarray(temperature, jnp.float32)

    def _sampled():
        scaled = lf / jnp.maximum(t, 1e-6)[:, None]

        # top-k: keep logits >= the k-th largest (ties keep extra members —
        # still deterministic)
        k = jnp.asarray(top_k, jnp.int32)
        k = jnp.where(k > 0, jnp.clip(k, 1, V), V)
        srt = jnp.sort(scaled, axis=-1)[:, ::-1]
        kth = jnp.take_along_axis(srt, (k - 1)[:, None], axis=-1)
        masked = jnp.where(scaled >= kth, scaled, -jnp.inf)

        # top-p (nucleus): smallest prefix of the sorted distribution with
        # cumulative mass >= p; `cum - sp < p` always keeps the top-1 token
        p = jnp.asarray(top_p, jnp.float32)
        p = jnp.where((p <= 0.0) | (p >= 1.0), 1.0, p)
        probs = jax.nn.softmax(masked, axis=-1)
        sp = jnp.sort(probs, axis=-1)[:, ::-1]
        cum = jnp.cumsum(sp, axis=-1)
        keep = (cum - sp) < p[:, None]
        pmin = jnp.min(jnp.where(keep, sp, jnp.inf), axis=-1, keepdims=True)
        masked = jnp.where(probs >= pmin, masked, -jnp.inf)

        sampled = jnp.argmax(masked + gumbel_noise(seeds, positions, V),
                             axis=-1).astype(jnp.int32)
        return jnp.where(t > 0.0, sampled, greedy)

    # all-greedy batches (the serving default) skip the two (B,V) sorts and
    # the full-vocab hash — at 256k vocab that is the decode hot path
    return jax.lax.cond(jnp.any(t > 0.0), _sampled, lambda: greedy)
