"""Symmetric fake-quantization with straight-through estimators.

The paper fine-tunes with quantized weights *and* activations (§5).  Cells store a
bounded conductance, so weights are quantized to ``w_bits`` symmetric integer levels;
activations (the analog input lines / DAC levels) to ``a_bits`` levels.  Technique C
additionally requires activations as explicit integer levels so they can be read out
bit-serially (see :mod:`repro.core.decompose`).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class QuantConfig:
    w_bits: int = 8
    a_bits: int = 8
    enabled: bool = True
    # per-channel scales for weights (last dim), per-tensor for activations
    per_channel: bool = True
    # Per-row (per-sample) activation DAC scale instead of per-tensor: each
    # token's input-line levels are scaled by its own max, so quantization
    # never couples co-tenant batch rows.  The per-tensor default is the
    # paper's model (one shared DAC reference per array read) but makes token
    # streams occupancy-sensitive at the LSB in serving (ROADMAP "Known
    # subtlety"); enable this for occupancy-independent analog serving.
    a_per_row: bool = False


def _ste(x, q):
    """Straight-through: forward q, backward identity."""
    return x + jax.lax.stop_gradient(q - x)


def scale_from_amax(amax, bits, eps=1e-8):
    qmax = 2.0 ** (bits - 1) - 1.0
    return jnp.maximum(amax, eps) / qmax


def symmetric_scale(x, bits, axis=None, eps=1e-8):
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=axis is not None)
    return scale_from_amax(amax, bits, eps)


def fake_quant(x, bits, axis=None, scale=None):
    """Quantize-dequantize with STE. Returns (x_q_dequant, scale).

    ``scale`` (optional) is a precomputed :func:`symmetric_scale` of ``x``
    over ``axis``; the elementwise quantize is the same either way."""
    qmax = 2.0 ** (bits - 1) - 1.0
    if scale is None:
        scale = symmetric_scale(x, bits, axis=axis)
    scale = jax.lax.stop_gradient(scale)
    q = jnp.clip(jnp.round(x / scale), -qmax, qmax)
    return _ste(x, q * scale), scale


def quant_levels(x, bits, axis=None):
    """Integer levels + scale (no dequant); levels in [-qmax, qmax].

    Forward: rounded integers. Backward: d(levels)/dx = 1/scale via STE, so training
    through the bit-serial path still works.
    """
    qmax = 2.0 ** (bits - 1) - 1.0
    scale = jax.lax.stop_gradient(symmetric_scale(x, bits, axis=axis))
    q = jnp.clip(jnp.round(x / scale), -qmax, qmax)
    q = _ste(x / scale, q)
    return q, scale


def quantize_weights(w, cfg: QuantConfig, amax=None):
    """Fake-quantize a weight to ``cfg.w_bits`` levels.

    ``amax`` (optional) is the weight's precomputed per-column ``max|w|``
    (over all axes but the last, kept as size-1 axes): the scale is then
    derived from it instead of reducing ``w`` again.  A per-tensor scale
    takes the max of the column maxes, which is the same number."""
    if not cfg.enabled:
        return w, None
    axis = tuple(range(w.ndim - 1)) if cfg.per_channel else None
    scale = None
    if amax is not None:
        scale = scale_from_amax(amax if cfg.per_channel else jnp.max(amax),
                                cfg.w_bits)
    wq, scale = fake_quant(w, cfg.w_bits, axis=axis, scale=scale)
    return wq, scale


def quantize_activations(x, cfg: QuantConfig):
    if not cfg.enabled:
        return x, None
    xq, scale = fake_quant(x, cfg.a_bits, axis=None)
    return xq, scale
