"""Technique C — low-fluctuation bit-serial decomposition (paper §4.3).

An activation quantized to integer level ``q`` is fed to the crossbar one binary
digit at a time (Eq. 14): ``x = sum_p delta_p 2^p``.  Each bit-plane read draws an
*independent* fluctuation sample ``w(p)`` (independent RTN states), so the
accumulated output

    O_new = sum_p 2^p * delta_p * w(p)

has std ``sqrt(sum 4^p delta_p^2) * sigma(w)`` — strictly below the single-read std
``(sum 2^p delta_p) * sigma(w)`` whenever more than one bit is set (Eqs. 16-18) —
and energy ``rho * sum_p delta_p`` below ``rho * x`` (Eqs. 19-20).

The jnp implementation here is the *oracle* for the Pallas kernel
(:mod:`repro.kernels.emt_bitserial`) and the reference path used by dry-runs.
Backward pass: the decomposition is a zero-mean perturbation of the ideal matmul, so
we give it the ideal-matmul VJP (standard noise-STE; see DESIGN.md §3).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import hashrng
from repro.core.device import DeviceModel


def bit_plane(mag, p):
    """delta_p of non-negative integer-valued float array `mag` (paper Eq. 14)."""
    return jnp.floor(mag / (2.0 ** p)) % 2.0


def popcount_levels(mag, bits):
    """sum_p delta_p  — number of crossbar reads a level costs (Eq. 19)."""
    return sum(bit_plane(mag, p) for p in range(bits))


def sigma_ratio_theory(levels, bits):
    """Per-element theoretical sigma(O_new)/sigma(O_ori) from Eqs. 16-17.

    levels: non-negative integer-valued array. Returns ratio (1.0 where level==0 or a
    single bit is set — decomposition only helps multi-bit levels).
    """
    num = jnp.zeros_like(levels, dtype=jnp.float32)
    den = jnp.zeros_like(levels, dtype=jnp.float32)
    for p in range(bits):
        d = bit_plane(levels, p).astype(jnp.float32)
        num = num + (4.0 ** p) * d
        den = den + (2.0 ** p) * d
    return jnp.where(den > 0, jnp.sqrt(num) / jnp.maximum(den, 1e-9), 1.0)


def _bitserial_fwd(xq, w, rho, device: DeviceModel, bits: int, seed, base_plane):
    """Core loop: xq integer levels (may be negative), w already quantized.

    Per plane p: independent hash-noise draw on w, matmul of the signed bit-plane,
    scaled 2^p accumulation (exactly the analog timing diagram of Fig. 8(b)).
    """
    sign = jnp.sign(xq)
    mag = jnp.abs(xq)
    k, n = w.shape[-2], w.shape[-1]
    sig = device.sigma_rel(rho)
    acc = None
    for p in range(bits):
        with jax.named_scope("emt.noise"):
            offs = hashrng.tile_state_offsets(
                seed, 0, 0, (k, n), device.state_offsets, device.state_probs,
                plane=base_plane + p)
            wn = w * (1.0 + offs.astype(w.dtype) * sig.astype(w.dtype))
        with jax.named_scope("emt.crossbar"):
            planes = (sign * bit_plane(mag, p)).astype(w.dtype)
            term = (2.0 ** p) * jnp.matmul(planes, wn)
            acc = term if acc is None else acc + term
    return acc


@partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def bitserial_matmul_ref(xq, w, rho, device: DeviceModel, bits: int,
                         seed=0, base_plane=0):
    """y = bit-serial noisy matmul; oracle for the Pallas kernel.

    xq: (..., K) integer-valued float levels; w: (K, N); rho: scalar.
    """
    return _bitserial_fwd(xq, w, rho, device, bits, seed, base_plane)


def _fwd(xq, w, rho, device, bits, seed, base_plane):
    y = _bitserial_fwd(xq, w, rho, device, bits, seed, base_plane)
    return y, (xq, w, rho)


def _bwd(device, bits, res, g):
    # Ideal-matmul VJP (noise treated as zero-mean data perturbation).
    xq, w, rho = res
    gx = jnp.matmul(g, w.T).astype(xq.dtype)
    lead = int(np.prod(xq.shape[:-1]))
    gw = jnp.matmul(xq.reshape(lead, -1).T.astype(jnp.float32),
                    g.reshape(lead, -1).astype(jnp.float32)).astype(w.dtype)
    return gx, gw, jnp.zeros_like(rho), None, None


bitserial_matmul_ref.defvjp(_fwd, _bwd)
