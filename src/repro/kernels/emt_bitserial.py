"""Pallas TPU kernel — bit-serial decomposed noisy matmul (technique C).

Analog semantics (Fig. 8(b)): the crossbar is read once per activation bit-plane;
every read draws an independent RTN state; plane outputs are accumulated at 2^p.

TPU mapping:
* One kernel invocation per (bm, bn, bk) tile; the **bit loop is innermost and
  unrolled inside the kernel**, so the weight tile is loaded from HBM→VMEM *once*
  and re-read (with fresh in-register noise) `bits` times — the MXU analogue of
  "read the same cell eight times", costing 8x MXU issue but 1x HBM weight traffic.
* Bit-planes are extracted on VREGs from the integer activation levels — the
  (bits, M, K) plane tensor never exists in HBM either.
* Accumulation is fp32 in VMEM across both K-steps and bit-planes.

Inputs are *integer-valued float levels* (from repro.core.quant.quant_levels); sign
is applied to the plane (signed bits in {-1, 0, +1}), matching ref.py exactly.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import hashrng
from repro.core.device import DeviceModel


def _kernel(x_ref, w_ref, sig_ref, o_ref, *, bk, bits, seed, base_plane, device):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    k = pl.program_id(2)
    j = pl.program_id(1)
    xq = x_ref[...].astype(jnp.float32)      # integer-valued levels
    w = w_ref[...]
    sig = sig_ref[0]

    sign = jnp.sign(xq)
    mag = jnp.abs(xq)
    row0 = k * bk
    col0 = j * w.shape[1]

    acc = jnp.zeros(o_ref.shape, jnp.float32)
    for p in range(bits):                    # unrolled bit loop — w tile reused
        offs = hashrng.tile_state_offsets(
            seed, row0, col0, w.shape, device.state_offsets, device.state_probs,
            plane=base_plane + p)
        wn = (w.astype(jnp.float32) * (1.0 + offs * sig)).astype(w.dtype)
        plane_bits = (sign * (jnp.floor(mag / (2.0 ** p)) % 2.0)).astype(w.dtype)
        acc += (2.0 ** p) * jnp.dot(plane_bits, wn,
                                    preferred_element_type=jnp.float32)
    o_ref[...] += acc


def emt_bitserial_pallas(xq, w, rho, *, device: DeviceModel, bits=7, seed=0,
                         base_plane=0, bm=128, bn=128, bk=128, interpret=False):
    """xq: (M, K) integer-valued float levels; w: (K, N) -> (M, N) float32."""
    m, kdim = xq.shape
    k2, n = w.shape
    assert kdim == k2
    assert m % bm == 0 and n % bn == 0 and kdim % bk == 0, \
        f"shapes {(m, kdim, n)} must tile by {(bm, bk, bn)}"
    grid = (m // bm, n // bn, kdim // bk)
    # sigma_rel(rho) is evaluated outside the kernel: the TPU kernel compiler
    # has no pow, and the reference evaluates it the same way
    sig = device.sigma_rel(jnp.asarray(rho, jnp.float32)).reshape(1)
    return pl.pallas_call(
        functools.partial(_kernel, bk=bk, bits=bits, seed=seed,
                          base_plane=base_plane, device=device),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        interpret=interpret,
        name="emt_bitserial_matmul",
    )(xq, w, sig)
