"""Pallas TPU kernel — fused noisy-crossbar matmul (technique A forward).

Computes  y = x @ (w_q * (1 + a_l(state) * sigma_rel(rho)))  with the RTN state
sampled *inside the kernel* from the counter-hash RNG: noise never exists in HBM.

TPU mapping (DESIGN.md §3):
* grid = (M/bm, N/bn, K/bk); the K dimension is innermost so the fp32 accumulator
  tile stays resident in VMEM across K steps (revisiting semantics of out_specs).
* Block shapes are multiples of 128 to line up with MXU tiles / VREG lanes.
* The hash RNG is evaluated on the (bk, bn) weight tile from its *global* element
  coordinates, so the result is bit-identical to the jnp reference (ref.py) and
  invariant to the chosen block decomposition and to SPMD sharding.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import hashrng
from repro.core.device import DeviceModel


def _kernel(x_ref, w_ref, sig_ref, o_ref, *, bk, seed, plane, device, k0_base):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    k = pl.program_id(2)
    j = pl.program_id(1)
    x = x_ref[...]
    w = w_ref[...]
    sig = sig_ref[0]
    # global coordinates of this weight tile
    row0 = k0_base + k * bk
    col0 = j * w.shape[1]
    offs = hashrng.tile_state_offsets(
        seed, row0, col0, w.shape, device.state_offsets, device.state_probs,
        plane=plane)
    wn = (w.astype(jnp.float32) * (1.0 + offs * sig)).astype(w.dtype)
    o_ref[...] += jnp.dot(x, wn, preferred_element_type=jnp.float32)


def emt_matmul_pallas(x, w, rho, *, device: DeviceModel, seed=0, plane=0,
                      bm=128, bn=128, bk=128, interpret=False):
    """x: (M, K) float; w: (K, N); rho: scalar -> (M, N) float32."""
    m, kdim = x.shape
    k2, n = w.shape
    assert kdim == k2, (x.shape, w.shape)
    assert m % bm == 0 and n % bn == 0 and kdim % bk == 0, \
        f"shapes {(m, kdim, n)} must tile by {(bm, bk, bn)}"
    grid = (m // bm, n // bn, kdim // bk)
    # sigma_rel(rho) is evaluated outside the kernel: the TPU kernel compiler
    # has no pow, and the reference evaluates it the same way
    sig = device.sigma_rel(jnp.asarray(rho, jnp.float32)).reshape(1)
    return pl.pallas_call(
        functools.partial(_kernel, bk=bk, seed=seed, plane=plane, device=device,
                          k0_base=0),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        interpret=interpret,
        name="emt_matmul",
    )(x, w, sig)
