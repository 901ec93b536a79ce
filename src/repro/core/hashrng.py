"""Counter-based hash RNG shared by Pallas kernels and pure-jnp reference paths.

TPU co-design: technique A needs a fresh fluctuation sample *per read* of every weight
element.  Materializing those samples with a stateful RNG costs an extra weight-sized
HBM stream per step.  Instead we derive noise as a pure function of

    (seed, plane, global_row, global_col)

with a cheap avalanche hash (two rounds of the murmur3/'lowbias32' finalizer over a
Weyl-sequence counter).  Inside a Pallas kernel the same function runs on VREGs over a
``broadcasted_iota`` — zero HBM traffic; in the jnp reference it lowers to ~10 fused
elementwise uint32 ops.  Kernel and reference are bit-exact by construction.

All functions are usable both inside ``pl.pallas_call`` bodies and in plain jnp code.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

_U = jnp.uint32
# odd constants (murmur3 / splitmix / lowbias32 lineage)
_C1 = 0x9E3779B9
_C2 = 0x85EBCA6B
_C3 = 0xC2B2AE35
_M1 = 0x7FEB352D
_M2 = 0x846CA68B


def _finalize(x):
    x = x ^ (x >> 16)
    x = x * _U(_M1)
    x = x ^ (x >> 15)
    x = x * _U(_M2)
    x = x ^ (x >> 16)
    return x


def hash_counters(seed, row, col, plane=0):
    """Hash integer counter arrays (uint32) into uniform uint32.

    `row`/`col` are arrays (broadcastable); `seed`/`plane` scalars or arrays.
    """
    h = (row.astype(_U) * _U(_C1)) ^ (col.astype(_U) * _U(_C2))
    h = h ^ (_U(plane) * _U(_C3)) ^ _U(seed)
    h = _finalize(h)
    # second round for avalanche quality
    h = _finalize(h ^ _U(0x68E31DA4))
    return h


def tile_uniform_bits(seed, row0, col0, shape, plane=0):
    """uint32 uniform bits for a (rows, cols) tile whose global origin is (row0, col0).

    Works inside Pallas kernels: ``broadcasted_iota`` + elementwise uint ops only.
    """
    rows = jax.lax.broadcasted_iota(_U, shape, 0) + _U(row0)
    cols = jax.lax.broadcasted_iota(_U, shape, 1) + _U(col0)
    return hash_counters(seed, rows, cols, plane)


def bits_to_state(bits, probs):
    """Map uniform uint32 -> categorical state index given static state probs."""
    # u in [0, 1).  The TPU kernel compiler has no uint32 -> float32 cast, so
    # the word converts as two exact 16-bit halves through int32; their f32
    # sum rounds once, to the value a direct cast gives.
    hi = (bits >> 16).astype(jnp.int32).astype(jnp.float32)
    lo = (bits & _U(0xFFFF)).astype(jnp.int32).astype(jnp.float32)
    u = (hi * 65536.0 + lo) * (1.0 / 4294967296.0)
    state = jnp.zeros(bits.shape, jnp.int32)
    cum = 0.0
    for i, p in enumerate(probs[:-1]):
        cum += p
        state = jnp.where(u >= cum, i + 1, state)
    return state


def state_offset_from_bits(bits, offsets, probs):
    """uniform bits -> normalized RTN state offset a_l (float32).

    Uses only scalar literals (no captured constant arrays) so the same code can run
    inside a Pallas kernel body.
    """
    state = bits_to_state(bits, probs)
    out = jnp.full(bits.shape, float(offsets[0]), jnp.float32)
    # small static table: select-chain is cheaper than a gather on TPU VREGs
    for i in range(1, len(offsets)):
        out = jnp.where(state == i, float(offsets[i]), out)
    return out


def tile_state_offsets(seed, row0, col0, shape, offsets, probs, plane=0):
    """Fused: tile coords -> RTN normalized offsets. Pallas- and jnp-safe."""
    return state_offset_from_bits(
        tile_uniform_bits(seed, row0, col0, shape, plane), offsets, probs)
