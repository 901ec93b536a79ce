"""Jit'd public wrappers around the Pallas kernels.

Handle: leading-dim flattening, padding to MXU-aligned tiles, block-size selection,
and dispatch (TPU pallas / interpret-mode pallas / jnp reference).  All wrappers are
shape-polymorphic at the Python level and jit-stable.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.core.device import DeviceModel
from repro.kernels import ref as kref
from repro.kernels.emt_matmul import emt_matmul_pallas
from repro.kernels.emt_bitserial import emt_bitserial_pallas
from repro.kernels.paged_attention import (NEG_INF, paged_attention_pallas,
                                           paged_attention_decode_pallas)
from repro.kernels.paged_prefill import paged_prefill_pallas


def _pad_to(x, m, axis):
    pad = (-x.shape[axis]) % m
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def pick_blocks(m, k, n, *, vmem_budget=8 * 2 ** 20, dtype_bytes=4, bits=1):
    """Choose (bm, bn, bk) multiples of 128 that keep the working set in VMEM.

    Working set per grid step ≈ bm*bk (x) + bk*bn (w) + bm*bn (fp32 acc) + noise
    regs. We prefer large bk (fewer revisits of the accumulator) then bm.
    """
    bm = min(512, max(128, 128 * (m // 128 or 1)))
    bn = 128
    bk = 128
    def ws(bm, bn, bk):
        return dtype_bytes * (bm * bk + bk * bn + bm * bn)
    while ws(bm, bn, bk * 2) <= vmem_budget and bk * 2 <= k and k % (bk * 2) == 0:
        bk *= 2
    while ws(bm, bn * 2, bk) <= vmem_budget and bn * 2 <= n and n % (bn * 2) == 0:
        bn *= 2
    while ws(bm, bn, bk) > vmem_budget and bm > 128:
        bm //= 2
    return int(min(bm, m)), int(bn), int(bk)


@partial(jax.jit, static_argnames=("device", "seed_static", "plane", "interpret",
                                   "use_ref"))
def emt_matmul(x, w, rho, *, device: DeviceModel, seed_static: int = 0, plane=0,
               interpret=False, use_ref=False):
    """Noisy crossbar matmul: x (..., K) @ w (K, N) with in-kernel RTN noise."""
    lead = x.shape[:-1]
    kdim, n = w.shape
    x2 = x.reshape(-1, kdim)
    if use_ref:
        y = kref.emt_matmul_ref(x2, w, rho, device=device, seed=seed_static,
                                plane=plane)
        return y.reshape(*lead, n)
    m = x2.shape[0]
    bm, bn, bk = pick_blocks(m, kdim, n)
    xp = _pad_to(_pad_to(x2, bm, 0), bk, 1)
    wp = _pad_to(_pad_to(w, bk, 0), bn, 1)
    y = emt_matmul_pallas(xp, wp, rho, device=device, seed=seed_static, plane=plane,
                          bm=bm, bn=bn, bk=bk, interpret=interpret)
    return y[:m, :n].reshape(*lead, n)


@partial(jax.jit, static_argnames=("device", "bits", "seed_static", "base_plane",
                                   "interpret", "use_ref"))
def _bitserial_jit(xq, w, rho, *, device: DeviceModel, bits: int,
                   seed_static: int, base_plane: int, interpret: bool,
                   use_ref: bool):
    lead = xq.shape[:-1]
    kdim, n = w.shape
    x2 = xq.reshape(-1, kdim)
    if use_ref:
        y = kref.emt_bitserial_ref(x2, w, rho, device=device, bits=bits,
                                   seed=seed_static, base_plane=base_plane)
        return y.reshape(*lead, n)
    m = x2.shape[0]
    bm, bn, bk = pick_blocks(m, kdim, n, bits=bits)
    xp = _pad_to(_pad_to(x2, bm, 0), bk, 1)
    wp = _pad_to(_pad_to(w, bk, 0), bn, 1)
    y = emt_bitserial_pallas(xp, wp, rho, device=device, bits=bits,
                             seed=seed_static, base_plane=base_plane,
                             bm=bm, bn=bn, bk=bk, interpret=interpret)
    return y[:m, :n].reshape(*lead, n)


PAGED_ATTN_IMPLS = ("auto", "pallas", "interpret", "ref")


def pick_block_chunk(width: int, block_size: int, *, head_dim: int = 128,
                     kv_heads: int = 1, dtype_bytes: int = 4,
                     vmem_budget: int = 4 * 2 ** 20):
    """Blocks streamed per grid step of the paged attention/prefill kernels.

    Occupancy-aware: ``width`` is the (clamped) block-table width — the
    serving engine shrinks it each step to the block-rounded bucket of the
    furthest live position (lm.clamped_lens), so table width tracks cache
    occupancy.  Low occupancy -> narrow table -> the whole view fits one
    grid step (no online-softmax corrections, no double-buffer churn); a
    full table walks in ~512-position chunks — large enough to amortize the
    recurrence and keep the MXU fed per score matmul, small enough that the
    double buffer (2 slots x K+V tiles) stays well inside the VMEM budget.

    Every kv head of a block shares one grid step (the kernels DMA whole
    (block_size, kv_heads, head_dim) blocks), so a position costs
    ``kv_heads * head_dim`` elements of VMEM.

    Returns a power of two so padded table widths stay minimal.
    """
    if width <= 0:
        return 1
    # positions the VMEM budget allows per slot-pair: 2 slots x 2 arrays
    pos_budget = max(block_size, vmem_budget //
                     (4 * kv_heads * head_dim * dtype_bytes))
    span_cap = max(block_size, min(512, pos_budget))
    cpb = max(1, span_cap // block_size)
    cpb = 1 << (cpb.bit_length() - 1)                  # floor to pow2
    width_pow2 = 1 << (int(width) - 1).bit_length()    # ceil to pow2
    return int(min(cpb, width_pow2))


def _pad_view(table, mask, k_pool, cpb):
    """Pad the block table (zero block) and mask rows (NEG_INF) to a
    block-chunk multiple — padded chunks read the zero block and contribute
    exact zeros."""
    T = table.shape[1]
    pad = (-T) % cpb
    if pad:
        zero_blk = k_pool.shape[0] - 1
        table = jnp.pad(table, ((0, 0), (0, pad)), constant_values=zero_blk)
        if mask is not None:
            bs = k_pool.shape[1]
            mask = jnp.pad(mask, ((0, 0), (0, pad * bs)),
                           constant_values=NEG_INF)
    return table, mask


def default_paged_impl() -> str:
    """Resolve the "auto" paged-attention impl for this process: compiled
    pallas on TPU, the jnp reference elsewhere (interpret mode is an
    emulator — correct everywhere, fast nowhere; tests opt into it)."""
    return "pallas" if jax.default_backend() == "tpu" else "ref"


@partial(jax.jit, static_argnames=("softcap", "impl"))
def paged_attention(q, k_pool, v_pool, table, mask, *, softcap=0.0,
                    impl="ref"):
    """Fused paged-attention decode — jit-stable wrapper + dispatch.

    q (B, KV, G, hd) post-RoPE query token per row; k_pool/v_pool
    (num_blocks + 1, block_size, KV, hd) serving pools (zero block last);
    table (B, T) int32 block rows (possibly length-clamped); mask (B, L)
    additive fp32 over logical positions, L <= T * block_size.

    The wrapper pads the mask rows up to the block-rounded width T*bs with
    NEG_INF (a ring shorter than one block, say window 8 paged at
    block_size 16, leaves a partial last chunk) — padded lanes read whatever
    the block holds and contribute exact zeros.  Returns (B, KV, G, hd) fp32.
    """
    if impl not in PAGED_ATTN_IMPLS:
        raise ValueError(f"unknown paged-attention impl {impl!r}; "
                         f"known: {PAGED_ATTN_IMPLS}")
    bs = k_pool.shape[1]
    T = table.shape[1]
    L = mask.shape[1]
    assert L <= T * bs, f"mask rows ({L}) exceed the table view ({T}x{bs})"
    mask = mask.astype(jnp.float32)
    if L < T * bs:                           # partial last block: mask it out
        mask = jnp.pad(mask, ((0, 0), (0, T * bs - L)),
                       constant_values=NEG_INF)
    if impl == "ref" or (impl == "auto" and default_paged_impl() == "ref"):
        out = kref.paged_attention_ref(q, k_pool, v_pool, table, mask,
                                       softcap=softcap)
        # Materialization point, matching what the pallas custom-call is on
        # TPU.  Without it XLA (CPU) fuses the reference's masked-softmax
        # arithmetic into downstream reductions (e.g. the EMT DAC per-tensor
        # max) and fully-masked rows (zero-length-encoder slots) come back
        # NaN — the de-optimized graph is clean, so this is purely an XLA
        # rewrite hazard (tests/test_paged_attention.py enc-dec harness).
        return jax.lax.optimization_barrier(out)
    cpb = pick_block_chunk(T, bs, head_dim=q.shape[-1],
                           kv_heads=q.shape[1])
    table, mask = _pad_view(table, mask, k_pool, cpb)
    return paged_attention_pallas(q, k_pool, v_pool, table, mask,
                                  softcap=softcap, block_chunk=cpb,
                                  interpret=(impl == "interpret"))


@partial(jax.jit, static_argnames=("softcap", "impl"))
def paged_attention_decode(q, k_pool, v_pool, table, mask, k_new, v_new,
                           wpos, active, *, softcap=0.0, impl="ref"):
    """One-launch decode: fused KV cache write + paged attention.

    On top of :func:`paged_attention`: k_new/v_new (B, KV, hd) are the
    step's new K/V rows and ``wpos`` (B,) int32 the per-row absolute (or
    ring-wrapped) write position — row b writes them at
    ``pool[table[b, wpos[b] // bs], wpos[b] % bs]`` before attending, iff
    ``active[b]`` (None => all rows write).  The mask rows must already make
    the written position visible (the decode mask does: position index is
    causally visible to itself).

    Returns (out (B, KV, G, hd) fp32, k_pool, v_pool) — the returned pools
    ARE the update (pallas rungs alias them onto the inputs via
    input_output_aliases; the ref rung scatters functionally), bit-identical
    to the legacy scatter-then-attend pair (`attention._paged_write` +
    gather/attend): same cast, same drop semantics for inactive rows.
    """
    if impl not in PAGED_ATTN_IMPLS:
        raise ValueError(f"unknown paged-attention impl {impl!r}; "
                         f"known: {PAGED_ATTN_IMPLS}")
    B = q.shape[0]
    bs = k_pool.shape[1]
    T = table.shape[1]
    L = mask.shape[1]
    assert L <= T * bs, f"mask rows ({L}) exceed the table view ({T}x{bs})"
    mask = mask.astype(jnp.float32)
    if L < T * bs:
        mask = jnp.pad(mask, ((0, 0), (0, T * bs - L)),
                       constant_values=NEG_INF)
    wpos = jnp.asarray(wpos, jnp.int32)
    wblk = jnp.take_along_axis(table, (wpos // bs)[:, None], axis=1)[:, 0]
    wblk = wblk.astype(jnp.int32)
    woff = (wpos % bs).astype(jnp.int32)
    wok = (jnp.ones((B,), jnp.int32) if active is None
           else jnp.asarray(active).astype(jnp.int32))
    k_new = k_new.astype(k_pool.dtype)
    v_new = v_new.astype(v_pool.dtype)
    if impl == "ref" or (impl == "auto" and default_paged_impl() == "ref"):
        out, k_pool, v_pool = kref.paged_attention_decode_ref(
            q, k_pool, v_pool, table, mask, k_new, v_new, wblk, woff, wok,
            softcap=softcap)
        # same XLA CPU rewrite hazard as paged_attention (see above)
        return jax.lax.optimization_barrier((out, k_pool, v_pool))
    cpb = pick_block_chunk(T, bs, head_dim=q.shape[-1],
                           kv_heads=q.shape[1])
    table, mask = _pad_view(table, mask, k_pool, cpb)
    return paged_attention_decode_pallas(
        q, k_pool, v_pool, table, mask, k_new, v_new, wblk, woff, wok,
        softcap=softcap, block_chunk=cpb, interpret=(impl == "interpret"))


@partial(jax.jit, static_argnames=("softcap", "impl"))
def paged_prefill(q, k_pool, v_pool, table, qpos, *, softcap=0.0,
                  impl="ref"):
    """Flash-style chunked prefill through the block table.

    q (B, C, H, hd) post-RoPE query chunk (the chunk's K/V must already be
    written to the pools — write-then-attend, like the legacy path);
    qpos (B, C) int32 absolute per-lane query positions, padding lanes
    clamped to the row's last real lane (lm.chunk_step's convention).
    Causality is derived from qpos — no materialized mask.

    Returns (B, C, H * hd) fp32 — the `_gqa_core` output contract, sans the
    final cache-dtype cast (the caller owns it).
    """
    if impl not in PAGED_ATTN_IMPLS:
        raise ValueError(f"unknown paged-attention impl {impl!r}; "
                         f"known: {PAGED_ATTN_IMPLS}")
    B, C, H, hd = q.shape
    KV = k_pool.shape[2]
    G = H // KV
    bs = k_pool.shape[1]
    T = table.shape[1]
    # regroup (B, C, H, hd) -> (B, KV, C*G, hd): kv head to a grid axis,
    # chunk lanes x group heads fused into the query-tile rows (row c*G + g)
    qt = q.reshape(B, C, KV, G, hd).transpose(0, 2, 1, 3, 4)
    qt = qt.reshape(B, KV, C * G, hd)
    qpe = jnp.repeat(jnp.asarray(qpos, jnp.int32), G, axis=1)   # (B, C*G)
    if impl == "ref" or (impl == "auto" and default_paged_impl() == "ref"):
        out = kref.paged_prefill_ref(qt, k_pool, v_pool, table, qpe,
                                     softcap=softcap)
        out = jax.lax.optimization_barrier(out)
    else:
        cpb = pick_block_chunk(T, bs, head_dim=hd, kv_heads=KV)
        table, _ = _pad_view(table, None, k_pool, cpb)
        qlast = jnp.max(qpe, axis=1).astype(jnp.int32)
        out = paged_prefill_pallas(qt, k_pool, v_pool, table, qpe, qlast,
                                   softcap=softcap, block_chunk=cpb,
                                   interpret=(impl == "interpret"))
    out = out.reshape(B, KV, C, G, hd).transpose(0, 2, 1, 3, 4)
    return out.reshape(B, C, H * hd)


def emt_bitserial_matmul(xq, w, rho, *, device: DeviceModel, bits=7, seed=0,
                         base_plane=0, interpret=False, use_ref=False):
    """Bit-serial decomposed noisy matmul (technique C). xq: integer-valued levels."""
    return _bitserial_jit(xq, w, rho, device=device, bits=bits,
                          seed_static=int(seed) if not hasattr(seed, "dtype") else 0,
                          base_plane=base_plane, interpret=interpret, use_ref=use_ref)
