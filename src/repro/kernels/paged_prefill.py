"""Pallas TPU kernel — flash-style chunked prefill over the paged KV cache.

The chunked-prefill path (`models/attention._chunk_attend`, non-ring layers)
used to write the chunk's K/V into the pool and then *gather the full
(B, view_len, KV, hd) logical view* to attend against — the same
materialize-then-attend waste the fused decode kernel killed for single-token
steps, paid once per layer per chunk.  This kernel attends the chunk's query
tile straight against table-resolved pool tiles with an online softmax: the
view never exists.

Shape story: the (B, C, H, hd) query chunk is regrouped to (B, KV, C * G, hd)
— the C chunk lanes x G group heads collapse into one query-tile row axis,
so each kv head runs a single (C * G, chunk_positions) score matmul per grid
step (C and G are both small; fusing them keeps the MXU fed).  The grid is
(B, kv block chunks); every kv head of a chunk is attended in the same step,
for the tile-alignment reason given in kernels/paged_attention.py.

Causality is derived *in-kernel* from the per-lane query positions instead of
a materialized (B, 1, C, L) mask: kv position p is visible to query row r iff
``p <= qpos[b, r // G]`` — this covers the causal prefix, in-chunk causality
(the chunk's own K/V is written to the pool before the kernel runs), the
clamped-view tail (positions past the view hold qpos < p), and padding lanes
(their qpos is clamped to the row's last real lane, exactly like the legacy
mask built by `lm.chunk_step`).

Speed levers (mirrors kernels/paged_attention.py — see its module docstring):
pools stay in HBM (ANY) with double-buffered ``make_async_copy`` tile DMA,
``block_chunk`` pool blocks stream per grid step, statistics scratch is
(8, 128)-aligned.  One extra lever decode doesn't have: per-row chunk
*skipping*.  A scalar-prefetched ``qlast[b] = max(qpos[b])`` bounds each
row's visible range, and chunks entirely past it are neither copied nor
attended (`@pl.when` on both the DMA start and the compute) — a row early in
its prompt touches only the blocks it can see, which is where the analytic
K/V byte win over the materialized view comes from.

Parity: kernels/ref.py::paged_prefill_ref is the one-shot-softmax oracle
(ulp-level agreement, accumulation order differs); masking semantics are
identical to the decode kernel (NEG_INF sentinel, m_safe guard, exact zeros
for fully-masked rows).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.paged_attention import (NEG_INF, _block_buf, _head_tile,
                                           _pool_block, _stats_rows)


def _prefill_kernel(table_ref, qlast_ref, q_ref, qpos_ref, k_hbm, v_hbm,
                    o_ref, kbuf, vbuf, sem, m_ref, l_ref, acc_ref,
                    *, scale, softcap, cpb, bs, KV, R):
    """One (batch row, kv block chunk) grid step over every kv head;
    R = C * G query rows per head tile."""
    b = pl.program_id(0)
    c = pl.program_id(1)
    C = pl.num_programs(1)
    span = cpb * bs                                   # kv positions per step

    def chunk_needed(ci):
        # chunks strictly past the row's furthest visible position are dead
        return ci * span <= qlast_ref[b]

    def copies(ci, slot):
        for i in range(cpb):
            blk = table_ref[b, ci * cpb + i]
            yield pltpu.make_async_copy(_pool_block(k_hbm, blk, KV),
                                        kbuf.at[slot, i], sem.at[slot, 0, i])
            yield pltpu.make_async_copy(_pool_block(v_hbm, blk, KV),
                                        vbuf.at[slot, i], sem.at[slot, 1, i])

    @pl.when(c == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)
        for cp in copies(0, 0):
            cp.start()

    @pl.when((c + 1 < C) & chunk_needed(c + 1))
    def _prefetch_next():                             # double buffer
        for cp in copies(c + 1, (c + 1) % 2):
            cp.start()

    @pl.when(chunk_needed(c))
    def _attend():
        slot = c % 2
        for cp in copies(c, slot):
            cp.wait()
        # in-kernel causal mask: kv position vs per-row clamped query
        # position, shared by every kv head
        qp = qpos_ref[0, 0][:, None]                  # (R, 1)
        p = c * span + jax.lax.broadcasted_iota(jnp.int32, (R, span), 1)
        mask = jnp.where(p <= qp, 0.0, NEG_INF)
        for h in range(KV):
            k = _head_tile(kbuf, slot, h, KV)         # (span, hd)
            v = _head_tile(vbuf, slot, h, KV)
            q = q_ref[0, h]                           # (R, hd)
            s = jax.lax.dot_general(q, k.astype(q.dtype),
                                    (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32) * scale
            if softcap:
                s = softcap * jnp.tanh(s / softcap)
            s = s + mask

            m_prev = m_ref[h, 0:R]
            l_prev = l_ref[h, 0:R]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            m_safe = jnp.where(m_new > NEG_INF / 2, m_new, 0.0)
            pr = jnp.where(s > NEG_INF / 2, jnp.exp(s - m_safe), 0.0)
            corr = jnp.exp(m_prev - m_safe)
            l_ref[h, 0:R] = l_prev * corr + jnp.sum(pr, axis=-1, keepdims=True)
            acc_ref[h, 0:R] = acc_ref[h, 0:R] * corr + jax.lax.dot_general(
                pr.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_ref[h, 0:R] = m_new

    @pl.when(c == C - 1)
    def _done():
        for h in range(KV):
            o_ref[0, h] = acc_ref[h, 0:R] / jnp.maximum(l_ref[h, 0:R], 1e-30)


def paged_prefill_pallas(q, k_pool, v_pool, table, qpos, qlast, *,
                         softcap=0.0, block_chunk=1, interpret=False):
    """Chunked-prefill flash attention through the block table.

    q:      (B, KV, R, hd) query tile, R = chunk_lanes * G, row r = lane
            (r // G), group head (r % G) — post-RoPE, chunk K/V already
            written to the pools.
    k_pool/v_pool: (num_blocks + 1, block_size, KV, hd), zero block last.
    table:  (B, T) int32, T a multiple of ``block_chunk``.
    qpos:   (B, R) int32 — absolute query position per tile row (padding
            lanes clamped to the row's last real lane).
    qlast:  (B,) int32 — max over qpos rows (chunk-skip bound).

    Returns (B, KV, R, hd) fp32.
    """
    B, KV, R, hd = q.shape
    bs = k_pool.shape[1]
    T = table.shape[1]
    cpb = int(block_chunk)
    assert T % cpb == 0, (T, cpb)
    assert qpos.shape == (B, R), (qpos.shape, (B, R))
    assert k_pool.shape == v_pool.shape and k_pool.shape[2] == KV
    C = T // cpb
    # unit axis before the last two: the qpos block's last two dims equal
    # the array's (Mosaic's (8, 128) rule)
    qpos = qpos[:, None, :]                           # (B, 1, R)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, C),
        in_specs=[
            pl.BlockSpec((1, KV, R, hd), lambda b, c, *_: (b, 0, 0, 0)),
            pl.BlockSpec((1, 1, R), lambda b, c, *_: (b, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, KV, R, hd), lambda b, c, *_: (b, 0, 0, 0)),
        scratch_shapes=[
            _block_buf(KV, cpb, bs, hd, k_pool.dtype),
            _block_buf(KV, cpb, bs, hd, v_pool.dtype),
            pltpu.SemaphoreType.DMA((2, 2, cpb)),
            pltpu.VMEM((KV, _stats_rows(R), 1), jnp.float32),
            pltpu.VMEM((KV, _stats_rows(R), 1), jnp.float32),
            pltpu.VMEM((KV, _stats_rows(R), hd), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _prefill_kernel, scale=1.0 / np.sqrt(hd),
        softcap=float(softcap or 0.0), cpb=cpb, bs=bs, KV=KV, R=R)
    # qpos rides as a VMEM tile (mask arithmetic), qlast as scalar prefetch
    # (control flow)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, R, hd), jnp.float32),
        interpret=interpret,
        name="paged_prefill",
    )(table, qlast, q, qpos, k_pool, v_pool)
