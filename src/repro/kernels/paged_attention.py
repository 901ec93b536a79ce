"""Pallas TPU kernel — fused paged-attention decode (vLLM block-table style).

One query token per batch row attends a block-table-paged KV cache *without
ever materializing the (B, logical_len, KV, hd) gathered view* — and, in the
fused-write variant, the step's new K/V token is scattered through the block
table inside the same kernel launch, so decode is ONE kernel per layer: no
separate scatter op, no gather, no view.

Raw-speed layout (this file's second generation — the first pulled one
(block_size, hd) tile per grid step through BlockSpec index maps):

* K/V pools stay in HBM (``memory_space=ANY``); the kernel owns the tile
  movement with explicit ``make_async_copy`` DMAs instead of BlockSpec
  pipelining, because the pool tiles it needs are scattered by the block
  table and per-(block) granularity grid steps leave the MXU idle between
  tiny (block_size, hd) matmuls.
* grid = (B, C) where each C step covers a *chunk* of ``block_chunk``
  blocks for every kv head: one (block_chunk * block_size, hd) score
  matmul per head per step.  The heads share a step because a pool block
  (block_size, KV, hd) tiles its (KV, hd) minor pair: one head's slice of
  it is not tile-aligned, so the DMA moves whole blocks and the kernel
  reads each head out of VMEM with a strided load (MQA moves the squeezed
  (block_size, hd) tile directly).
  ``kernels/ops.py::pick_block_chunk`` chooses the chunk from the clamped
  view width (occupancy) so small views run in one step and large views
  amortize the online-softmax recurrence.
* double-buffered DMA: chunk c+1's block tiles start copying while chunk c
  computes (2-slot VMEM scratch, per-slot DMA semaphores), hiding pool
  latency behind the attend.
* scratch is (8, 128)-lane aligned: the running (max, sum, acc) statistics
  are padded to 8 sublanes (G is usually < 8) and sliced back, so vector
  loads never straddle tile boundaries.
* the fused write lands the (KV, hd) K/V rows for the step's token at
  ``pool[table[b, wpos // bs], wpos % bs]`` *before* chunk 0's read DMA
  is issued — the token always sees its own write, matching the scatter-
  then-attend ordering of the fallback path bit-for-bit.

Aliasing invariant (``input_output_aliases`` pins the output pools to the
input pool buffers): every pool element is either overwritten with the new
token's row (at most one (b) row per launch, gated by ``wok``) or left
untouched in place — the kernel never reads-modifies-writes pool content, so
retired blocks keep their engine-zeroed state and prefix-shared blocks are
only ever written through refcount-1 tables (the engine appends into
exclusively-owned tail blocks; see serve/engine.py).

The accumulation is the same online-softmax recurrence the chunked prefill
path in :func:`repro.models.attention._gqa_core` uses: running (max, sum,
acc) statistics with `softcap` applied before the additive mask and
`NEG_INF` masked lanes contributing exact zeros, so fully-masked chunks
(zero-block reads for unallocated table entries, ring positions not yet
written) cannot pollute the normalizer.

Bit-exactness note: the fp32 accumulation *order* differs from the one-shot
softmax the gather fallback and the jnp reference
(kernels/ref.py::paged_attention_ref) use, so outputs agree to fp32 rounding
(~1e-7 relative), which preserves temperature-0 token identity — the
property the serving harness (tests/test_paged_attention.py) enforces.  Pool
contents after the fused write are bit-identical to the scatter path: the
written rows are the same values cast to the same dtype.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# The additive-mask sentinel. Single definition for the kernel stack (ops.py,
# ref.py and paged_prefill.py import it); MUST equal models.common.NEG_INF,
# which builds the mask rows this kernel thresholds against (kernels cannot
# import models — layering — so the tie is enforced by
# tests/test_paged_attention.py).
NEG_INF = -1e30

# sublane padding for the (G, ·) statistics scratch — fp32 VMEM tiles are
# (8, 128); G (query heads per kv head) is typically 1..8
_SUBLANE = 8


def _stats_rows(g: int) -> int:
    return max(_SUBLANE, -(-g // _SUBLANE) * _SUBLANE)


def _pool_block(pool, blk, kv: int):
    """DMA source for one pool block.  A single kv head moves as its squeezed
    (bs, hd) tile; several heads move as the whole (bs, KV, hd) block, which
    is contiguous in HBM — a one-head slice of the tiled (KV, hd) minor pair
    is not tile-aligned, and the DMA engine refuses it."""
    return pool.at[blk, :, 0, :] if kv == 1 else pool.at[blk]


def _block_buf(kv: int, cpb: int, bs: int, hd: int, dtype):
    """2-slot VMEM tile buffer matching :func:`_pool_block`."""
    shape = (2, cpb, bs, hd) if kv == 1 else (2, cpb, bs, kv, hd)
    return pltpu.VMEM(shape, dtype)


def _head_tile(buf, slot, h: int, kv: int):
    """(cpb * bs, hd) tile of kv head ``h`` out of a :func:`_block_buf` slot
    (a sublane-strided load when several heads share the buffer)."""
    t = buf[slot] if kv == 1 else buf[slot, :, :, h, :]
    return t.reshape(-1, t.shape[-1])


def _decode_kernel(table_ref, wblk_ref, woff_ref, wok_ref,
                   q_ref, knew_ref, vnew_ref, mask_ref, k_hbm, v_hbm,
                   o_ref, kout_hbm, vout_hbm,
                   kbuf, vbuf, sem, wsem, m_ref, l_ref, acc_ref,
                   *, scale, softcap, cpb, bs, KV, G, has_write):
    """One (batch row, block chunk) grid step over every kv head.

    ``cpb`` blocks stream per step; ``kout_hbm``/``vout_hbm`` alias the input
    pools, and all reads go through the *output* refs so the fused write (at
    chunk 0) is ordered before every chunk read of the same launch.
    """
    b = pl.program_id(0)
    c = pl.program_id(1)
    C = pl.num_programs(1)

    def copies(ci, slot):
        for i in range(cpb):
            blk = table_ref[b, ci * cpb + i]
            yield pltpu.make_async_copy(_pool_block(kout_hbm, blk, KV),
                                        kbuf.at[slot, i], sem.at[slot, 0, i])
            yield pltpu.make_async_copy(_pool_block(vout_hbm, blk, KV),
                                        vbuf.at[slot, i], sem.at[slot, 1, i])

    @pl.when(c == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)
        if has_write:
            # the new token's (KV, hd) K/V rows land before any read DMA is
            # issued — the token always sees its own write, like the
            # scatter-then-attend fallback
            @pl.when(wok_ref[b] != 0)
            def _write():
                kw = pltpu.make_async_copy(
                    knew_ref.at[0], kout_hbm.at[wblk_ref[b], woff_ref[b]],
                    wsem.at[0])
                vw = pltpu.make_async_copy(
                    vnew_ref.at[0], vout_hbm.at[wblk_ref[b], woff_ref[b]],
                    wsem.at[1])
                kw.start()
                vw.start()
                kw.wait()
                vw.wait()
        for cp in copies(0, 0):
            cp.start()

    @pl.when(c + 1 < C)
    def _prefetch_next():                       # double buffer: overlap DMA
        for cp in copies(c + 1, (c + 1) % 2):
            cp.start()

    slot = c % 2
    for cp in copies(c, slot):
        cp.wait()

    mask = mask_ref[0]                                     # (1, chunk)
    for h in range(KV):
        k = _head_tile(kbuf, slot, h, KV)                  # (chunk, hd)
        v = _head_tile(vbuf, slot, h, KV)
        q = q_ref[0, h]                                    # (G, hd)
        s = jax.lax.dot_general(q, k.astype(q.dtype), (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if softcap:                                        # gemma2 logit cap
            s = softcap * jnp.tanh(s / softcap)
        s = s + mask                                       # (G, chunk)

        m_prev = m_ref[h, 0:G]                             # (G, 1)
        l_prev = l_ref[h, 0:G]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        # masked lanes must contribute exact zeros even when the whole chunk
        # is masked; m_safe keeps every exp argument away from sentinel-
        # minus-sentinel differences (exact in strict fp, NaN-prone under
        # XLA's reassociating fusions — see kernels/ref.py, which mirrors
        # this)
        m_safe = jnp.where(m_new > NEG_INF / 2, m_new, 0.0)
        p = jnp.where(s > NEG_INF / 2, jnp.exp(s - m_safe), 0.0)
        corr = jnp.exp(m_prev - m_safe)
        l_ref[h, 0:G] = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[h, 0:G] = acc_ref[h, 0:G] * corr + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[h, 0:G] = m_new

    @pl.when(c == C - 1)
    def _done():
        for h in range(KV):
            o_ref[0, h] = acc_ref[h, 0:G] / jnp.maximum(l_ref[h, 0:G], 1e-30)


def _call(q, k_pool, v_pool, table, mask, knew, vnew, wblk, woff, wok, *,
          softcap, block_chunk, has_write, interpret):
    B, KV, G, hd = q.shape
    bs = k_pool.shape[1]
    T = table.shape[1]
    cpb = int(block_chunk)
    assert T % cpb == 0, (T, cpb)
    assert mask.shape == (B, T * bs), (mask.shape, (B, T * bs))
    assert k_pool.shape == v_pool.shape and k_pool.shape[2] == KV
    C = T // cpb
    # the mask rows get a unit axis before their last two, so every block's
    # last two dims are (8, 128)-aligned or equal to the array's (Mosaic)
    mask = mask[:, None, :]                                # (B, 1, T*bs)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(B, C),
        in_specs=[
            pl.BlockSpec((1, KV, G, hd), lambda b, c, *_: (b, 0, 0, 0)),
            pl.BlockSpec((1, KV, hd), lambda b, c, *_: (b, 0, 0)),
            pl.BlockSpec((1, KV, hd), lambda b, c, *_: (b, 0, 0)),
            pl.BlockSpec((1, 1, cpb * bs), lambda b, c, *_: (b, 0, c)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[
            pl.BlockSpec((1, KV, G, hd), lambda b, c, *_: (b, 0, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        scratch_shapes=[
            _block_buf(KV, cpb, bs, hd, k_pool.dtype),      # K tiles (2 slots)
            _block_buf(KV, cpb, bs, hd, v_pool.dtype),      # V tiles
            pltpu.SemaphoreType.DMA((2, 2, cpb)),          # per-slot/tile sems
            pltpu.SemaphoreType.DMA((2,)),                 # write sems (K, V)
            pltpu.VMEM((KV, _stats_rows(G), 1), jnp.float32),   # running max
            pltpu.VMEM((KV, _stats_rows(G), 1), jnp.float32),   # running sum
            pltpu.VMEM((KV, _stats_rows(G), hd), jnp.float32),  # accumulator
        ],
    )
    kernel = functools.partial(
        _decode_kernel, scale=1.0 / np.sqrt(hd),
        softcap=float(softcap or 0.0), cpb=cpb, bs=bs, KV=KV, G=G,
        has_write=has_write)
    out, k_pool, v_pool = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B, KV, G, hd), jnp.float32),
                   jax.ShapeDtypeStruct(k_pool.shape, k_pool.dtype),
                   jax.ShapeDtypeStruct(v_pool.shape, v_pool.dtype)],
        # operand indices include the 4 scalar-prefetch refs: k_pool is
        # operand 8, v_pool 9; outputs 1 and 2 are the pools
        input_output_aliases={8: 1, 9: 2},
        interpret=interpret,
        # the op's name in HLO and in profiler traces, which readers match
        name="paged_attention_decode" if has_write else "paged_attention",
    )(table, wblk, woff, wok, q, knew, vnew, mask, k_pool, v_pool)
    return out, k_pool, v_pool


def paged_attention_pallas(q, k_pool, v_pool, table, mask, *, softcap=0.0,
                           block_chunk=1, interpret=False):
    """Read-only fused paged-attention decode (cross-attention, parity tests).

    q:      (B, KV, G, hd) — one post-RoPE query token per row, grouped by
            kv head (H = KV * G, head h = k * G + g, matching _gqa_core).
    k_pool: (num_blocks + 1, block_size, KV, hd) serving pool (zero block
            last; unallocated table entries must already point at it).
    v_pool: same shape as k_pool.
    table:  (B, T) int32 block ids, T a multiple of ``block_chunk`` (the
            wrapper pads with the zero block).
    mask:   (B, T * block_size) additive fp32 rows; positions beyond the
            per-row visible range must be NEG_INF.

    Returns (B, KV, G, hd) fp32.
    """
    B, KV, _, hd = q.shape
    zeros = jnp.zeros((B, KV, hd), k_pool.dtype)
    zi = jnp.zeros((B,), jnp.int32)
    out, _, _ = _call(q, k_pool, v_pool, table, mask, zeros, zeros,
                      zi, zi, zi, softcap=softcap, block_chunk=block_chunk,
                      has_write=False, interpret=interpret)
    return out


def paged_attention_decode_pallas(q, k_pool, v_pool, table, mask, k_new,
                                  v_new, wblk, woff, wok, *, softcap=0.0,
                                  block_chunk=1, interpret=False):
    """Fused write + attend: ONE launch per decode layer.

    On top of :func:`paged_attention_pallas`: k_new/v_new (B, KV, hd) are the
    step's new K/V rows (already cast to the pool dtype); row b writes them
    at ``pool[wblk[b], woff[b], :, :]`` iff ``wok[b] != 0`` (int32), before
    any read of the launch.  The mask must already make the written position
    visible.  Returns (out, k_pool, v_pool) — the pools are aliased in-place
    updates of the inputs.
    """
    return _call(q, k_pool, v_pool, table, mask, k_new, v_new,
                 wblk, woff, wok, softcap=softcap, block_chunk=block_chunk,
                 has_write=True, interpret=interpret)
