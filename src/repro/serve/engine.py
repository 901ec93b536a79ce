"""Serving runtime: sharded prefill/decode steps + a continuous-batching engine.

``serve_step`` (decode) is THE artifact the decode_32k / long_500k dry-run cells
lower: one new token against a seq_len KV cache, with all projections running as
EMT analog (optionally bit-serial, technique C) crossbar reads.

Architecture (continuous batching)
----------------------------------
The engine owns a fixed batch of ``batch_size`` **slots** over one shared KV
cache of shape ``(batch_size, max_len, ...)`` per attention layer.  Each slot
is free or bound to exactly one in-flight request:

* **admission** — a FIFO :class:`~repro.serve.scheduler.Scheduler` assigns the
  queue head to a free slot.  For decoder-only attention stacks (the default,
  ``chunked``) admission just binds the request: its prompt then streams into
  the cache as **chunked prefill** — up to ``prefill_chunk`` tokens per engine
  step at their *exact* positions, directly into the slot's cache region or
  pool blocks, through the same mixed step that decodes the other slots
  (:func:`repro.models.lm.chunk_step`, per-slot phase mask).  There is no
  power-of-two prompt bucket and no separate batch-1 prefill compile; a long
  prompt no longer stalls co-tenant decode while it prefills.  Recurrent /
  enc-dec / mrope stacks keep the legacy path: left-pad into a pow2 bucket,
  prefill alone (batch 1, compiled once per bucket), scatter into the slot.
  Admission happens *mid-decode* either way: nothing recompiles, because the
  step's shapes are static in ``batch_size``.
* **decode** — one jitted step per token for the whole batch.
  :func:`repro.models.lm.decode_step` takes a per-slot ``(B,)`` position vector
  plus an active mask, so slots at different sequence positions share the step;
  retired/free slots flow through the matmuls but their cache rows are frozen.
  While any slot is still streaming its prompt the engine runs the mixed
  chunk step instead (decode-phase slots ride along with ``ntok == 1``).
* **prefix caching** (``prefix_cache=True``; paged + chunked, all-global
  attention) — full prompt blocks are keyed by a rolling hash of the token
  prefix and **refcounted** in the :class:`~repro.serve.kv_pool.BlockPool`.
  A request whose prompt starts with a resident registered chain shares those
  blocks instead of re-prefilling them: the EMT analog reads that produced
  that K/V are paid once, and the hit bills zero incremental ``energy_pj`` /
  ``kv_reads``.  A prompt diverging *inside* a registered block reuses the
  shared head copy-on-write.  Blocks whose refcount drops to zero park in an
  LRU cached-free list (still hit-able) and are evicted + re-zeroed only when
  allocation needs them.
* **sampling** — :mod:`repro.serve.sampling` draws each slot's next token from
  a pure hash of (request seed, generated-token counter): deterministic per
  request, independent of slot placement and co-tenants.
* **retirement** — a slot is released on EOS, ``max_new`` tokens, or cache
  exhaustion (``max_len``), and immediately becomes available for backfill.
  Its cache region (contiguous) or blocks (paged) are zeroed on release so a
  backfilled request can never gather a predecessor's stale K/V.
* **paged KV (``paged=True``)** — instead of a contiguous ``(B, max_len, ...)``
  region per slot, attention layers share a pool of ``block_size``-position
  blocks (:mod:`repro.serve.kv_pool`).  The scheduler keeps a per-request
  block table: prompt blocks + a decode worst-case reservation are allocated
  at admission, one reserved block is drawn each time decode crosses a block
  boundary, and everything is freed at retirement.  Admission is gated on the
  free-block budget as well as a free batch row, so an engine can hold many
  more rows than ``max_len``-sized KV regions — short requests no longer
  strand ``max_len - len`` positions of capacity.  Decode is ONE kernel
  launch per layer by default: the fused paged-attention kernel
  (:mod:`repro.kernels.paged_attention`) scatters the step's new K/V row
  through the block table *inside* the kernel that streams the block tiles
  (``input_output_aliases`` pins the pool update in place) — no separate
  scatter op, no materialized view.  Chunked prefill likewise attends
  table-resolved tiles in a flash-style kernel
  (:mod:`repro.kernels.paged_prefill`) instead of gathering the view per
  chunk.  The only fallback is the explicit kill switch
  (``cfg.fused_paged_attn=False``), which scatters then materializes a view
  clamped to the block-rounded bucket of the furthest live position
  (``view_bucket``), not ``max_len``; M-RoPE configs run the fused path (the
  kernels only see post-RoPE q/k and token-index mask rows).  Unallocated
  entries resolve to a dedicated always-zero block,
  keeping paged decode token-identical to the contiguous cache at
  temperature 0.
* **energy** — the paper's per-step scalar ``energy_pj`` aux is attributed per
  request: prefill energy goes to the admitted request; each decode step's
  energy is split by read counts — every slot (active or idle) issues the same
  crossbar reads per step, so an active slot is billed ``e/batch_size`` and
  the idle rows' share accrues to ``idle_energy_pj`` (scheduler waste, not any
  request's).  Per-request numbers are therefore occupancy-independent, and
  ``sum(per-request) + idle_energy_pj == total_energy_pj`` by construction.

Weight-noise seeding (technique A): with ``fresh_noise=True`` (default) every
decode step folds the global step counter into the EMT fluctuation seed — the
physical RTN picture, matching the pre-continuous-batching engine.  With
``fresh_noise=False`` the fluctuation is frozen at the engine seed (static
programming-noise picture), which makes generation a pure function of the
request — the property the alone-vs-staggered equivalence tests exercise.

Tracing: every ``step()`` writes host spans (``TraceAnnotation``, recorded
only while a profiler session is active) on the calling thread —
``engine.step`` holding ``engine.admit``, ``engine.prepare`` (with
``engine.tables`` on a paged engine), ``engine.dispatch``, ``engine.wait``,
``engine.book`` and ``engine.emit`` — on the device trace's clock
(docs/serving.md, "Tracing").
"""
from __future__ import annotations

import dataclasses
from typing import Callable, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from repro.core.emt_linear import stat_layers, with_weight_stats
from repro.models import lm
from repro.models.config import ModelConfig
from repro.models.context import Ctx
from repro.models.stack import ATTN_KINDS
from repro.nn.param import param_shardings
from repro.parallel.sharding import RULES, make_shard_fn, cache_shardings
from repro.serve import sampling
from repro.serve.kv_pool import PagedKV
from repro.serve.scheduler import RejectedError, Scheduler, Slot

__all__ = ["ServingEngine", "GenRequest", "GenResult", "RejectedError",
           "prefill_bucket", "view_bucket", "serve_shardings",
           "make_prefill_step", "make_decode_step", "make_serve_decode_step",
           "make_chunk_step", "make_paged_decode_step",
           "make_sharded_chunk_step", "make_sharded_decode_step"]


def make_prefill_step(cfg: ModelConfig, mesh: Optional[Mesh], rules=None):
    shard = make_shard_fn(mesh, rules) if mesh is not None else (lambda x, n: x)

    def prefill_step(params, batch, cache, seed):
        ctx = Ctx(seed=seed, shard=shard)
        return lm.prefill(params, batch, cfg, ctx, cache)

    return prefill_step


def make_decode_step(cfg: ModelConfig, mesh: Optional[Mesh], rules=None):
    """Lockstep decode step (scalar position) — the dry-run lowering artifact."""
    shard = make_shard_fn(mesh, rules) if mesh is not None else (lambda x, n: x)

    def decode_step(params, cache, tokens, index, seed):
        ctx = Ctx(seed=seed, shard=shard)
        logits, cache, aux = lm.decode_step(params, cache, tokens, index, cfg, ctx)
        return logits, cache, aux["energy_pj"]

    return decode_step


def _next_tokens(logits, temps, top_k, top_p, sample_seeds, sample_pos,
                 sampled: bool):
    """The step's sampled tokens.  ``sampled`` is jit-static and False when
    the caller knows every row is greedy: the program then holds no
    full-vocab sort, which at a 262k vocab costs tens of seconds of TPU
    compile per step program (greedy rows get the same argmax either way)."""
    if not sampled:
        return sampling.greedy_tokens(logits)
    return sampling.sample_tokens(logits, temps, top_k, top_p, sample_seeds,
                                  sample_pos)


def make_serve_decode_step(cfg: ModelConfig, mesh: Optional[Mesh], rules=None):
    """Continuous-batching decode: per-slot positions/active mask + fused
    per-slot seeded sampling. Returns (next_tokens, new_cache, energy_pj)."""
    shard = make_shard_fn(mesh, rules) if mesh is not None else (lambda x, n: x)

    def serve_decode_step(params, cache, tokens, index, active, seed,
                          sample_seeds, sample_pos, temps, top_k, top_p,
                          enc_lens, sampled=True):
        ctx = Ctx(seed=seed, shard=shard)
        logits, cache, aux = lm.decode_step(params, cache, tokens, index, cfg,
                                            ctx, active=active,
                                            enc_lens=enc_lens)
        next_tok = _next_tokens(logits, temps, top_k, top_p, sample_seeds,
                                sample_pos, sampled)
        return next_tok, cache, {"energy_pj": aux["energy_pj"],
                                 "corners": aux["corners"],
                                 "kv_reads": aux["kv_reads"]}

    return serve_decode_step


def make_chunk_step(cfg: ModelConfig, mesh: Optional[Mesh], rules,
                    page_lens: Optional[dict] = None):
    """One jitted **mixed prefill+decode** step (lm.chunk_step): every batch
    row advances by `ntok[b]` tokens — a fixed-size chunk of its prompt for
    prefill-phase slots, one generated token for decode-phase slots — and the
    row's last real lane is sampled.  Paged engines additionally pass the
    width-clamped block tables + the static clamped `view_len` (same contract
    as make_paged_decode_step)."""
    shard = make_shard_fn(mesh, rules) if mesh is not None else (lambda x, n: x)

    def chunk_step(params, cache, tokens, start, ntok, active, seed,
                   sample_seeds, sample_pos, temps, top_k, top_p,
                   table_g=None, table_l=None, view_len=0, sampled=True):
        ctx = Ctx(seed=seed, shard=shard)
        pt = pl = None
        if page_lens is not None:
            pt = {"global": table_g, "local": table_l}
            pl = lm.clamped_lens(page_lens, view_len)
        logits, cache, aux = lm.chunk_step(params, cache, tokens, start, ntok,
                                           cfg, ctx, active=active,
                                           page_tables=pt, page_lens=pl)
        next_tok = _next_tokens(logits, temps, top_k, top_p, sample_seeds,
                                sample_pos, sampled)
        return next_tok, cache, {"energy_pj": aux["energy_pj"],
                                 "corners": aux["corners"],
                                 "kv_reads": aux["kv_reads"]}

    return chunk_step


def make_verify_step(cfg: ModelConfig, mesh: Optional[Mesh], rules,
                     page_lens: Optional[dict] = None):
    """Speculative-decoding verify step: one lm.chunk_step over the
    [last_token, draft_1..draft_k] chunk of every slot with `all_lanes=True`,
    returning the per-lane greedy argmax (B, C) — lane j's token is the
    target model's greedy continuation after ..start+j, i.e. the token that
    validates draft j+1 (or replaces it on rejection).  Greedy only: the
    argmax matches sampling.sample_tokens at temperature 0 bit-exactly, which
    is what makes speculative decoding token-identical to plain decode."""
    shard = make_shard_fn(mesh, rules) if mesh is not None else (lambda x, n: x)

    def verify_step(params, cache, tokens, start, ntok, active, seed,
                    table_g=None, table_l=None, view_len=0):
        ctx = Ctx(seed=seed, shard=shard)
        pt = pl = None
        if page_lens is not None:
            pt = {"global": table_g, "local": table_l}
            pl = lm.clamped_lens(page_lens, view_len)
        logits, cache, aux = lm.chunk_step(params, cache, tokens, start, ntok,
                                           cfg, ctx, active=active,
                                           page_tables=pt, page_lens=pl,
                                           all_lanes=True)
        greedy = jnp.argmax(logits.astype(jnp.float32), axis=-1) \
                    .astype(jnp.int32)
        return greedy, cache, {"energy_pj": aux["energy_pj"],
                               "corners": aux["corners"],
                               "kv_reads": aux["kv_reads"]}

    return verify_step


def make_pool_copy(cfg: ModelConfig):
    """Copy one global-pool block row src -> dst across every attention
    layer's K/V pools — the device half of prefix-cache copy-on-write (the
    donor block's leading rows are our prompt's K/V verbatim; the diverging
    tail is overwritten by the resuming prefill and never mask-visible)."""
    kinds = cfg.blocks()

    def copy(big, src, dst):
        out = {}
        for i, kind in enumerate(kinds):
            name = f"layer_{i:03d}"
            b = big[name]
            if kind in ATTN_KINDS:
                out[name] = {key: e.at[dst].set(e[src]) for key, e in b.items()}
            else:
                out[name] = b
        return out

    return copy


def view_bucket(need: int, block_size: int, max_len: int) -> int:
    """Block-rounded power-of-two view length covering `need` positions.

    The paged decode step is jit-static in the logical view length; bucketing
    the clamp to power-of-two block counts bounds recompiles at O(log
    max_len/block_size) while still shrinking masks, gathers, and the fused
    kernel's chunk walk to what live requests actually occupy."""
    nb = 1
    while nb * block_size < need:
        nb *= 2
    return nb * block_size if nb * block_size < max_len else max_len


def make_paged_decode_step(cfg: ModelConfig, mesh: Optional[Mesh], rules,
                           page_lens: dict):
    """Continuous-batching decode against the paged block-table KV cache:
    same contract as make_serve_decode_step plus the (B, T) block tables
    (width-clamped by the caller) and the static clamped `view_len` the
    tables/masks cover this step (lm.clamped_lens; jit once per bucket)."""
    shard = make_shard_fn(mesh, rules) if mesh is not None else (lambda x, n: x)

    def paged_decode_step(params, cache, tokens, index, active, seed,
                          sample_seeds, sample_pos, temps, top_k, top_p,
                          enc_lens, table_g, table_l, view_len, sampled=True):
        ctx = Ctx(seed=seed, shard=shard)
        logits, cache, aux = lm.decode_step(
            params, cache, tokens, index, cfg, ctx, active=active,
            page_tables={"global": table_g, "local": table_l},
            page_lens=lm.clamped_lens(page_lens, view_len), enc_lens=enc_lens)
        next_tok = _next_tokens(logits, temps, top_k, top_p, sample_seeds,
                                sample_pos, sampled)
        return next_tok, cache, {"energy_pj": aux["energy_pj"],
                                 "corners": aux["corners"],
                                 "kv_reads": aux["kv_reads"]}

    return paged_decode_step


def make_paged_insert(cfg: ModelConfig, block_size: int, page_lens: dict):
    """Scatter a freshly prefilled batch-1 contiguous cache into the pools.

    `row_g`/`row_l` are the slot's block-table rows with unallocated entries
    pointing out of bounds (dropped), so only the request's own blocks are
    written — including their zero padding tails, which clears any stale
    content left by the blocks' previous owner."""
    kinds = cfg.blocks()

    def pad_to_blocks(x, width):
        # (1, L, KV, hd) -> (width, block_size, KV, hd), zero-padded
        pad = width * block_size - x.shape[1]
        if pad:
            x = jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
        return x[0].reshape(width, block_size, *x.shape[2:])

    def insert(big, small, row_g, row_l, slot):
        out = {}
        for i, kind in enumerate(kinds):
            name = f"layer_{i:03d}"
            b, s = big[name], small[name]
            if kind in ATTN_KINDS:
                ring = kind == "local" and page_lens["ring"]
                e = {}
                for key in b:
                    row = row_g if (key in ("ck", "cv") or not ring) else row_l
                    e[key] = b[key].at[row].set(
                        pad_to_blocks(s[key].astype(b[key].dtype),
                                      row.shape[0]),
                        mode="drop")
                out[name] = e
            else:
                out[name] = jax.tree.map(
                    lambda bb, ss: bb.at[slot].set(ss[0].astype(bb.dtype)),
                    b, s)
        return out

    return insert


def make_paged_zero(cfg: ModelConfig, page_lens: dict):
    """Zero a retiring request's pool blocks (+ its recurrent-state row) so a
    later owner of the same blocks can never gather its stale K/V."""
    kinds = cfg.blocks()

    def zero(big, ids_g, ids_l, slot):
        out = {}
        for i, kind in enumerate(kinds):
            name = f"layer_{i:03d}"
            b = big[name]
            if kind in ATTN_KINDS:
                ring = kind == "local" and page_lens["ring"]
                e = {}
                for key in b:
                    ids = ids_g if (key in ("ck", "cv") or not ring) else ids_l
                    e[key] = b[key].at[ids].set(0.0, mode="drop")
                out[name] = e
            else:
                out[name] = jax.tree.map(lambda bb: bb.at[slot].set(0.0), b)
        return out

    return zero


# --------------------------------------------------------------------------
# Data-parallel (sharded) serving steps.
#
# The engine's sharded mode wraps the *same* per-shard step functions built
# above (with mesh=None — no GSPMD constraints inside) in `shard_map` over
# the mesh "data" axis: params replicated (P()), the cache tree + every
# per-slot (B, ...) argument sharded on dim 0 (P("data")), the noise seed
# replicated.  Each device therefore runs the whole model on its own
# batch_size/n_shards slots against its own pool rows — the paged gathers
# and scatters index *shard-local* block ids by construction, so no table
# resolution ever becomes a cross-device collective (the GSPMD alternative,
# sharding the pool dim of a gathered operand, would all-gather the pools).
# Scalar aux leaves (energy_pj / corners / kv_reads) are lifted to (1,)
# inside the shard, so the stacked output is a (n_shards,) per-shard vector:
# the engine's per-shard energy/idle/corner ledgers come straight off the
# step with no extra collective.
# --------------------------------------------------------------------------


def _shard_stack_aux(aux):
    """Lift scalar aux leaves to (1, ...) so shard_map stacks them into
    per-shard vectors under out_specs=P("data")."""
    return jax.tree.map(lambda e: jnp.asarray(e)[None], aux)


def make_sharded_chunk_step(cfg: ModelConfig, mesh: Mesh,
                            page_lens: Optional[dict] = None):
    """shard_map-SPMD mixed prefill+decode step (see block comment above):
    same contract as make_chunk_step but aux leaves come back as (n_shards,)
    per-shard vectors.  `view_len` stays jit-static (the compiled view width
    is the max over the shards' buckets — SPMD programs share static
    shapes); per-shard clamping happens in the *table values* the engine
    stages (entries past a shard's own bucket resolve to the zero block)."""
    base = make_chunk_step(cfg, None, None, page_lens)
    paged = page_lens is not None
    data, rep = PartitionSpec("data"), PartitionSpec()
    in_specs = (rep, data, data, data, data, data, rep,
                data, data, data, data, data) + ((data, data) if paged else ())
    out_specs = (data, data, data)

    def chunk_step(params, cache, tokens, start, ntok, active, seed,
                   sample_seeds, sample_pos, temps, top_k, top_p,
                   table_g=None, table_l=None, view_len=0, sampled=True):
        def local(params, cache, tokens, start, ntok, active, seed,
                  sample_seeds, sample_pos, temps, top_k, top_p, *tables):
            kw = {"table_g": tables[0], "table_l": tables[1],
                  "view_len": view_len} if paged else {}
            kw["sampled"] = sampled
            next_tok, cache, aux = base(
                params, cache, tokens, start, ntok, active, seed,
                sample_seeds, sample_pos, temps, top_k, top_p, **kw)
            return next_tok, cache, _shard_stack_aux(aux)

        args = (params, cache, tokens, start, ntok, active, seed,
                sample_seeds, sample_pos, temps, top_k, top_p)
        if paged:
            args += (table_g, table_l)
        return jax.shard_map(local, mesh=mesh, in_specs=in_specs,
                             out_specs=out_specs, check_vma=False)(*args)

    return chunk_step


def make_sharded_decode_step(cfg: ModelConfig, mesh: Mesh,
                             page_lens: Optional[dict] = None):
    """shard_map-SPMD pure-decode step: make_paged_decode_step (paged) /
    make_serve_decode_step (contiguous) per shard, aux stacked per shard."""
    paged = page_lens is not None
    base = make_paged_decode_step(cfg, None, None, page_lens) if paged \
        else make_serve_decode_step(cfg, None, None)
    data, rep = PartitionSpec("data"), PartitionSpec()
    in_specs = (rep, data, data, data, data, rep, data, data, data, data,
                data, data) + ((data, data) if paged else ())
    out_specs = (data, data, data)

    def decode_step(params, cache, tokens, index, active, seed,
                    sample_seeds, sample_pos, temps, top_k, top_p, enc_lens,
                    table_g=None, table_l=None, view_len=0, sampled=True):
        def local(params, cache, tokens, index, active, seed,
                  sample_seeds, sample_pos, temps, top_k, top_p, enc_lens,
                  *tables):
            if paged:
                next_tok, cache, aux = base(
                    params, cache, tokens, index, active, seed,
                    sample_seeds, sample_pos, temps, top_k, top_p, enc_lens,
                    tables[0], tables[1], view_len, sampled=sampled)
            else:
                next_tok, cache, aux = base(
                    params, cache, tokens, index, active, seed,
                    sample_seeds, sample_pos, temps, top_k, top_p, enc_lens,
                    sampled=sampled)
            return next_tok, cache, _shard_stack_aux(aux)

        args = (params, cache, tokens, index, active, seed, sample_seeds,
                sample_pos, temps, top_k, top_p, enc_lens)
        if paged:
            args += (table_g, table_l)
        return jax.shard_map(local, mesh=mesh, in_specs=in_specs,
                             out_specs=out_specs, check_vma=False)(*args)

    return decode_step


def make_sharded_paged_zero(cfg: ModelConfig, mesh: Mesh, page_lens: dict):
    """Per-shard zero-on-retire/evict: `(n_shards, W)` id grids + a
    `(n_shards,)` slot vector, one row per shard — non-target shards carry
    the out-of-bounds sentinels and their scatters drop."""
    base = make_paged_zero(cfg, page_lens)
    data = PartitionSpec("data")

    def zero(big, ids_g, ids_l, slot):
        def local(big, ids_g, ids_l, slot):
            return base(big, ids_g[0], ids_l[0], slot[0])
        return jax.shard_map(local, mesh=mesh, in_specs=(data,) * 4,
                             out_specs=data, check_vma=False)(
                             big, ids_g, ids_l, slot)

    return jax.jit(zero, donate_argnums=(0,))


def make_sharded_slot_zero(mesh: Mesh):
    """Contiguous-cache zero-on-retire per shard: `(n_shards,)` local slot
    ids, sentinel (== shard batch size, out of bounds -> dropped) on the
    shards that retire nothing this call."""
    data = PartitionSpec("data")

    def zero(big, slot):
        def local(big, slot):
            return jax.tree.map(
                lambda b: b.at[slot[0]].set(0.0, mode="drop"), big)
        return jax.shard_map(local, mesh=mesh, in_specs=(data, data),
                             out_specs=data, check_vma=False)(big, slot)

    return jax.jit(zero, donate_argnums=(0,))


def make_sharded_pool_copy(cfg: ModelConfig, mesh: Mesh):
    """Per-shard prefix-cache COW copy: `(n_shards,)` src/dst id vectors;
    non-target shards carry the out-of-bounds dst sentinel (update dropped —
    jit scatter semantics — so their gathered src row never lands)."""
    base = make_pool_copy(cfg)
    data = PartitionSpec("data")

    def copy(big, src, dst):
        def local(big, src, dst):
            return base(big, src[0], dst[0])
        return jax.shard_map(local, mesh=mesh, in_specs=(data,) * 3,
                             out_specs=data, check_vma=False)(big, src, dst)

    return jax.jit(copy, donate_argnums=(0,))


def serve_shardings(cfg: ModelConfig, mesh: Mesh, batch: int, max_len: int,
                    rules_name: str = "serve_2d"):
    """(param_shardings, cache_shardings, cache_specs) for the serving mesh."""
    rules = RULES[rules_name]
    pspecs = lm.specs(cfg)
    psh = param_shardings(pspecs, mesh, rules)
    cspecs = lm.init_cache_specs(cfg, batch, max_len)
    csh = cache_shardings(cspecs, mesh, rules)
    return psh, csh, cspecs, rules


@dataclasses.dataclass
class GenRequest:
    prompt: np.ndarray               # (S,) int32
    max_new: int = 16
    temperature: float = 0.0         # 0 = greedy
    top_k: int = 0                   # 0 = disabled
    top_p: float = 1.0               # >=1 = disabled
    seed: int = 0                    # sampling seed (deterministic per request)
    eos_id: Optional[int] = None     # stop token (None = run to max_new)
    # per-request energy SLA: once the energy billed to this request
    # (prefill + decode + draft) exceeds the budget, the control plane sheds
    # it through the normal cancel path with done_reason="energy_budget"
    # (None = no budget; see serve/control.py)
    energy_budget_uj: Optional[float] = None


@dataclasses.dataclass
class GenResult:
    rid: int                         # request id, submission order
    tokens: np.ndarray               # (n,) int32 generated tokens
    energy_pj: float                 # total EMT energy billed to this request
    prefill_energy_pj: float         # ... of which prefill
    steps: int                       # decode steps the request participated in
    # "eos" | "max_new" | "max_len" | "cancelled" | "timeout" |
    # "energy_budget" — the last three come from ServingEngine.cancel(): the
    # slot retired early with whatever partial tokens/energy it had
    # accumulated (per-request + idle == total energy conservation holds for
    # partials too). "energy_budget" is the control plane shedding a request
    # that exhausted its energy_budget_uj (serve/control.py).
    done_reason: str
    # speculative decoding split (serve/speculative.py; 0 on plain engines):
    # draft_energy_pj is the subset of energy_pj billed on the draft
    # placement; spec_accepted/spec_proposed give the request's accept rate
    draft_energy_pj: float = 0.0
    spec_proposed: int = 0
    spec_accepted: int = 0


def prefill_bucket(n: int, lo: int = 4) -> int:
    """Smallest power-of-two >= n (min `lo`) — prefill compile-cache buckets.

    Sizing note for callers: a request's prompt occupies ``prefill_bucket(len)``
    cache positions (left-padded), so an engine serving prompts of length L for
    ``max_new`` tokens wants ``max_len >= prefill_bucket(L) + max_new - 1``."""
    b = lo
    while b < n:
        b *= 2
    return b


class ServingEngine:
    """Slot-based continuous-batching engine (single host; the sharded steps
    are the same functions the multi-pod dry-run compiles).

    Streaming API: ``submit()`` enqueues a request and returns its rid,
    ``step()`` advances the whole batch one token (admitting queued requests
    into free slots first) and returns any finished :class:`GenResult`s,
    ``drain()`` steps until idle.  ``generate()`` is the batch-mode wrapper.
    """

    def __init__(self, cfg: ModelConfig, params, batch_size: int, max_len: int,
                 mesh: Optional[Mesh] = None, rules=None, seed: int = 0,
                 fresh_noise: bool = True, paged: bool = False,
                 block_size: int = 16, num_blocks: Optional[int] = None,
                 num_ring_blocks: Optional[int] = None, placement=None,
                 chunked_prefill: Optional[bool] = None,
                 prefill_chunk: int = 16, prefix_cache: bool = False,
                 max_pending: Optional[int] = None,
                 on_token: Optional[Callable[[int, int], None]] = None,
                 controller=None, n_shards: int = 1):
        if placement is not None:
            # heterogeneous device placement (EMTConfig or DevicePlacement):
            # overrides the config's EMT surface for this engine. Params must
            # have been initialized against the same placement.
            cfg = cfg.replace(emt=placement)
        self.cfg = cfg
        self.params = params
        # the tree every step program reads: the caller's weights with each
        # active EMT dense layer's weight-only statistics computed once here
        # (core/emt_linear.py, "weight-only statistics"), so no step reduces
        # a weight that cannot change while serving
        self.step_params = with_weight_stats(params, cfg.placement,
                                             lm.tied_weights(cfg))
        self.emt_prepared_layers, self.emt_inline_layers = stat_layers(
            self.step_params)
        self.batch_size = batch_size
        self.max_len = max_len
        self.seed = seed
        self.fresh_noise = fresh_noise
        # data-parallel serving: slots are partitioned into n_shards groups
        # and every step runs as ONE shard_map SPMD program over the mesh
        # "data" axis — each device owns its group's cache rows / pool blocks
        # (see the sharded-step block comment above).  The mesh must carry a
        # "data" axis of size n_shards (jax.sharding.Mesh over n_shards
        # devices; CI simulates them with
        # XLA_FLAGS=--xla_force_host_platform_device_count=N).
        self.n_shards = int(n_shards)
        if self.n_shards < 1 or batch_size % self.n_shards:
            raise ValueError(f"batch_size {batch_size} not divisible by "
                             f"n_shards {n_shards}")
        self.shard_size = batch_size // self.n_shards
        if self.n_shards > 1:
            if mesh is None:
                from repro.launch.mesh import make_mesh
                mesh = make_mesh(self.n_shards, 1)
            if mesh.shape["data"] != self.n_shards:
                raise ValueError(
                    f"mesh data axis {mesh.shape['data']} != n_shards "
                    f"{self.n_shards}")
        self._mesh = mesh
        self._prefill = jax.jit(make_prefill_step(cfg, mesh, rules))
        self._sample = jax.jit(sampling.sample_tokens)
        # chunked prefill (default for decoder-only attention stacks): prompts
        # stream into the cache in fixed-size chunks through one mixed
        # prefill+decode step at their exact positions — no pow2 prompt
        # buckets, no separate batch-1 prefill compile.  Recurrent stacks
        # (token-serial state), enc-dec (encoder pass), and mrope (3-stream
        # positions) keep the legacy bucketed path.
        can_chunk = (all(k in ATTN_KINDS for k in cfg.blocks())
                     and not cfg.is_encdec and cfg.rope_type != "mrope"
                     and cfg.input_kind != "embeds")
        self.chunked = can_chunk if chunked_prefill is None \
            else bool(chunked_prefill)
        if self.chunked and not can_chunk:
            raise ValueError("chunked_prefill requires a decoder-only "
                             "attention stack without mrope/embeds input")
        if self.n_shards > 1 and not self.chunked:
            raise ValueError("sharded serving (n_shards > 1) requires "
                             "chunked prefill — the legacy bucketed prefill "
                             "path scatters batch-1 caches across shards")
        self.prefill_chunk = int(prefill_chunk)
        assert self.prefill_chunk >= 1
        sharded = self.n_shards > 1
        # paged mode only changes attention caches; pure-recurrent stacks
        # (mamba/xlstm) have nothing to page
        self.paged = bool(paged) and any(k in ATTN_KINDS for k in cfg.blocks())
        if self.paged:
            lens = lm.paged_lens(cfg, max_len)
            ring_len = lens["local"] if lens["ring"] else 0
            wg = -(-max_len // block_size)
            wl = -(-ring_len // block_size) if ring_len else 1
            # default pools: capacity-equal to the contiguous per-slot regions
            if num_blocks is None:
                num_blocks = batch_size * wg
            if num_ring_blocks is None:
                num_ring_blocks = batch_size * wl if ring_len else 0
            self.block_size = block_size
            self.kv = PagedKV(batch_size, max_len, block_size, num_blocks,
                              ring_len, num_ring_blocks if ring_len else 0,
                              n_shards=self.n_shards)
            self.page_lens = lens
            if sharded:
                # device pools hold n_shards * (per-shard blocks + 1 zero
                # block) rows: shard s's rows are its own pool followed by
                # its own zero row, so the (shard-local) gather sentinel
                # kv.zero_block_g and scatter sentinel +1 work unchanged.
                # init_paged_cache adds the one zero row itself, hence the
                # "- 1"; every row starts zeroed, so the NamedSharding
                # device_put is the only placement step needed.
                npb = num_blocks // self.n_shards
                dev_blocks = self.n_shards * (npb + 1) - 1
                dev_ring = 0
                if ring_len:
                    nrb = num_ring_blocks // self.n_shards
                    dev_ring = self.n_shards * (nrb + 1) - 1
                self.cache = lm.init_paged_cache(
                    cfg, batch_size, max_len, block_size, dev_blocks,
                    dev_ring)
                self.cache = jax.device_put(
                    self.cache,
                    NamedSharding(mesh, PartitionSpec("data")))
                self._decode = jax.jit(
                    make_sharded_decode_step(cfg, mesh, lens),
                    donate_argnums=(1,),
                    static_argnames=("view_len", "sampled"))
                self._chunk = jax.jit(
                    make_sharded_chunk_step(cfg, mesh, lens),
                    donate_argnums=(1,),
                    static_argnames=("view_len", "sampled"))
                self._zero_retired = make_sharded_paged_zero(cfg, mesh, lens)
                self._insert = None      # chunked admission never scatters
            else:
                self.cache = lm.init_paged_cache(
                    cfg, batch_size, max_len, block_size, num_blocks,
                    num_ring_blocks if ring_len else 0)
                # view_len is static: one compile per power-of-two bucket
                self._decode = jax.jit(
                    make_paged_decode_step(cfg, mesh, rules, lens),
                    donate_argnums=(1,),
                    static_argnames=("view_len", "sampled"))
                self._insert = jax.jit(
                    make_paged_insert(cfg, block_size, lens),
                    donate_argnums=(0,))
                self._zero_retired = jax.jit(make_paged_zero(cfg, lens),
                                             donate_argnums=(0,))
                if self.chunked:
                    self._chunk = jax.jit(
                        make_chunk_step(cfg, mesh, rules, lens),
                        donate_argnums=(1,),
                        static_argnames=("view_len", "sampled"))
            self.scheduler = Scheduler(batch_size, kv=self.kv,
                                       max_pending=max_pending,
                                       n_shards=self.n_shards)
        else:
            self.kv = None
            self.cache = lm.init_cache(cfg, batch_size, max_len)
            if sharded:
                self.cache = jax.device_put(
                    self.cache,
                    NamedSharding(mesh, PartitionSpec("data")))
                self._decode = jax.jit(make_sharded_decode_step(cfg, mesh),
                                       donate_argnums=(1,),
                                       static_argnames=("sampled",))
                self._chunk = jax.jit(make_sharded_chunk_step(cfg, mesh),
                                      donate_argnums=(1,),
                                      static_argnames=("sampled",))
                self._zero_retired = make_sharded_slot_zero(mesh)
                self._insert = None
            else:
                self._decode = jax.jit(
                    make_serve_decode_step(cfg, mesh, rules),
                    donate_argnums=(1,), static_argnames=("sampled",))
                self._insert = jax.jit(self._insert_slot, donate_argnums=(0,))
                self._zero_retired = jax.jit(self._zero_slot,
                                             donate_argnums=(0,))
                if self.chunked:
                    self._chunk = jax.jit(make_chunk_step(cfg, mesh, rules),
                                          donate_argnums=(1,),
                                          static_argnames=("sampled",))
            self.scheduler = Scheduler(batch_size, max_pending=max_pending,
                                       n_shards=self.n_shards)
        if sharded:
            # replicate params across the mesh once (weight noise is seeded,
            # so every shard regenerates identical fluctuations per step)
            self.step_params = jax.device_put(
                self.step_params, NamedSharding(mesh, PartitionSpec()))
        # refcounted prefix caching: shared prompt-prefix blocks are reused
        # across requests (paged + chunked only; ring/recurrent/enc-dec state
        # cannot be shared across requests, so those stacks are refused)
        self.prefix_cache = bool(prefix_cache)
        if self.prefix_cache:
            if not (self.paged and self.chunked):
                raise ValueError("prefix_cache requires paged=True and "
                                 "chunked prefill")
            if self.page_lens["ring"]:
                raise ValueError("prefix_cache requires an all-global "
                                 "attention stack (sliding-window ring K/V is "
                                 "positional and cannot be shared)")
            if sharded:
                self._pool_copy = make_sharded_pool_copy(cfg, mesh)
            else:
                self._pool_copy = jax.jit(make_pool_copy(cfg),
                                          donate_argnums=(0,))
        # per-token streaming hook: called as on_token(rid, token) the moment
        # a slot's new token is sampled (inside step()/_chunk_advance, before
        # the request retires) — the async front-end points this at the
        # per-request event queues.  Must be cheap and must not touch the
        # engine (it runs mid-step).
        self.on_token = on_token
        # energy-aware control plane (serve/control.py): gates admission
        # against a rolling per-engine uJ bucket and sheds requests that
        # exhaust their per-request energy_budget_uj (None = no control)
        self.controller = controller
        self.total_energy_pj = 0.0
        self.idle_energy_pj = 0.0    # decode energy of idle slots (waste)
        # per-corner energy totals (prefill + decode), keyed by the placement's
        # corner labels — sums to total_energy_pj by construction
        self.corner_energy_pj = {}
        # per-shard ledgers (length n_shards; a single-shard engine keeps
        # them too, as length-1 views of the same accounting): the sharded
        # step returns each aux scalar as a per-shard vector, so the split
        # is exact — sum(shard_energy_pj) == total_energy_pj and
        # sum(shard_idle_energy_pj) == idle_energy_pj up to summation order.
        self.shard_energy_pj = np.zeros(self.n_shards)
        self.shard_idle_energy_pj = np.zeros(self.n_shards)
        self.shard_corner_energy_pj = {}     # name -> (n_shards,) float64
        self.shard_kv_reads = np.zeros(self.n_shards)
        # occupancy integral: per-shard sum over steps of active slots —
        # min/max over shards is the scheduler's balance metric
        self.shard_occupancy = np.zeros(self.n_shards, np.int64)
        self._steps = 0              # global decode-step counter (noise clock)
        self.peak_concurrent = 0     # high-water mark of active slots
        self._tables_dev = None      # (key, tables) on device (None = stale)
        self.view_len = 0            # last decode step's clamped logical view
        self.shard_view_lens = [0] * self.n_shards   # per-shard view buckets
        # decode + chunk K/V cache elements actually read (mask-visible
        # positions of real lanes only — aux["kv_reads"]); padded/zero-block
        # gathers and chunk padding lanes (clamped duplicate qpos rows) are
        # not billed, identically on the kernel and legacy attend paths
        self.kv_reads_total = 0.0
        # chunked-prefill accounting: prompt tokens actually run through the
        # model vs served straight from the prefix cache (zero energy/reads)
        self.prefill_tokens_total = 0
        self.cached_prefix_tokens = 0

    def _shard_of(self, slot_id: int) -> int:
        return slot_id // self.shard_size

    def _book_corners(self, corners):
        for name, c in corners.items():
            # sharded steps return (n_shards,) per-shard vectors; the legacy
            # paths (and prefill) return scalars, which land on shard 0
            e = np.asarray(c["energy_pj"], np.float64).reshape(-1)
            self.corner_energy_pj[name] = (self.corner_energy_pj.get(name, 0.0)
                                           + float(e.sum()))
            arr = self.shard_corner_energy_pj.setdefault(
                name, np.zeros(self.n_shards))
            if e.size == self.n_shards:
                arr += e
            else:
                arr[0] += float(e.sum())

    # -- jitted helpers ------------------------------------------------------
    @staticmethod
    def _insert_slot(big, small, slot):
        """Scatter a freshly prefilled batch-1 cache into slot `slot` (entries
        shorter than the slot region — e.g. bucketed cross K/V — are
        zero-padded to it)."""
        def put(b, s):
            v = s[0].astype(b.dtype)
            pads = [(0, bd - vd) for bd, vd in zip(b.shape[1:], v.shape)]
            if any(p != (0, 0) for p in pads):
                v = jnp.pad(v, pads)
            return b.at[slot].set(v)

        return jax.tree.map(put, big, small)

    @staticmethod
    def _pad_ids(ids, width: int, sentinel: int) -> np.ndarray:
        """Fixed-width int32 id vector for the jitted zero op: pad the freed
        block ids with the out-of-bounds scatter sentinel (dropped)."""
        out = np.full(width, sentinel, np.int32)
        out[:len(ids)] = ids
        return out

    @staticmethod
    def _zero_slot(big, slot):
        """Zero a retired slot's cache region before the next backfill: the
        full-region prefill scatter used to mask stale reads, but nothing may
        rely on that (partial inserts / paged blocks would leak the previous
        request's K/V)."""
        return jax.tree.map(lambda b: b.at[slot].set(0.0), big)

    # -- streaming API -------------------------------------------------------
    def _bucket_len(self, prompt_len: int) -> int:
        """Cache positions the prompt occupies.  Chunked prefill streams the
        prompt at its exact positions; the legacy one-shot path left-pads into
        a power-of-two bucket (or prefills at exact length when the bucket
        would leave no decode room)."""
        if self.chunked:
            return prompt_len
        S = prefill_bucket(prompt_len)
        return prompt_len if S >= self.max_len else S

    def validate(self, req: GenRequest) -> np.ndarray:
        """Hard request validation — every guard is a ValueError, never a bare
        assert (asserts are stripped under ``python -O``; the ``kv.fits``
        guard was made a hard error for exactly this reason and the rest must
        match).  Returns the normalized (S,) int32 prompt.

        Reads only static engine state (config, pool capacity), never the
        mutable queue/slot tables — the streaming front-end calls it from the
        submitting thread to reject bad requests synchronously before they
        cross into the driver loop.
        """
        prompt = np.asarray(req.prompt, np.int32).reshape(-1)
        if not 1 <= len(prompt) <= self.max_len:
            raise ValueError(f"prompt length {len(prompt)} out of range "
                             f"[1, max_len={self.max_len}]")
        S = self._bucket_len(len(prompt))
        if S > self.max_len:
            # legacy bucketed prefill left-pads into prefill_bucket(L)
            # positions (see the sizing note on prefill_bucket): a bucket
            # wider than max_len would overrun the slot's cache region.
            # _bucket_len clamps near-capacity buckets to the exact prompt
            # length, so this is unreachable unless that clamp regresses —
            # keep the hard guard so an overrun can never reach the cache.
            raise ValueError(f"prompt bucket {S} overruns max_len "
                             f"{self.max_len} (prompt length {len(prompt)})")
        if req.max_new < 1:
            raise ValueError(f"max_new must be >= 1, got {req.max_new}")
        if not req.temperature >= 0:
            raise ValueError(f"temperature must be >= 0, "
                             f"got {req.temperature}")
        if not req.top_p >= 0:
            raise ValueError(f"top_p must be >= 0, got {req.top_p}")
        if req.top_k < 0:
            raise ValueError(f"top_k must be >= 0, got {req.top_k}")
        if req.energy_budget_uj is not None and not req.energy_budget_uj > 0:
            raise ValueError(f"energy_budget_uj must be > 0, "
                             f"got {req.energy_budget_uj}")
        if self.paged:
            # FIFO admission head-blocks: a request that cannot fit even an
            # empty pool would deadlock the queue, so refuse it up front
            if not self.kv.fits(S, req.max_new):
                raise ValueError(
                    f"request needs more KV blocks than the pool holds "
                    f"({self.kv.pool_g.num_blocks} x {self.block_size}"
                    + (" per shard)" if self.n_shards > 1 else ")"))
        return prompt

    def submit(self, req: GenRequest) -> int:
        """Enqueue a request; returns its rid. Admission happens in step().

        Raises ValueError on an invalid request (see :meth:`validate`) and
        :class:`RejectedError` when the engine was built with ``max_pending``
        and the FIFO is full (backpressure, not an error in the request)."""
        self.validate(req)
        return self.scheduler.submit(req)

    def step(self) -> List[GenResult]:
        """Admit queued requests into free slots (paged: against the
        free-block budget; with a controller, also against the rolling uJ
        bucket), then advance every active slot one step: a mixed
        prefill+decode chunk step while any slot is still streaming its
        prompt (chunked mode), a pure decode step otherwise.  Finally the
        control plane sheds any request that exhausted its energy budget.
        Returns requests finished this step."""
        with TraceAnnotation("engine.step"):
            with TraceAnnotation("engine.admit"):
                finished = self._admit_pending()
            active = self.scheduler.active_slots()
            if active:
                if self.chunked and any(s.prefilling for _, s in active):
                    finished += self._chunk_advance(active)
                else:
                    finished += self._decode_advance(active)
            if self.controller is not None:
                for rid in self.controller.over_budget(self):
                    res = self.cancel(rid, reason="energy_budget")
                    if res is not None:
                        finished.append(res)
            return finished

    def _admit_pending(self) -> List[GenResult]:
        """FIFO admission into free slots: stops at the first request the
        block budget (paged) or the controller's uJ bucket cannot take —
        head-blocking keeps admission order deterministic."""
        finished = []
        while self.scheduler.pending:
            rid, req = self.scheduler.peek_pending()
            shard = self.scheduler.pick_shard(
                self._bucket_len(len(req.prompt)), req.max_new)
            if shard is None:
                break
            if self.controller is not None and \
                    not self.controller.may_admit(self):
                break
            self.scheduler.pop_pending()
            sid = self.scheduler.free_slot(shard)
            self._admit(sid, rid, req)
            done = self._maybe_retire(sid)
            if done is not None:
                finished.append(done)
        return finished

    def _decode_advance(self, active) -> List[GenResult]:
        """Advance every decode-phase slot one generated token in one jitted
        pure-decode step (SpeculativeEngine overrides this with a
        draft-k/verify-one round)."""
        finished = []
        B = self.batch_size
        with TraceAnnotation("engine.prepare"):
            tokens = np.zeros(B, np.int32)
            index = np.zeros(B, np.int32)
            act = np.zeros(B, bool)
            seeds = np.zeros(B, np.uint32)
            spos = np.zeros(B, np.int32)
            temps = np.zeros(B, np.float32)
            topk = np.zeros(B, np.int32)
            topp = np.ones(B, np.float32)
            enc = np.zeros(B, np.int32)
            for i, s in active:
                tokens[i] = s.last_token
                index[i] = s.pos
                act[i] = True
                seeds[i] = np.uint32(s.req.seed)
                spos[i] = s.sample_pos
                temps[i] = s.req.temperature
                topk[i] = s.req.top_k
                topp[i] = s.req.top_p
                enc[i] = s.enc_len

            self.peak_concurrent = max(self.peak_concurrent, len(active))
            extra = ()
            kwargs = {}
            if self.paged:
                # append-on-decode: a slot crossing a block boundary draws one of
                # its reserved blocks before the step writes at pos
                for i, s in active:
                    if self.scheduler.kv_ensure(i, s.pos):
                        self._tables_dev = None
                needs = [1] * self.n_shards
                for i, s in active:
                    sh = self._shard_of(i)
                    needs[sh] = max(needs[sh], 1 + s.pos)
                extra, kwargs = self._paged_tables(needs)
            kwargs["sampled"] = bool((temps > 0).any())
            step_seed = self.seed + self._steps + 1 if self.fresh_noise else self.seed
        next_tok, share = self._run_step(active, lambda: self._decode(
            self.step_params, self.cache, jnp.asarray(tokens), jnp.asarray(index),
            jnp.asarray(act), jnp.uint32(step_seed), jnp.asarray(seeds),
            jnp.asarray(spos), jnp.asarray(temps), jnp.asarray(topk),
            jnp.asarray(topp), jnp.asarray(enc), *extra, **kwargs))
        with TraceAnnotation("engine.emit"):
            for i, s in active:
                s.energy_pj += float(share[self._shard_of(i)])
                s.steps += 1
                s.pos += 1
                t = int(next_tok[i])
                s.last_token = t
                s.generated.append(t)
                self._emit(s.rid, t)
                self._register_decode_blocks(i, s)
                done = self._maybe_retire(i)
                if done is not None:
                    finished.append(done)
        return finished

    def _chunk_advance(self, active) -> List[GenResult]:
        """One mixed prefill+decode step: prefill-phase slots consume up to
        `prefill_chunk` prompt tokens at their exact positions, decode-phase
        slots advance one generated token — all in one jitted call with a
        per-slot phase mask (`ntok`).  Energy is split e/B per row exactly
        like the pure decode step (every row flows the same C lanes through
        the crossbars); a prefill row's share accrues to its prefill energy."""
        B, C = self.batch_size, self.prefill_chunk
        with TraceAnnotation("engine.prepare"):
            tokens = np.zeros((B, C), np.int32)
            start = np.zeros(B, np.int32)
            ntok = np.ones(B, np.int32)
            act = np.zeros(B, bool)
            seeds = np.zeros(B, np.uint32)
            spos = np.zeros(B, np.int32)
            temps = np.zeros(B, np.float32)
            topk = np.zeros(B, np.int32)
            topp = np.ones(B, np.float32)
            for i, s in active:
                act[i] = True
                seeds[i] = np.uint32(s.req.seed)
                spos[i] = s.sample_pos
                temps[i] = s.req.temperature
                topk[i] = s.req.top_k
                topp[i] = s.req.top_p
                start[i] = s.pos
                if s.prefilling:
                    take = min(C, len(s.prompt) - s.pos)
                    tokens[i, :take] = s.prompt[s.pos:s.pos + take]
                    ntok[i] = take
                else:
                    tokens[i, 0] = s.last_token
            self.peak_concurrent = max(self.peak_concurrent, len(active))

            extra = ()
            kwargs = {}
            if self.paged:
                for i, s in active:
                    if not s.prefilling and self.scheduler.kv_ensure(i, s.pos):
                        self._tables_dev = None
                needs = [1] * self.n_shards
                for i, _ in active:
                    sh = self._shard_of(i)
                    needs[sh] = max(needs[sh], int(start[i] + ntok[i]))
                extra, kwargs = self._paged_tables(needs)
            kwargs["sampled"] = bool((temps > 0).any())
            step_seed = self.seed + self._steps + 1 if self.fresh_noise else self.seed
        next_tok, share = self._run_step(active, lambda: self._chunk(
            self.step_params, self.cache, jnp.asarray(tokens), jnp.asarray(start),
            jnp.asarray(ntok), jnp.asarray(act), jnp.uint32(step_seed),
            jnp.asarray(seeds), jnp.asarray(spos), jnp.asarray(temps),
            jnp.asarray(topk), jnp.asarray(topp), *extra, **kwargs))
        finished = []
        with TraceAnnotation("engine.emit"):
            for i, s in active:
                if s.prefilling:
                    s.prefill_energy_pj += float(share[self._shard_of(i)])
                    s.pos += int(ntok[i])
                    self.prefill_tokens_total += int(ntok[i])
                    if self.paged and self.prefix_cache:
                        # full prompt blocks just written become shareable
                        self.kv.register_filled(i, s.pos)
                    if not s.prefilling:        # final chunk: first sampled token
                        t = int(next_tok[i])
                        s.last_token = t
                        s.generated.append(t)
                        self._emit(s.rid, t)
                else:
                    s.energy_pj += float(share[self._shard_of(i)])
                    s.steps += 1
                    s.pos += 1
                    t = int(next_tok[i])
                    s.last_token = t
                    s.generated.append(t)
                    self._emit(s.rid, t)
                    self._register_decode_blocks(i, s)
                done = self._maybe_retire(i)
                if done is not None:
                    finished.append(done)
        return finished

    def _paged_tables(self, needs):
        """Stage the width-clamped block tables on device for a step whose
        per-shard write frontiers are `needs` (length n_shards): zero any
        prefix-cache evictions first, clamp the logical view to the
        block-rounded bucket of the furthest live write position (masks,
        gathers, and the fused kernel walk view_len positions instead of
        max_len), and re-upload only when the tables or a bucket changed.

        Sharded: the jit-static view width is the *max* over the shards'
        buckets (one SPMD program, one static shape) — but each shard's
        table rows are clamped to its **own** bucket, entries past it
        resolving to the zero block.  A long request on one shard therefore
        never makes another shard gather real blocks past its own frontier;
        the per-shard buckets are observable as `shard_view_lens`.

        Returns (extra_args, kwargs) for the jitted step; shared by the pure
        decode and mixed chunk paths."""
        with TraceAnnotation("engine.tables"):
            self._zero_evicted()
            buckets = tuple(view_bucket(n, self.block_size, self.max_len)
                            for n in needs)
            vlen = max(buckets)
            key = (vlen, buckets)
            if self._tables_dev is None or self._tables_dev[0] != key:
                tg, tl = self.kv.gather_tables()
                width = -(-vlen // self.block_size)
                tg = tg[:, :width].copy()
                if self.n_shards > 1:
                    for sh, b in enumerate(buckets):
                        w = -(-b // self.block_size)
                        lo = sh * self.shard_size
                        tg[lo:lo + self.shard_size, w:] = self.kv.zero_block_g
                self._tables_dev = (key, jnp.asarray(tg), jnp.asarray(tl))
            self.view_len = vlen
            self.shard_view_lens = list(buckets)
            return self._tables_dev[1:], {"view_len": vlen}

    def _run_step(self, active, call):
        """Dispatch one jitted step (``call``: the step with its argument
        uploads), block until the device has run it, and book its aux.
        Returns the sampled tokens on the host and the per-slot energy shares
        (:meth:`_book_step`).  The explicit wait gives the host's block on
        the device a span of its own; the pulls after it find the outputs
        ready."""
        with TraceAnnotation("engine.dispatch"):
            next_tok, self.cache, eaux = call()
        with TraceAnnotation("engine.wait"):
            jax.block_until_ready((next_tok, eaux))
        with TraceAnnotation("engine.book"):
            share = self._book_step(eaux, active)
            return np.asarray(next_tok), share

    def _book_step(self, eaux, active) -> np.ndarray:
        """Book one jitted step's aux into the engine totals.  Returns the
        (n_shards,) per-active-slot energy shares: every row issues the same
        crossbar reads per step, so an active slot is billed its *shard's*
        energy over the shard's rows, e_s / (batch_size / n_shards)
        (occupancy-independent), and the idle rows' share accrues to the
        shard's slice of idle_energy_pj — shared by the pure decode and
        mixed chunk paths.  Unsharded engines are the n_shards == 1 case of
        the same arithmetic (e / batch_size, bit-identical to the historic
        scalar path)."""
        self._steps += 1
        kv = np.asarray(eaux["kv_reads"], np.float64).reshape(-1)
        e = np.asarray(eaux["energy_pj"], np.float64).reshape(-1)
        self.kv_reads_total += float(kv.sum())
        self.shard_kv_reads += kv
        self._book_corners(eaux["corners"])
        self.total_energy_pj += float(e.sum())
        self.shard_energy_pj += e
        n_act = np.zeros(self.n_shards, np.int64)
        for i, _ in active:
            n_act[self._shard_of(i)] += 1
        self.shard_occupancy += n_act
        share = e / self.shard_size
        idle_inc = share * (self.shard_size - n_act)
        self.shard_idle_energy_pj += idle_inc
        self.idle_energy_pj += float(idle_inc.sum())
        return share

    def _register_decode_blocks(self, slot_id: int, s: Slot) -> None:
        """Decode-block registration: when a decode step fills a block (the
        slot's write frontier crosses a block boundary), extend the slot's
        rolling-hash chain over its *written stream* — prompt ++ generated
        tokens — and register the filled block in the prefix registry.  An
        identical few-shot continuation (same prompt, same greedy
        continuation, longer max_new) then admits against the decode-written
        blocks with zero incremental prefill energy, exactly like a prompt
        prefix hit."""
        if not (self.paged and self.prefix_cache):
            return
        if s.pos % self.block_size:
            return
        # written positions are [0, pos): prompt, then every generated token
        # except the newest (sampled this step, written next step)
        stream = np.concatenate(
            [s.prompt, np.asarray(s.generated[:-1], np.int32)])
        self.kv.register_filled(slot_id, s.pos, stream=stream)

    def _zero_evicted(self):
        """Zero blocks the prefix cache evicted for reuse — their stale K/V
        must never be gatherable by the new owner (same hygiene as
        zero-on-retire for unregistered blocks)."""
        if not (self.paged and self.prefix_cache):
            return
        if self.n_shards > 1:
            self._zero_evicted_sharded()
            return
        evicted = self.kv.pool_g.pop_evicted()
        if not evicted:
            return
        for lo in range(0, len(evicted), self.kv.width_g):
            ids = self._pad_ids(evicted[lo:lo + self.kv.width_g],
                                self.kv.width_g, self.kv.zero_block_g + 1)
            empty_l = self._pad_ids([], self.kv.width_l,
                                    self.kv.zero_block_l + 1)
            self.cache = self._zero_retired(self.cache, jnp.asarray(ids),
                                            jnp.asarray(empty_l),
                                            jnp.int32(0))

    def _zero_evicted_sharded(self):
        """Sharded eviction hygiene: each shard zeroes its own evicted ids —
        one (n_shards, width) grid per round, sentinel rows for shards with
        nothing to zero (their scatters drop)."""
        per_shard = self.kv.pop_evicted_g()
        if not any(per_shard):
            return
        n, wg, wl = self.n_shards, self.kv.width_g, self.kv.width_l
        rounds = max(-(-len(ids) // wg) for ids in per_shard if ids)
        for r in range(rounds):
            ids_g = np.full((n, wg), self.kv.zero_block_g + 1, np.int32)
            for sh, ids in enumerate(per_shard):
                chunk = ids[r * wg:(r + 1) * wg]
                ids_g[sh, :len(chunk)] = chunk
            ids_l = np.full((n, wl), self.kv.zero_block_l + 1, np.int32)
            slot = np.full(n, self.shard_size, np.int32)   # OOB -> dropped
            self.cache = self._zero_retired(self.cache, jnp.asarray(ids_g),
                                            jnp.asarray(ids_l),
                                            jnp.asarray(slot))

    def _emit(self, rid: int, token: int) -> None:
        if self.on_token is not None:
            self.on_token(rid, token)

    def cancel(self, rid: int, reason: str = "cancelled") -> Optional[GenResult]:
        """Cancel request `rid` wherever it is: still queued (removed, empty
        result) or bound to a slot (retired immediately with its partial
        tokens).  The slot's paged blocks are freed through the same
        refcount/zero-on-retire hygiene as a natural retirement — shared
        prefix-cache blocks only lose one reference and stay hit-able — and
        the energy already billed to the request rides out on the result, so
        per-request + idle == total conservation holds with cancelled
        partials.  `reason` becomes ``done_reason`` ("cancelled"/"timeout").
        Returns None when `rid` is unknown or already finished."""
        if self.scheduler.remove_pending(rid) is not None:
            return GenResult(rid=rid, tokens=np.zeros(0, np.int32),
                             energy_pj=0.0, prefill_energy_pj=0.0, steps=0,
                             done_reason=reason)
        slot_id = self.scheduler.slot_of(rid)
        if slot_id is None:
            return None
        return self._retire(slot_id, reason)

    def drain(self, stall_limit: int = 8) -> List[GenResult]:
        """Run step() until queue and slots are empty.

        Forward-progress guard: an active slot advances its position every
        step (prefill chunk or decode token), so a step that changes nothing
        — no admission, no position advance, no retirement, queue length
        unchanged — means the engine can never retire anything again (e.g. a
        pending request whose block budget is held by a leaked owner).
        `stall_limit` identical steps raise RuntimeError with the stuck
        state instead of spinning forever."""
        out = []
        stalled, last = 0, None
        while self.scheduler.busy:
            out.extend(self.step())
            snap = (self.scheduler.pending, len(out),
                    tuple((i, s.pos) for i, s in
                          self.scheduler.active_slots()))
            if snap == last:
                stalled += 1
                if stalled >= stall_limit:
                    slots = [f"slot {i}: rid={s.rid} pos={s.pos} "
                             f"prefilling={s.prefilling} "
                             f"generated={len(s.generated)}"
                             for i, s in self.scheduler.active_slots()]
                    pool = ""
                    if self.paged:
                        pool = (f"; pool free={self.kv.pool_g.num_free}"
                                f"/{self.kv.pool_g.num_blocks} blocks")
                    raise RuntimeError(
                        f"drain() made no progress for {stalled} steps: "
                        f"{self.scheduler.pending} pending, "
                        f"{self.scheduler.num_active} active "
                        f"[{'; '.join(slots) or 'none'}]{pool}")
            else:
                stalled = 0
            last = snap
        return out

    # -- metrics -------------------------------------------------------------
    def reset_metrics(self):
        """Zero every accounting counter (energy ledgers, shard splits,
        kv-read/prefill totals, occupancy integrals, the noise clock and the
        concurrency high-water mark) without touching serving state.

        This is the between-phases reset the benches need after warmup: the
        compiled steps, pools and scheduler stay live, only the books open
        fresh.  Requires an idle engine — resetting mid-flight would break
        per-request + idle == total conservation for in-flight requests."""
        assert not self.scheduler.busy, "reset_metrics() requires an idle engine"
        self.total_energy_pj = 0.0
        self.idle_energy_pj = 0.0
        self.corner_energy_pj = {}
        self.shard_energy_pj[:] = 0.0
        self.shard_idle_energy_pj[:] = 0.0
        self.shard_corner_energy_pj = {}
        self.shard_kv_reads[:] = 0.0
        self.shard_occupancy[:] = 0
        self._steps = 0
        self.peak_concurrent = 0
        self.kv_reads_total = 0.0
        self.prefill_tokens_total = 0
        self.cached_prefix_tokens = 0

    def metrics(self) -> dict:
        """Plain-python snapshot of the accounting counters (JSON-safe)."""
        return {
            "total_energy_pj": float(self.total_energy_pj),
            "idle_energy_pj": float(self.idle_energy_pj),
            "corner_energy_pj": {k: float(v)
                                 for k, v in self.corner_energy_pj.items()},
            "shard_energy_pj": [float(v) for v in self.shard_energy_pj],
            "shard_idle_energy_pj": [float(v)
                                     for v in self.shard_idle_energy_pj],
            "shard_occupancy": [int(v) for v in self.shard_occupancy],
            "steps": int(self._steps),
            "peak_concurrent": int(self.peak_concurrent),
            "kv_reads_total": float(self.kv_reads_total),
            "prefill_tokens_total": int(self.prefill_tokens_total),
            "cached_prefix_tokens": int(self.cached_prefix_tokens),
            "emt_prepared_layers": int(self.emt_prepared_layers),
            "emt_inline_layers": int(self.emt_inline_layers),
        }

    def energy_conserved(self, results, rtol: float = 1e-6) -> bool:
        """Per-request billed + idle waste == engine total (the conservation
        invariant, including partial/shed/cancelled results)."""
        billed = float(sum(r.energy_pj for r in results))
        return bool(np.isclose(billed + self.idle_energy_pj,
                               self.total_energy_pj, rtol=rtol))

    # -- batch-mode wrapper --------------------------------------------------
    def generate(self, requests):
        """Submit `requests` together and drain. Returns (token arrays in
        submission order, EMT energy in pJ billed to these requests). Resets
        the noise clock so repeated calls are bit-identical."""
        assert not self.scheduler.busy, "generate() requires an idle engine"
        self._steps = 0
        rids = [self.submit(r) for r in requests]
        res = {r.rid: r for r in self.drain()}
        outs = [np.asarray(res[rid].tokens) for rid in rids]
        return outs, float(sum(res[rid].energy_pj for rid in rids))

    def serve(self, requests, stagger: int = 0) -> List[GenResult]:
        """Streaming driver: submit one request every `stagger` steps
        (0 = all upfront), then run to completion. Returns results in
        submission (rid) order."""
        results = []
        for r in requests:
            self.submit(r)
            for _ in range(max(stagger, 0)):
                results += self.step()
        results += self.drain()
        return sorted(results, key=lambda r: r.rid)

    # -- internals -----------------------------------------------------------
    def _admit(self, slot_id: int, rid: int, req: GenRequest):
        """Bind `req` to slot `slot_id`.

        Chunked mode (default for decoder-only attention stacks): allocate
        the slot's blocks (+ decode reservation) and place the slot in the
        prefill phase — the prompt streams into the cache chunk by chunk
        through the mixed step, directly into pool blocks, with no separate
        prefill call.  With the prefix cache on, admission first walks the
        prompt's rolling-hash chain: resident shared prefix blocks are
        refcount-shared (their prefill is skipped entirely — zero incremental
        energy/kv_reads) and a partially shared tail block is reused
        copy-on-write.

        Legacy mode (recurrent / enc-dec / mrope stacks): prefill `req` alone
        into a power-of-two bucket (batch 1, compiled once per bucket) and
        scatter the rows into the slot's cache region, sampling the first
        token from the prefill logits."""
        prompt = np.asarray(req.prompt, np.int32).reshape(-1)
        if self.chunked:
            pos = 0
            if self.paged:
                if self.prefix_cache:
                    res = self.kv.admit_prefix(slot_id, prompt, req.max_new)
                    assert res is not None, "admission raced the block budget"
                    self._tables_dev = None
                    self._zero_evicted()
                    if res["cow"] is not None:
                        src, dst = res["cow"]
                        if self.n_shards > 1:
                            sh = self._shard_of(slot_id)
                            sv = np.zeros(self.n_shards, np.int32)
                            dv = np.full(self.n_shards,
                                         self.kv.zero_block_g + 1, np.int32)
                            sv[sh], dv[sh] = src, dst
                            self.cache = self._pool_copy(
                                self.cache, jnp.asarray(sv), jnp.asarray(dv))
                        else:
                            self.cache = self._pool_copy(
                                self.cache, jnp.int32(src), jnp.int32(dst))
                    pos = res["cached_len"]
                    self.cached_prefix_tokens += pos
                else:
                    ok = self.scheduler.kv_admit(slot_id, len(prompt),
                                                 req.max_new)
                    assert ok, "admission raced the block budget"
                    self._tables_dev = None
            self.scheduler.place(slot_id, Slot(rid=rid, req=req, pos=pos,
                                               last_token=0, prompt=prompt))
            return
        S = self._bucket_len(len(prompt))
        # bucket >= max_len: prefill at exact length (one extra compile for
        # the rare near-capacity prompt); left-pad into the bucket otherwise
        toks = np.zeros((1, S), np.int32)
        toks[0, S - len(prompt):] = prompt               # left-pad preserved
        batch = {"tokens": jnp.asarray(toks)}
        if self.cfg.input_kind == "embeds":
            batch["embeds"] = jnp.zeros((1, S, self.cfg.d_model), jnp.float32)
        if self.cfg.is_encdec:
            batch["enc_embeds"] = jnp.zeros((1, S, self.cfg.d_model), jnp.float32)
        small = lm.init_cache(self.cfg, 1, self.max_len)
        small, logits, aux = self._prefill(self.step_params, batch, small,
                                           jnp.uint32(self.seed))
        if self.paged:
            ok = self.scheduler.kv_admit(slot_id, S, req.max_new)
            assert ok, "admission raced the block budget"   # step() checked
            self._tables_dev = None
            row_g, row_l = self.kv.scatter_rows(slot_id)
            self.cache = self._insert(self.cache, small, jnp.asarray(row_g),
                                      jnp.asarray(row_l), jnp.int32(slot_id))
        else:
            self.cache = self._insert(self.cache, small, jnp.int32(slot_id))
        prefill_e = float(aux["energy_pj"])
        self._book_corners(aux["corners"])
        self.total_energy_pj += prefill_e
        tok0 = int(self._sample(
            logits, jnp.asarray([req.temperature], jnp.float32),
            jnp.asarray([req.top_k], jnp.int32),
            jnp.asarray([req.top_p], jnp.float32),
            jnp.asarray([req.seed], jnp.uint32),
            jnp.asarray([0], jnp.int32))[0])
        self.scheduler.place(slot_id, Slot(
            rid=rid, req=req, pos=S, last_token=tok0, generated=[tok0],
            prefill_energy_pj=prefill_e,
            enc_len=S if self.cfg.is_encdec else 0))
        self._emit(rid, tok0)

    def _maybe_retire(self, slot_id: int) -> Optional[GenResult]:
        s = self.scheduler.slots[slot_id]
        if not s.generated:
            return None                  # still streaming its prompt
        if s.req.eos_id is not None and s.generated[-1] == s.req.eos_id:
            reason = "eos"
        elif len(s.generated) >= s.req.max_new:
            reason = "max_new"
        elif s.pos >= self.max_len:
            reason = "max_len"           # cache exhausted: truncate
        else:
            return None
        return self._retire(slot_id, reason)

    def _retire(self, slot_id: int, reason: str) -> GenResult:
        """Release slot `slot_id` with ``done_reason=reason``: free its paged
        blocks (refcount-aware) or contiguous region, zero whatever became
        blank, and return the request's result — shared by natural
        retirement (_maybe_retire) and cancellation/timeout (cancel())."""
        slot = self.scheduler.retire(slot_id)
        # zero the retiring request's cache before its region/blocks can be
        # backfilled — stale K/V must never be gatherable by a later request
        if self.paged:
            freed_g, freed_l = self.scheduler.kv_release(slot_id)
            self._tables_dev = None
            if self.n_shards > 1:
                # one (n_shards, W) grid: the retiring slot's shard row holds
                # its freed local ids + local slot index, every other shard's
                # row is all sentinels (scatters drop)
                sh = self._shard_of(slot_id)
                n = self.n_shards
                ids_g = np.full((n, self.kv.width_g),
                                self.kv.zero_block_g + 1, np.int32)
                ids_g[sh, :len(freed_g)] = freed_g
                ids_l = np.full((n, self.kv.width_l),
                                self.kv.zero_block_l + 1, np.int32)
                ids_l[sh, :len(freed_l)] = freed_l
                slot_v = np.full(n, self.shard_size, np.int32)
                slot_v[sh] = slot_id - sh * self.shard_size
                self.cache = self._zero_retired(
                    self.cache, jnp.asarray(ids_g), jnp.asarray(ids_l),
                    jnp.asarray(slot_v))
            else:
                ids_g = self._pad_ids(freed_g, self.kv.width_g,
                                      self.kv.zero_block_g + 1)
                ids_l = self._pad_ids(freed_l, self.kv.width_l,
                                      self.kv.zero_block_l + 1)
                self.cache = self._zero_retired(self.cache,
                                                jnp.asarray(ids_g),
                                                jnp.asarray(ids_l),
                                                jnp.int32(slot_id))
        elif self.n_shards > 1:
            sh = self._shard_of(slot_id)
            slot_v = np.full(self.n_shards, self.shard_size, np.int32)
            slot_v[sh] = slot_id - sh * self.shard_size
            self.cache = self._zero_retired(self.cache, jnp.asarray(slot_v))
        else:
            self.cache = self._zero_retired(self.cache, jnp.int32(slot_id))
        return GenResult(
            rid=slot.rid, tokens=np.asarray(slot.generated, np.int32),
            energy_pj=slot.prefill_energy_pj + slot.energy_pj,
            prefill_energy_pj=slot.prefill_energy_pj, steps=slot.steps,
            done_reason=reason, draft_energy_pj=slot.draft_energy_pj,
            spec_proposed=slot.spec_proposed,
            spec_accepted=slot.spec_accepted)
