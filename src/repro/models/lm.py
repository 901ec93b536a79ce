"""Top-level language models: decoder-only LM and encoder-decoder.

Public API (all functional):

    specs(cfg)                                  -> ParamSpec tree
    train_loss(params, batch, cfg, ctx)          -> (loss, metrics)
    prefill(params, batch, cfg, ctx, max_len)    -> (cache, last_logits, aux)
    decode_step(params, cache, tokens, cfg, ctx) -> (logits, new_cache, aux)
    init_cache_specs(cfg, batch, max_len)        -> abstract cache tree

`batch` dict: "tokens" (B,S) int32 or "embeds" (B,S,D) for vlm/audio stubs, plus
"labels" (B,S) for training; enc-dec adds "enc_embeds"/"enc_tokens".
"""
from __future__ import annotations


import jax
import jax.numpy as jnp

from repro.core.emt_linear import new_aux, add_aux
from repro.core import regularizer
from repro.models import common
from repro.models.config import ModelConfig
from repro.models.context import Ctx
from repro.models import stack as stk


# ---------------------------------------------------------------------------
# specs
# ---------------------------------------------------------------------------
def specs(cfg: ModelConfig) -> dict:
    kinds = cfg.blocks()
    moe_mask = cfg.moe_layer_mask()
    s = {
        "embed": common.embedding_specs(cfg.vocab_size, cfg.d_model, cfg.dtype),
        "decoder": stk.stack_specs(cfg, cfg.num_layers, kinds, moe_mask,
                                   cross=cfg.is_encdec, tag="dec"),
        "final_norm": common.rmsnorm_specs(cfg.d_model),
    }
    head_emt = cfg.emt_at("unembed")
    if not cfg.tie_embeddings:
        s["lm_head"] = common.unembed_specs(cfg.d_model, cfg.vocab_size,
                                            head_emt, cfg.dtype)
    elif head_emt.active:
        # tied table reused as the crossbar — still needs its energy coefficient
        from repro.nn.param import ParamSpec, constant_init
        s["lm_head"] = {"rho_raw": ParamSpec(
            (), jnp.float32, (),
            constant_init(regularizer.rho_init_raw(head_emt.rho_init)))}
    if cfg.is_encdec:
        enc_kinds = tuple("attn" for _ in range(cfg.encoder_layers))
        enc_moe = tuple(False for _ in range(cfg.encoder_layers))
        s["encoder"] = stk.stack_specs(cfg, cfg.encoder_layers, enc_kinds,
                                       enc_moe, tag="enc")
        s["enc_norm"] = common.rmsnorm_specs(cfg.d_model)
    return s


# ---------------------------------------------------------------------------
# input embedding
# ---------------------------------------------------------------------------
def _embed_inputs(params, batch, cfg: ModelConfig, ctx: Ctx):
    if cfg.input_kind == "embeds" and "embeds" in batch:
        x = batch["embeds"].astype(cfg.dtype)
    else:
        x = common.embed(params["embed"], batch["tokens"], cfg.embed_scale,
                         cfg.d_model)
    return ctx.shard(x, ("batch", "seq", "embed"))


def _encode(params, batch, cfg: ModelConfig, ctx: Ctx):
    """Bidirectional encoder (seamless audio stub: precomputed frame embeds)."""
    enc_x = batch.get("enc_embeds")
    if enc_x is None:
        enc_x = common.embed(params["embed"], batch["enc_tokens"],
                             cfg.embed_scale, cfg.d_model)
    enc_x = enc_x.astype(cfg.dtype)
    B, S = enc_x.shape[:2]
    pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    valid = jnp.ones((B, S), bool)
    mask = common.full_mask(valid, valid)
    kinds = tuple("attn" for _ in range(cfg.encoder_layers))
    moe = tuple(False for _ in range(cfg.encoder_layers))
    y, aux, _ = stk.apply_stack(params["encoder"], enc_x, cfg, kinds, moe,
                                ctx=ctx, tag="enc", positions=pos, mask=mask,
                                remat=cfg.remat)
    return common.rmsnorm(params["enc_norm"], y, cfg.norm_eps), pos, aux


def tied_weights(cfg: ModelConfig) -> tuple:
    """(layer path, source path) of EMT dense layers whose weight is another
    leaf's transpose: a tied LM head reads the embedding table."""
    return ((("lm_head",), ("embed", "table")),) if cfg.tie_embeddings else ()


def _logits(params, h, cfg: ModelConfig, ctx: Ctx):
    tied = params["embed"]["table"] if cfg.tie_embeddings else None
    p = params.get("lm_head", {})
    y, aux = common.unembed(p, h, cfg.emt_at("unembed"), tied_table=tied,
                            seed=ctx.seed, key=ctx.key)
    y = common.softcap(y.astype(cfg.logit_dtype), cfg.final_softcap)
    return y, aux


# ---------------------------------------------------------------------------
# training forward + loss
# ---------------------------------------------------------------------------
def train_loss(params, batch, cfg: ModelConfig, ctx: Ctx, lam: float = 0.0):
    x = _embed_inputs(params, batch, cfg, ctx)
    B, S = x.shape[:2]
    pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    masks = {"global": common.causal_mask(pos, pos),
             "local": common.causal_mask(pos, pos, cfg.sliding_window)}

    enc_out = enc_mask = None
    aux = new_aux()
    if cfg.is_encdec:
        enc_out, enc_pos, a = _encode(params, batch, cfg, ctx)
        aux = add_aux(aux, a)
        valid = jnp.ones(enc_pos.shape, bool)
        enc_mask = common.full_mask(jnp.ones((B, S), bool), valid)

    h, a, _ = stk.apply_stack(
        params["decoder"], x, cfg, cfg.blocks(), cfg.moe_layer_mask(), ctx=ctx,
        tag="dec", positions=pos, mask=masks, enc_out=enc_out, enc_mask=enc_mask,
        remat=cfg.remat)
    aux = add_aux(aux, a)
    h = common.rmsnorm(params["final_norm"], h, cfg.norm_eps)
    logits, a = _logits(params, h, cfg, ctx)
    aux = add_aux(aux, a)

    labels = batch["labels"]
    # Sharded-vocab-safe CE: take_along_axis over a model-sharded vocab dim
    # makes SPMD all-gather the full (B,S,V) fp32 logits (measured: +192 GB/chip
    # temps, +198 GB/chip all-reduce on gemma3-1b train_4k — EXPERIMENTS.md
    # §Perf it.1). The masked-sum form keeps every reduction local + a small
    # (B,S) all-reduce, and never materializes log_softmax.
    logits_f = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits_f, axis=-1)
    vocab_iota = jax.lax.broadcasted_iota(jnp.int32, logits_f.shape,
                                          logits_f.ndim - 1)
    picked = jnp.sum(jnp.where(vocab_iota == labels[..., None], logits_f, 0.0),
                     axis=-1)
    ce = jnp.mean(lse - picked)
    loss = ce + lam * aux["reg"] + aux["aux_loss"]
    metrics = {
        "loss": loss, "ce": ce,
        "energy_uj": aux["energy_pj"] * 1e-6,
        "reg": aux["reg"], "aux_loss": aux["aux_loss"],
        "rho_mean": aux["rho_sum"] / max(1, aux["rho_layers"]),
    }
    # per-corner energy breakdown (flat scalar keys: the train loop JSONL
    # logger floats every metric). Corner labels are static per placement.
    for name, c in aux["corners"].items():
        metrics[f"energy_uj/{name}"] = c["energy_pj"] * 1e-6
    return loss, metrics


# ---------------------------------------------------------------------------
# serving: prefill + decode
# ---------------------------------------------------------------------------
def init_cache_specs(cfg: ModelConfig, batch: int, max_len: int):
    kinds = cfg.blocks()
    cache = {}
    for i, kind in enumerate(kinds):
        cache[f"layer_{i:03d}"] = stk.block_state_specs(
            cfg, kind, batch, max_len,
            cross_len=max_len if cfg.is_encdec else 0)
    return cache


def init_cache(cfg: ModelConfig, batch: int, max_len: int):
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                        init_cache_specs(cfg, batch, max_len))


# ---------------------------------------------------------------------------
# paged (block-table) KV cache
# ---------------------------------------------------------------------------
def paged_lens(cfg: ModelConfig, max_len: int) -> dict:
    """Logical per-slot cache lengths for the paged layout.

    Mirrors the contiguous rule in ``stack.block_state_specs``: sliding-window
    layers hold ``min(window, max_len)`` positions; when the window does not
    shrink the cache they share the global table (``ring`` False, lens equal).
    The explicit ``ring`` flag (not lens equality) routes local layers to the
    ring table downstream — the engine clamps the global view per decode step
    (``clamped_lens``), which may transiently equal the ring length."""
    ring = min(cfg.sliding_window, max_len) if cfg.sliding_window else 0
    has_ring = bool(ring and ring < max_len and "local" in cfg.blocks())
    return {"global": max_len, "local": ring if has_ring else max_len,
            "ring": has_ring}


def clamped_lens(page_lens_full: dict, view_len: int) -> dict:
    """Length-clamp the global logical view to ``view_len`` positions.

    ``view_len`` must be block-rounded and cover every live slot's write
    position (+1); the engine buckets it to a power-of-two block count so the
    decode step recompiles O(log) times, not once per length.  Ring layers
    keep their window-sized view — only the global/cross table is clamped."""
    lens = dict(page_lens_full)
    lens["global"] = min(int(view_len), page_lens_full["global"])
    if not lens["ring"]:
        lens["local"] = lens["global"]
    return lens


def init_paged_cache_specs(cfg: ModelConfig, batch: int, max_len: int,
                           block_size: int, num_blocks: int,
                           num_ring_blocks: int = 0):
    """Abstract paged cache: attention layers become block pools of shape
    (num_blocks + 1, block_size, kv_heads, head_dim) — the extra row is the
    never-written zero block that unallocated block-table entries gather from.
    Recurrent-state layers (mamba/xlstm) keep their per-slot (batch, ...) rows.
    """
    lens = paged_lens(cfg, max_len)
    kv_shape = (cfg.num_kv_heads, cfg.head_dim)
    cache = {}
    for i, kind in enumerate(cfg.blocks()):
        name = f"layer_{i:03d}"
        if kind in stk.ATTN_KINDS:
            ring = kind == "local" and lens["ring"]
            rows = (num_ring_blocks if ring else num_blocks) + 1
            blk = {"k": jax.ShapeDtypeStruct((rows, block_size) + kv_shape,
                                             cfg.dtype),
                   "v": jax.ShapeDtypeStruct((rows, block_size) + kv_shape,
                                             cfg.dtype)}
            if cfg.is_encdec:
                xrows = num_blocks + 1       # cross K/V pages the global table
                blk["ck"] = jax.ShapeDtypeStruct(
                    (xrows, block_size) + kv_shape, cfg.dtype)
                blk["cv"] = jax.ShapeDtypeStruct(
                    (xrows, block_size) + kv_shape, cfg.dtype)
            cache[name] = blk
        else:
            cache[name] = stk.block_state_specs(cfg, kind, batch, max_len)
    return cache


def init_paged_cache(cfg: ModelConfig, batch: int, max_len: int,
                     block_size: int, num_blocks: int,
                     num_ring_blocks: int = 0):
    return jax.tree.map(
        lambda s: jnp.zeros(s.shape, s.dtype),
        init_paged_cache_specs(cfg, batch, max_len, block_size, num_blocks,
                               num_ring_blocks))


def prefill(params, batch, cfg: ModelConfig, ctx: Ctx, cache):
    """Run the prompt through the model, filling `cache`.

    Returns (new_cache, last_token_logits, aux).
    """
    x = _embed_inputs(params, batch, cfg, ctx)
    B, S = x.shape[:2]
    pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    # prefill attends within the prompt (the not-yet-filled cache tail would be
    # masked anyway — attending over S instead of max_len is strictly cheaper)
    masks = {"global": common.causal_mask(pos, pos),
             "local": common.causal_mask(pos, pos, cfg.sliding_window)}

    enc_out = enc_mask = None
    aux = new_aux()
    if cfg.is_encdec:
        enc_out, enc_pos, a = _encode(params, batch, cfg, ctx)
        aux = add_aux(aux, a)
        enc_mask = common.full_mask(jnp.ones((B, S), bool),
                                    jnp.ones(enc_pos.shape, bool))

    h, a, new_caches = stk.apply_stack(
        params["decoder"], x, cfg, cfg.blocks(), cfg.moe_layer_mask(), ctx=ctx,
        tag="dec", positions=pos, mask=masks, caches=cache, cache_index=None,
        enc_out=enc_out, enc_mask=enc_mask, remat=False)
    aux = add_aux(aux, a)
    h = common.rmsnorm(params["final_norm"], h[:, -1:], cfg.norm_eps)
    logits, a = _logits(params, h, cfg, ctx)
    aux = add_aux(aux, a)
    merged = {k: {**cache[k], **v} for k, v in new_caches.items()} if new_caches \
        else cache
    for k in cache:
        merged.setdefault(k, cache[k])
    return merged, logits[:, 0], aux


def _cache_len(cache):
    # max across layers: sliding-window layers hold ring buffers shorter than
    # the global context
    lens = [blk["k"].shape[1] for blk in cache.values() if "k" in blk]
    return max(lens) if lens else 0


def chunk_step(params, cache, tokens, start, ntok, cfg: ModelConfig, ctx: Ctx,
               active=None, page_tables=None, page_lens=None,
               all_lanes: bool = False):
    """One mixed prefill+decode step over a (B, C) token chunk.

    The continuous-batching engine admits long prompts as a stream of
    fixed-size chunks interleaved with decode: in one jitted step every batch
    row advances by ``ntok[b]`` tokens written at absolute positions
    ``start[b] .. start[b] + ntok[b] - 1`` — up to C prompt tokens for a
    prefill-phase slot, exactly one generated token for a decode-phase slot
    (the per-slot phase mask is just ``ntok``; lanes past ``ntok[b]`` are
    padding whose writes are dropped and whose query positions are clamped to
    the row's last real lane so no softmax row is ever empty).  This replaces
    the separate batch-1 power-of-two-bucketed prefill call: prompts occupy
    their *exact* positions (no left-pad) and the prefill/decode compile split
    collapses into one compile per (C, view-bucket).

    Only attention-only decoder stacks are supported (recurrent state cannot
    skip padded lanes; enc-dec needs the encoder pass) — the engine keeps the
    legacy bucketed path for those.

    Returns (last_valid_logits (B, vocab), new_cache, aux) — or, with
    ``all_lanes=True``, the full per-lane logits (B, C, vocab): the verify
    primitive of speculative decoding (serve/speculative.py), where lane j's
    logits score the draft token proposed for position ``start[b] + j + 1``.
    """
    B, C = tokens.shape
    x = common.embed(params["embed"], tokens, cfg.embed_scale, cfg.d_model)
    x = x.astype(cfg.dtype)
    start = jnp.asarray(start)
    ntok = jnp.asarray(ntok)
    j = jnp.arange(C)[None, :]
    wpos = start[:, None] + j                         # (B, C) lane positions
    qpos = start[:, None] + jnp.minimum(j, ntok[:, None] - 1)
    L = page_lens["global"] if page_lens else (_cache_len(cache) or 1)
    k_pos = jnp.broadcast_to(jnp.arange(L)[None], (B, L))
    # write-then-attend (non-ring layers): the chunk's own K/V lands in the
    # pools at its true positions first, so plain causal masking covers both
    # the cached history and in-chunk attention — via the chunked-prefill
    # kernel (kernels.ops.paged_prefill, causality derived from qpos
    # in-kernel) when fused, via gather + this materialized mask otherwise;
    # ring layers build their own [ring view | fresh chunk] masks
    # (attention._chunk_attend)
    masks = {"global": common.causal_mask(qpos, k_pos),
             "local": common.causal_mask(qpos, k_pos, cfg.sliding_window)}

    h, aux, new_caches = stk.apply_stack(
        params["decoder"], x, cfg, cfg.blocks(), cfg.moe_layer_mask(), ctx=ctx,
        tag="dec", positions=wpos, mask=masks, caches=cache, cache_index=start,
        remat=False, active=active, page_tables=page_tables,
        page_lens=page_lens, chunk_lens=ntok)
    if all_lanes:
        # verify mode: every lane's logits are consumed (lane j scores the
        # next-token distribution after the chunk prefix ..start+j), so the
        # unembed runs — and bills energy — over all C lanes.
        h = common.rmsnorm(params["final_norm"], h, cfg.norm_eps)
        logits, a = _logits(params, h, cfg, ctx)
    else:
        # only each row's last real lane feeds sampling (decode rows: their
        # one token; prefill rows: the final prompt token on their last chunk)
        h_last = jnp.take_along_axis(h, (ntok - 1)[:, None, None], axis=1)
        h_last = common.rmsnorm(params["final_norm"], h_last, cfg.norm_eps)
        logits, a = _logits(params, h_last, cfg, ctx)
    aux = add_aux(aux, a)
    merged = {}
    for k in cache:
        upd = new_caches.get(k)
        merged[k] = {**cache[k], **upd} if upd else cache[k]
    return (logits if all_lanes else logits[:, 0]), merged, aux


def decode_step(params, cache, tokens, index, cfg: ModelConfig, ctx: Ctx,
                active=None, page_tables=None, page_lens=None, enc_lens=None):
    """One decode step: `tokens` (B,) generated at position `index`.

    `index` is either a scalar (lockstep: all rows at the same position) or a
    (B,) int vector (continuous batching: each slot decodes at its own
    position inside one jitted step).  `active` (B,) bool marks live slots —
    inactive rows still flow through the matmuls (SPMD batch) but their cache
    and recurrent-state rows are left untouched, so a retired slot's region
    stays frozen until the scheduler prefills a new request into it.

    `page_tables` ({"global": (B,Tg), "local": (B,Tl)} int32) + `page_lens`
    (static {"global": view_len, "local": ring_len, "ring": bool}) switch
    attention layers to the paged block-table cache layout (see
    lm.init_paged_cache).  `page_lens["global"]` is the *view length*: the
    engine clamps it (and the `Tg` table width) each step to the block-rounded
    bucket of the furthest live write position instead of max_len — masks,
    gathers, and the fused kernel's chunk walk all scale with what is actually
    resident (lm.clamped_lens).  On the fused path the step's cache write is
    folded into the attention launch (kernels.ops.paged_attention_decode:
    in-kernel scatter via input/output aliasing, inactive rows drop their
    write) — one kernel per layer per step, no separate scatter op.

    `enc_lens` (B,) int masks enc-dec cross-attention to each row's real
    encoder positions — serving engines cache ck/cv at max_len (zero-padded
    past the encoder length), and without the mask those phantom zero-K
    positions would each soak up exp(0) of softmax mass.

    Returns (logits (B, vocab), new_cache, aux).
    """
    B = tokens.shape[0]
    # modality stubs ("embeds" input kind) still decode text tokens
    x = common.embed(params["embed"], tokens[:, None], cfg.embed_scale,
                     cfg.d_model)
    x = x.astype(cfg.dtype)
    idx = jnp.asarray(index)
    if idx.ndim == 0:
        pos = jnp.broadcast_to(idx[None, None], (B, 1))
    else:
        pos = idx[:, None]                                # (B, 1) per-slot
    max_len = page_lens["global"] if page_lens else (_cache_len(cache) or 1)
    k_pos = jnp.broadcast_to(jnp.arange(max_len)[None], (B, max_len))
    masks = {"global": common.causal_mask(pos, k_pos),
             "local": common.causal_mask(pos, k_pos, cfg.sliding_window)}

    enc_mask = None
    if enc_lens is not None and cfg.is_encdec:
        valid_k = jnp.arange(max_len)[None, :] < jnp.asarray(enc_lens)[:, None]
        enc_mask = common.full_mask(jnp.ones((B, 1), bool), valid_k)

    h, aux, new_caches = stk.apply_stack(
        params["decoder"], x, cfg, cfg.blocks(), cfg.moe_layer_mask(), ctx=ctx,
        tag="dec", positions=pos, mask=masks, caches=cache, cache_index=index,
        remat=False, active=active, page_tables=page_tables,
        page_lens=page_lens, enc_mask=enc_mask)
    h = common.rmsnorm(params["final_norm"], h, cfg.norm_eps)
    logits, a = _logits(params, h, cfg, ctx)
    aux = add_aux(aux, a)
    merged = {}
    for k in cache:
        upd = new_caches.get(k)
        merged[k] = {**cache[k], **upd} if upd else cache[k]
    return logits[:, 0], merged, aux
