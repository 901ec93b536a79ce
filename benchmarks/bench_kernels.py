"""Microbenchmark: paged-attention decode + chunked prefill — legacy gather
paths vs the fused one-launch kernels.

    PYTHONPATH=src python benchmarks/bench_kernels.py [--out BENCH_kernels.json]

Decode cases — one step of GQA attention (B rows, one query token each)
against a max_len-position KV budget, across ``block_size``, ``occupancy in
{25%, 50%, 100%}`` and ``max_len in {256, 1024}``.  Every variant now times
the step's **cache write too** (the fused kernel folds it into the attention
launch, so the legacy paths must pay their scatter for an honest ratio):

* ``contiguous``     — in-place row update + dense attention over the
  (B, max_len) contiguous cache (the pre-paging engine's decode step).
* ``gather_full``    — PR 2's fallback: pool scatter write, then
  ``paged_gather`` materializes the full (B, max_len) logical view through
  the block table, then dense attention.
* ``gather_clamped`` — the same write + gather clamped to the block-rounded
  power-of-two bucket of the furthest live position
  (``serve.engine.view_bucket``) — the current kernel-off fallback.
* ``fused``          — one launch: ``kernels.ops.paged_attention_decode``
  (in-kernel cache write via input/output aliasing + table-walk attend).
  On CPU this times the jnp reference rung (scatter + clamped-view
  batch-GEMM attend — the production CPU shape); on TPU the pallas rung
  scatters and reads block tiles inside the kernel and the view is never
  materialized, which is what the bytes model below describes.

Prefill cases — one chunked-prefill step (B rows × C query lanes) over a
**phase-mixed** batch (row lengths staggered, as the scheduler batches
mixed-phase requests), after the chunk's K/V is written (the write is
path-identical, so it is excluded from both variants):

* ``legacy_gather`` — ``attention._chunk_attend``'s old shape: materialize
  the clamped (B, view_len) logical view, dense masked attend.
* ``kernel``        — ``kernels.ops.paged_prefill``: flash-style chunk walk
  through the table with in-register causality; whole KV chunks beyond a
  row's last query position are skipped (DMA never issued).

Timing is **interleaved round-robin**: one call of each variant per
iteration, medians per variant — back-to-back per-variant loops drift with
clock/cache state and were worth >10% on the decode ratio.

Bytes-moved estimates (the quantity the paper's energy argument cares
about — crossbar/HBM K/V traffic):

* decode ``contiguous`` / ``gather_full``: B * max_len * KV * hd * 2 arrays
  * itemsize (every logical position touched, allocated or not);
  ``gather_clamped`` / ``fused``: the same over view_len (the pallas rung
  DMAs one tile per clamped-width table entry; zero-block tails still paid).
* prefill ``legacy_gather``: 2 traversals of the clamped view — the gather
  *materializes* it (pool read + view write) and the attend reads it back;
  ``kernel``: a single traversal of only the chunks a row actually needs
  (``ceil((qlast+1)/span)*span`` positions, span = block_chunk *
  block_size from ``ops.pick_block_chunk``) — strictly fewer at every
  benched occupancy, enforced below and in scripts/check_bench_json.py.

Writes a JSON report to --out (BENCH_kernels.json at the repo root) with a
``ratios`` section gated by scripts/check_bench_json.py.
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops
from repro.launch.cache import enable_compile_cache
from repro.models.attention import paged_gather
from repro.models.common import NEG_INF
from repro.serve.engine import view_bucket


def _roundrobin_wall(variants, iters=20, warmup=2):
    """Median wall per variant, interleaved one-call-per-variant rounds."""
    for fn, args in variants.values():
        for _ in range(warmup):
            jax.block_until_ready(fn(*args))
    ts = {name: [] for name in variants}
    for _ in range(iters):
        for name, (fn, args) in variants.items():
            t0 = time.perf_counter()
            jax.block_until_ready(fn(*args))
            ts[name].append(time.perf_counter() - t0)
    return {name: float(np.median(v)) * 1e6 for name, v in ts.items()}


def _attend_dense(q, k, v, mask, scale):
    """One-shot-softmax decode attention over a materialized (B, L) view."""
    s = jnp.einsum("bkgh,bskh->bkgs", q, k,
                   preferred_element_type=jnp.float32) * scale
    s = s + mask[:, None, None, :]
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bkgs,bskh->bkgh", p.astype(v.dtype), v,
                      preferred_element_type=jnp.float32)


def _attend_chunk_dense(q, k, v, mask_rows, scale):
    """Legacy chunked-prefill attend over a materialized (B, L) view.

    q (B, C, H, hd); k/v (B, L, KV, hd); mask_rows (B, C, L) additive fp32.
    The einsum form mirrors `_gqa_core`'s contraction on the gathered view.
    """
    B, C, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    qt = q.reshape(B, C, KV, G, hd).transpose(0, 2, 1, 3, 4)
    qt = qt.reshape(B, KV, C * G, hd)
    s = jnp.einsum("bkrh,blkh->bkrl", qt, k,
                   preferred_element_type=jnp.float32) * scale
    s = s + jnp.repeat(mask_rows, G, axis=1)[:, None]
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkrl,blkh->bkrh", p.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    o = o.reshape(B, KV, C, G, hd).transpose(0, 2, 1, 3, 4)
    return o.reshape(B, C, H * hd)


def bench_decode_case(*, B, KV, G, hd, max_len, block_size, occupancy, dtype,
                      iters, seed=0):
    rng = np.random.default_rng(seed)
    itemsize = jnp.dtype(dtype).itemsize
    filled = max(1, int(round(occupancy * max_len)))
    width = -(-max_len // block_size)
    used = -(-filled // block_size)
    num_blocks = B * width
    scale = 1.0 / np.sqrt(hd)

    q = jnp.asarray(rng.normal(size=(B, KV, G, hd)), dtype)
    kp = jnp.asarray(rng.normal(size=(num_blocks + 1, block_size, KV, hd)),
                     dtype).at[num_blocks].set(0.0)
    vp = jnp.asarray(rng.normal(size=(num_blocks + 1, block_size, KV, hd)),
                     dtype).at[num_blocks].set(0.0)
    k_new = jnp.asarray(rng.normal(size=(B, KV, hd)), dtype)
    v_new = jnp.asarray(rng.normal(size=(B, KV, hd)), dtype)
    # per-row tables: `used` allocated blocks, rest -> zero block
    tab = np.full((B, width), num_blocks, np.int32)
    perm = rng.permutation(num_blocks)
    for b in range(B):
        tab[b, :used] = perm[b * used:(b + 1) * used]
    table = jnp.asarray(tab)
    k_cont = jnp.asarray(rng.normal(size=(B, max_len, KV, hd)), dtype)
    v_cont = jnp.asarray(rng.normal(size=(B, max_len, KV, hd)), dtype)
    idx = filled - 1                       # this step's write position
    causal = lambda L: jnp.where(  # noqa: E731
        jnp.arange(L)[None, :] <= idx, 0.0, NEG_INF).astype(
        jnp.float32) * jnp.ones((B, 1), jnp.float32)
    vlen = view_bucket(filled, block_size, max_len)
    cwidth = -(-vlen // block_size)
    wblk = jnp.asarray(tab[:, idx // block_size])       # (B,) allocated
    woff = idx % block_size
    wpos = jnp.full((B,), idx, jnp.int32)

    contiguous = jax.jit(lambda q, k, v, kn, vn: _attend_dense(
        q, k.at[:, idx].set(kn), v.at[:, idx].set(vn), causal(max_len),
        scale))

    def _scatter_gather(q, kp, vp, t, kn, vn, L):
        kp = kp.at[wblk, woff].set(kn)
        vp = vp.at[wblk, woff].set(vn)
        return _attend_dense(q, paged_gather(kp, t, L),
                             paged_gather(vp, t, L), causal(L), scale)

    gather_full = jax.jit(
        lambda q, kp, vp, t, kn, vn: _scatter_gather(
            q, kp, vp, t, kn, vn, max_len))
    gather_clamped = jax.jit(
        lambda q, kp, vp, t, kn, vn: _scatter_gather(
            q, kp, vp, t, kn, vn, vlen))
    fused = jax.jit(lambda q, kp, vp, t, kn, vn: ops.paged_attention_decode(
        q, kp, vp, t, causal(vlen), kn, vn, wpos, None, impl="auto"))

    wall = _roundrobin_wall({
        "contiguous": (contiguous, (q, k_cont, v_cont, k_new, v_new)),
        "gather_full": (gather_full, (q, kp, vp, table, k_new, v_new)),
        "gather_clamped": (gather_clamped,
                           (q, kp, vp, table[:, :cwidth], k_new, v_new)),
        "fused": (fused, (q, kp, vp, table[:, :cwidth], k_new, v_new)),
    }, iters=iters)

    kv_elem = KV * hd * 2 * itemsize
    out = {
        "kind": "decode",
        "B": B, "KV": KV, "G": G, "hd": hd, "max_len": max_len,
        "block_size": block_size, "occupancy": occupancy, "filled": filled,
        "view_len": vlen,
        "wall_us": {k: round(v, 1) for k, v in wall.items()},
        "kv_bytes_moved": {
            "contiguous": B * max_len * kv_elem,
            "gather_full": B * max_len * kv_elem,
            "gather_clamped": B * vlen * kv_elem,
            # one tile per clamped-width table entry, zero-block tail incl.
            "fused": B * cwidth * block_size * kv_elem,
        },
    }
    return out


def bench_prefill_case(*, B, KV, G, hd, max_len, block_size, occupancy,
                       chunk, dtype, iters, seed=0):
    rng = np.random.default_rng(seed)
    itemsize = jnp.dtype(dtype).itemsize
    H = KV * G
    filled = max(chunk, int(round(occupancy * max_len)))
    # phase-mixed batch: row b holds a staggered fraction of `filled`
    row_fill = np.maximum(chunk, (filled * (B - np.arange(B)) // B))
    width = -(-max_len // block_size)
    num_blocks = B * width
    scale = 1.0 / np.sqrt(hd)

    q = jnp.asarray(rng.normal(size=(B, chunk, H, hd)), dtype)
    kp = jnp.asarray(rng.normal(size=(num_blocks + 1, block_size, KV, hd)),
                     dtype).at[num_blocks].set(0.0)
    vp = jnp.asarray(rng.normal(size=(num_blocks + 1, block_size, KV, hd)),
                     dtype).at[num_blocks].set(0.0)
    tab = np.full((B, width), num_blocks, np.int32)
    perm = rng.permutation(num_blocks)
    for b in range(B):
        used = -(-int(row_fill[b]) // block_size)
        tab[b, :used] = perm[b * width:b * width + used]
    # chunk lanes end at each row's fill point (lm.chunk_step's convention)
    qpos = jnp.asarray(row_fill[:, None] - chunk + np.arange(chunk)[None, :],
                       jnp.int32)
    vlen = view_bucket(int(row_fill.max()), block_size, max_len)
    cwidth = -(-vlen // block_size)
    table = jnp.asarray(tab[:, :cwidth])
    mask_rows = jnp.where(
        jnp.arange(vlen)[None, None, :] <= qpos[:, :, None], 0.0,
        NEG_INF).astype(jnp.float32)

    legacy = jax.jit(lambda q, kp, vp, t: _attend_chunk_dense(
        q, paged_gather(kp, t, vlen), paged_gather(vp, t, vlen), mask_rows,
        scale))
    kernel = jax.jit(lambda q, kp, vp, t: ops.paged_prefill(
        q, kp, vp, t, qpos, impl="auto"))

    wall = _roundrobin_wall({
        "legacy_gather": (legacy, (q, kp, vp, table)),
        "kernel": (kernel, (q, kp, vp, table)),
    }, iters=iters)

    kv_elem = KV * hd * 2 * itemsize
    cpb = ops.pick_block_chunk(cwidth, block_size, head_dim=hd,
                               kv_heads=KV, dtype_bytes=itemsize)
    span = cpb * block_size
    needed = np.minimum(vlen, -(-row_fill // span) * span)
    out = {
        "kind": "prefill",
        "B": B, "KV": KV, "G": G, "hd": hd, "max_len": max_len,
        "block_size": block_size, "occupancy": occupancy, "chunk": chunk,
        "row_fill": row_fill.tolist(), "view_len": vlen,
        "block_chunk": cpb,
        "wall_us": {k: round(v, 1) for k, v in wall.items()},
        "kv_bytes_moved": {
            # materialize the view (pool read + view write) + attend read
            "legacy_gather": 2 * B * vlen * kv_elem,
            # single traversal, whole-chunk skip past each row's last lane
            "kernel": int(needed.sum()) * kv_elem,
        },
    }
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="BENCH_kernels.json")
    ap.add_argument("--arch", default=None,
                    help="derive --kv-heads/--group/--head-dim from this "
                         "arch's ServeSpec-built config instead of the "
                         "explicit shape flags (the serving-shape sweep)")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--kv-heads", type=int, default=4)
    ap.add_argument("--group", type=int, default=2)
    ap.add_argument("--head-dim", type=int, default=64)
    ap.add_argument("--max-len", type=int, default=256)
    ap.add_argument("--iters", type=int, default=30)
    ap.add_argument("--smoke", action="store_true",
                    help="shrink the sweep for the CI bench-smoke job")
    args = ap.parse_args()
    enable_compile_cache()
    if args.arch:
        from repro.serve.spec import ServeSpec
        cfg = ServeSpec(arch=args.arch, smoke=args.smoke).build_config()
        args.kv_heads = cfg.num_kv_heads
        args.group = cfg.num_heads // cfg.num_kv_heads
        args.head_dim = cfg.head_dim
        print(f"shape from {args.arch}: KV={args.kv_heads} G={args.group} "
              f"hd={args.head_dim}")
    if args.smoke:
        args.batch = min(args.batch, 4)
        args.max_len = min(args.max_len, 128)
        args.iters = min(args.iters, 8)

    common = dict(B=args.batch, KV=args.kv_heads, G=args.group,
                  hd=args.head_dim, dtype=jnp.float32)
    occs = (0.25, 1.0) if args.smoke else (0.25, 0.5, 1.0)

    cases = []
    sweep = [(bs, occ, args.max_len)
             for bs in ((8, 16) if args.smoke else (8, 16, 32))
             for occ in occs]
    if not args.smoke:
        # long-context rung: chunk heuristic spans multiple blocks here
        sweep += [(32, occ, 1024) for occ in occs]
    for block_size, occupancy, max_len in sweep:
        iters = args.iters if max_len <= 256 else max(6, args.iters // 3)
        cases.append(bench_decode_case(
            max_len=max_len, block_size=block_size, occupancy=occupancy,
            iters=iters, **common))
        c = cases[-1]
        print(f"decode  bs={block_size:3d} occ={occupancy:4.0%} "
              f"L={max_len:5d} wall_us={c['wall_us']}")

    prefill_cases = []
    pf_len = 256 if args.smoke else 1024
    pf_occs = (1.0,) if args.smoke else (0.25, 0.5, 1.0)
    for occupancy in pf_occs:
        prefill_cases.append(bench_prefill_case(
            max_len=pf_len, block_size=16, occupancy=occupancy,
            chunk=16 if args.smoke else 32,
            iters=max(6, args.iters // 3), **common))
        c = prefill_cases[-1]
        print(f"prefill bs= 16 occ={occupancy:4.0%} L={pf_len:5d} "
              f"wall_us={c['wall_us']} bytes={c['kv_bytes_moved']}")

    # acceptance invariants (structural — deterministic, not wall noise):
    # at partial occupancy the fused decode path moves strictly fewer K/V
    # bytes than the materialized full gather ...
    for c in cases:
        if c["occupancy"] < 1.0:
            assert (c["kv_bytes_moved"]["fused"]
                    < c["kv_bytes_moved"]["gather_full"]), c
    # ... and the prefill kernel strictly fewer than the materialized view
    # at EVERY benched occupancy (single traversal + whole-chunk skip)
    for c in prefill_cases:
        assert (c["kv_bytes_moved"]["kernel"]
                < c["kv_bytes_moved"]["legacy_gather"]), c

    # the wall-ratio the regression gate watches: fused one-launch decode vs
    # the clamped gather fallback at full occupancy (worst case for the
    # fused path — no clamping win left, ratio is pure kernel-vs-gather)
    occ100 = [c for c in cases if c["occupancy"] == 1.0]
    ratios = [round(c["wall_us"]["fused"] / c["wall_us"]["gather_clamped"], 3)
              for c in occ100]
    report = {
        "shape": {"B": args.batch, "KV": args.kv_heads, "G": args.group,
                  "hd": args.head_dim, "max_len": args.max_len,
                  "dtype": "float32", "smoke": bool(args.smoke),
                  "arch": args.arch},
        "note": ("decode variants all include the step's cache write; "
                 "fused/kernel impls timed on the jnp reference rung (CPU "
                 "production shape); the pallas rungs write + read block "
                 "tiles in-kernel on TPU. Interleaved round-robin timing. "
                 "Bytes are the analytic K/V traffic model from the module "
                 "docstring."),
        "cases": cases,
        "prefill_cases": prefill_cases,
        "ratios": {
            "fused_vs_gather_clamped": {
                "occ100_per_case": ratios,
                "occ100_max": max(ratios),
            },
        },
    }
    with open(args.out, "w") as f:
        json.dump(report, f, indent=2)
    print(f"wrote {args.out}  fused/gather_clamped occ100 max = "
          f"{max(ratios)}")


if __name__ == "__main__":
    main()
