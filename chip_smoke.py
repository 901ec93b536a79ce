"""Serve full-width gemma3-1b on a TPU through the paged Pallas path.

    python3 chip_smoke.py             # one chip
    python3 chip_smoke.py --chips 4   # data-parallel serving over 4 chips

One chip: checks the paged decode and prefill kernels against
``kernels/ref.py`` at the model's shapes, then serves seeded requests through
the normal entry points (``ServeSpec`` -> ``build_engine`` ->
``StreamingServer``, as ``python -m repro.launch.serve --rate`` does) with
every attention layer on the compiled Pallas rung, and serves the same
requests again on the jnp reference rung to compare tokens.

``--chips 4`` runs only the data-parallel path: a 4-shard engine over the
chips against a 1-shard engine on the first, token for token.

The model is gemma3-1b at its published widths (d_model 1152, 4 query heads,
1 kv head of 256, d_ff 6912, vocab 262144, sliding window 512) with random
weights from a seed.  Depth is cut from 26 layers to 2, one sliding-window
layer and one global layer, so both attention paths run: the engine compiles
one step program per view bucket, and an analog step program compiles in
about 35 s at 2 layers, 100 s at 6 and 4 minutes at 26 (ahead-of-time
compiles for v5e).

The script refuses to run without a TPU.  Any failed check exits non-zero;
only a run where every phase passed prints the last line,
``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

# depth cut (see above): one sliding-window layer, then one global layer
LAYERS = {"num_layers": 2, "layer_pattern": ("local", "global")}
BATCH = 4
MAX_LEN = 1024
MAX_NEW = 32
BLOCK_SIZE = 16
PREFILL_CHUNK = 256
# prompts over 512 tokens wrap the sliding-window ring layers (window 512)
PROMPT_LENS = (640, 128, 530, 300, 600, 200, 450, 576)
# the 4-chip check: at most 224 + 32 positions per request keep every step
# in the 256-position view bucket, so each engine compiles one chunk and one
# decode program (four chips cost four times)
SHARDED_PROMPT_LENS = (200, 128, 224, 160, 208, 136, 192, 176)
SEED = 0
# kernel tolerance against the HIGHEST-precision reference: the kernels'
# fp32 matmuls take one bf16 MXU pass (each operand rounded to 8 mantissa
# bits), which moves outputs of these N(0, 1) inputs by up to 2.4e-3
# (decode) and 8.2e-3 (prefill, 4096 query rows) on a TPU v5e; a wrong
# block, mask or head moves them by O(1)
KERNEL_ATOL = 1e-2


def fail(msg: str):
    print(f"chip_smoke: FAILED: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def require_tpu(chips: int):
    import jax

    devices = jax.devices()
    d = devices[0]
    if d.platform != "tpu":
        fail(f"needs a TPU; JAX found platform {d.platform!r} "
             f"({d.device_kind}) — there is no CPU fallback")
    print(f"device: {d.platform} {d.device_kind!r} x {len(devices)}",
          flush=True)
    if len(devices) < chips:
        fail(f"--chips {chips} needs {chips} devices, found {len(devices)}")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


def import_repro():
    src = os.path.join(os.path.dirname(os.path.abspath(__file__)), "src")
    sys.path.insert(0, src)
    try:
        import repro  # noqa: F401
    except ImportError as e:
        fail(f"cannot import the repro package from {src}: {e}")


class CompileClock:
    """Seconds the XLA backend spends compiling (process-wide)."""

    def __init__(self):
        import jax

        self.total = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.total += duration


def base_spec():
    from repro.serve.spec import ServeSpec

    # greedy, frozen noise and a per-row DAC scale: a request's tokens are a
    # function of the request alone, whatever the schedule or co-tenants
    return ServeSpec(arch="gemma3-1b", smoke=False, mode="analog",
                     a_per_row=True, frozen_noise=True, seed=SEED,
                     batch_size=BATCH, max_len=MAX_LEN, paged=True,
                     block_size=BLOCK_SIZE, paged_attn_impl="pallas",
                     prefill_chunk=PREFILL_CHUNK,
                     model_overrides=LAYERS)


def make_requests(vocab: int, lens):
    from repro.serve.engine import GenRequest

    rng = np.random.default_rng(SEED)
    return [GenRequest(prompt=rng.integers(0, vocab, n).astype(np.int32),
                       max_new=MAX_NEW, seed=i)
            for i, n in enumerate(lens)]


def check_kernels(cfg, impl: str):
    """Paged decode (fused write) and prefill kernels vs kernels/ref.py at
    the model's attention shapes, on pools holding random K/V."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops

    rng = np.random.default_rng(SEED)
    B, KV, hd = BATCH, cfg.num_kv_heads, cfg.head_dim
    G = cfg.num_heads // KV
    T = MAX_LEN // BLOCK_SIZE
    nb = B * T
    normal = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)  # noqa: E731
    k_pool = normal(nb + 1, BLOCK_SIZE, KV, hd).at[nb].set(0.0)
    v_pool = normal(nb + 1, BLOCK_SIZE, KV, hd).at[nb].set(0.0)
    table = jnp.asarray(rng.permutation(nb).reshape(B, T), jnp.int32)
    # full, ring-wrapping, just past one 512-position chunk, short
    lens = np.array([MAX_LEN, MAX_LEN * 11 // 16, MAX_LEN // 2 + 1, 37])
    mask = jnp.asarray(np.where(np.arange(MAX_LEN)[None] < lens[:, None],
                                0.0, -1e30), jnp.float32)
    q = normal(B, KV, G, hd)
    k_new, v_new = normal(B, KV, hd), normal(B, KV, hd)
    wpos = jnp.asarray(lens - 1, jnp.int32)
    args = (q, k_pool, v_pool, table, mask, k_new, v_new, wpos, None)
    out, kp, vp = ops.paged_attention_decode(*args, impl=impl)
    with jax.default_matmul_precision("highest"):
        ref, kr, vr = ops.paged_attention_decode(*args, impl="ref")
    err = float(jnp.max(jnp.abs(out - ref)))
    same_pools = bool(jnp.all(kp == kr) & jnp.all(vp == vr))
    print(f"decode kernel vs reference: max |err| {err:.3e} "
          f"(tolerance {KERNEL_ATOL:g}, max |ref| "
          f"{float(jnp.max(jnp.abs(ref))):.3f}), written pools identical: "
          f"{same_pools}", flush=True)
    if not (err <= KERNEL_ATOL and same_pools):
        fail("paged decode kernel disagrees with kernels/ref.py")

    C = PREFILL_CHUNK
    qc = normal(B, C, KV * G, hd)
    start = np.array([0, MAX_LEN // 4, MAX_LEN // 2 - C // 2, MAX_LEN - C])
    qpos = jnp.asarray(start[:, None] + np.arange(C)[None], jnp.int32)
    out = ops.paged_prefill(qc, kp, vp, table, qpos, impl=impl)
    with jax.default_matmul_precision("highest"):
        ref = ops.paged_prefill(qc, kp, vp, table, qpos, impl="ref")
    err = float(jnp.max(jnp.abs(out - ref)))
    print(f"prefill kernel vs reference: max |err| {err:.3e} "
          f"(tolerance {KERNEL_ATOL:g})", flush=True)
    if not err <= KERNEL_ATOL:
        fail("paged prefill kernel disagrees with kernels/ref.py")


def check_results(eng, results, n_requests: int, label: str):
    """Every request retired with max_new tokens (or on eos), and
    per-request + idle energy equals the engine total."""
    if len(results) != n_requests:
        fail(f"{label}: {len(results)} of {n_requests} requests came back")
    for r in results:
        n = len(r.tokens)
        if not (r.done_reason == "max_new" and n == MAX_NEW
                or r.done_reason == "eos" and 1 <= n <= MAX_NEW):
            fail(f"{label}: request {r.rid} retired {r.done_reason!r} "
                 f"with {n} tokens")
    if not eng.energy_conserved(results):
        fail(f"{label}: per-request + idle energy != engine total")


def token_lists(results):
    return {r.rid: [int(t) for t in r.tokens] for r in results}


def run_one_chip(clock):
    import jax

    from repro.launch.serve import print_attn_paths, serve_streaming
    from repro.models import lm
    from repro.models.attention import paged_attn_plan
    from repro.nn.param import init_params

    spec = base_spec()
    cfg = spec.build_config()
    print(f"model: {cfg.name} layers {cfg.num_layers} d_model {cfg.d_model} "
          f"heads {cfg.num_heads}/{cfg.num_kv_heads}x{cfg.head_dim} d_ff "
          f"{cfg.d_ff} vocab {cfg.vocab_size}, EMT {cfg.emt.mode}",
          flush=True)
    check_kernels(cfg, spec.paged_attn_impl)

    print_attn_paths(cfg)
    plan = paged_attn_plan(cfg)
    rung = f"fused paged kernel [{spec.paged_attn_impl}]"
    off = [p for p, res in plan if res != rung]
    if len(plan) != cfg.num_layers or off:
        fail(f"attention layers off the compiled Pallas rung: {off}")

    params = init_params(lm.specs(cfg), jax.random.PRNGKey(SEED))
    reqs = make_requests(cfg.vocab_size, PROMPT_LENS)
    eng = spec.build_engine(cfg, params)
    c0, t0 = clock.total, time.monotonic()
    results, wall, rejected, ttft, itl = serve_streaming(
        eng, reqs, rate=1000.0, deadline_s=None, max_pending=len(reqs),
        seed=SEED)
    if rejected:
        fail(f"{rejected} requests rejected at admission")
    check_results(eng, results, len(reqs), "pallas")
    served = sum(len(r.tokens) for r in results)
    print(f"served {len(results)} requests / {served} tokens through "
          f"StreamingServer in {time.monotonic() - t0:.1f} s, of which "
          f"compile {clock.total - c0:.1f} s; TTFT p50 "
          f"{np.percentile(ttft, 50):.2f} s (host clock, compile included)",
          flush=True)

    ref_spec = spec.replace(paged_attn_impl="ref")
    ref_eng = ref_spec.build_engine(ref_spec.build_config(), params)
    c0 = clock.total
    for r in reqs:
        ref_eng.submit(r)
    ref_results = ref_eng.drain()
    check_results(ref_eng, ref_results, len(reqs), "ref")
    got, want = token_lists(results), token_lists(ref_results)
    same = [rid for rid in want if got[rid] == want[rid]]
    print(f"reference rung rerun (compile {clock.total - c0:.1f} s): "
          f"{len(same)}/{len(want)} requests token-identical", flush=True)
    for rid in sorted(set(want) - set(same)):
        a, b = got[rid], want[rid]
        first = next(i for i, (x, y) in enumerate(zip(a, b)) if x != y)
        print(f"  request {rid}: first differing token at {first}: "
              f"pallas {a[first:first + 4]} ref {b[first:first + 4]}")
    if len(same) != len(want):
        fail("the Pallas rung and the reference rung served different tokens")
    stats = jax.devices()[0].memory_stats() or {}
    print(f"peak device memory: {stats.get('peak_bytes_in_use')} bytes",
          flush=True)


def run_sharded(clock):
    """4-shard engine over the chips vs a 1-shard engine: same requests,
    same tokens."""
    import jax

    from repro.models import lm
    from repro.nn.param import init_params

    spec = base_spec()
    cfg = spec.build_config()
    params = init_params(lm.specs(cfg), jax.random.PRNGKey(SEED))
    reqs = make_requests(cfg.vocab_size, SHARDED_PROMPT_LENS)
    runs = {}
    for shards, batch in ((1, BATCH), (4, 2 * 4)):
        s = spec.replace(shards=shards, batch_size=batch)
        eng = s.build_engine(cfg, params)
        c0, t0 = clock.total, time.monotonic()
        for r in reqs:
            eng.submit(r)
        results = eng.drain()
        check_results(eng, results, len(reqs), f"shards={shards}")
        runs[shards] = token_lists(results)
        print(f"shards={shards} batch {s.batch_size}: "
              f"{sum(map(len, runs[shards].values()))} tokens in "
              f"{time.monotonic() - t0:.1f} s (compile "
              f"{clock.total - c0:.1f} s), shard occupancy "
              f"{eng.shard_occupancy.tolist()}", flush=True)
    same = sum(runs[4][rid] == runs[1][rid] for rid in runs[1])
    print(f"4-shard vs 1-shard: {same}/{len(runs[1])} requests "
          f"token-identical", flush=True)
    if same != len(runs[1]):
        fail("sharded serving changed tokens")


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the data-parallel serving check")
    args = ap.parse_args()
    device = require_tpu(args.chips)
    import_repro()
    from repro.launch.cache import enable_compile_cache

    print(f"compile cache: {enable_compile_cache()}", flush=True)
    clock = CompileClock()
    if args.chips == 4:
        run_sharded(clock)
    else:
        run_one_chip(clock)
    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()
