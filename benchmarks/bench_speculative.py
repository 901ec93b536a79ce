"""Heterogeneous speculative decoding: analog uJ/token vs plain greedy decode.

    PYTHONPATH=src python benchmarks/bench_speculative.py [--out BENCH_serve.json]

Runs the same greedy request batch twice on a calibrated analog target
placement (PCM corner, per-row DAC quantization, frozen noise):

* **baseline** — the plain continuous-batching engine, one analog decode
  step per token;
* **speculative** — `repro.serve.speculative.SpeculativeEngine`: a
  `sram_digital` draft placement (same weights, deterministic digital
  execution) proposes `--spec-k` tokens per slot, the analog target verifies
  them in one (k+1)-lane all-lane chunk step.

Because every committed token is the target's greedy token given its prefix,
the two runs are token-identical (asserted, recorded as
``token_identity``) — the comparison isolates *energy*, not quality.  The
analog win comes from amortizing the per-tile static macro-activation cost
(:meth:`repro.core.device.DeviceModel.static_energy`, the array-to-system
efficiency gap of measured PCM silicon — docs/device_models.md) over the
verify chunk's lanes; the rejected lanes' dynamic energy works against it,
so the result is a genuine function of the accept rate.

Writes the ``speculative`` section of ``BENCH_serve.json`` (merged into the
existing report).  CI gates (scripts/check_bench_json.py): accept rate in
(0, 1], draft + target energy summing to the total, conservation and token
identity flags, and — at accept rate >= 0.5 — a strictly positive analog
uJ/token improvement.
"""
from __future__ import annotations

import argparse
import json
import os
import time

import jax
import numpy as np

from repro.launch.cache import enable_compile_cache
from repro.models import lm
from repro.nn.param import init_params
from repro.serve.engine import GenRequest
from repro.serve.spec import ServeSpec

TARGET_CORNER = "pcm"
DRAFT_CORNER = "sram_digital"


def _spec(arch: str, num_layers: int, **kw) -> ServeSpec:
    # speculative decoding requires an all-global attention stack (rejected
    # drafts would clobber sliding-window ring K/V) and per-row DAC scales
    # (per-tensor activation quantization couples verify lanes, breaking
    # bit-identity with the 1-lane decode step)
    return ServeSpec(arch=arch, mode="analog", device=TARGET_CORNER,
                     smoke=True, all_global=True, a_per_row=True,
                     model_overrides={"num_layers": num_layers}, **kw)


def _requests(cfg, n, prompt_len, max_new):
    out = []
    for i in range(n):
        rng = np.random.default_rng(1000 + i)
        out.append(GenRequest(
            prompt=rng.integers(0, cfg.vocab_size, prompt_len).astype(np.int32),
            max_new=max_new))
    return out


def _run(eng, reqs):
    t0 = time.monotonic()
    results = eng.serve(reqs)
    wall = time.monotonic() - t0
    tokens = sum(len(r.tokens) for r in results)
    conserved = eng.energy_conserved(results)
    corners_ok = bool(np.isclose(sum(eng.corner_energy_pj.values()),
                                 eng.total_energy_pj, rtol=1e-6))
    return {
        "results": results,
        "tokens": tokens,
        "wall_s": wall,
        "tok_s": tokens / max(wall, 1e-9),
        "total_uj": eng.total_energy_pj * 1e-6,
        "corners_uj": {k: v * 1e-6 for k, v in eng.corner_energy_pj.items()},
        "energy_conserved": conserved and corners_ok,
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=None,
                    help="merge the section into this BENCH_serve.json")
    ap.add_argument("--arch", default="gemma3-1b")
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--spec-k", type=int, default=4)
    ap.add_argument("--smoke", action="store_true",
                    help="shrink for the CI bench-smoke job")
    args = ap.parse_args()
    enable_compile_cache()
    if args.smoke:
        # shrink the request count only: shortening max_new instead would
        # clamp k_eff on a larger fraction of rounds (the last k tokens of a
        # request draft short) and understate the static-energy amortization
        args.requests = min(args.requests, 4)

    base_spec = _spec(args.arch, args.layers, batch_size=args.batch,
                      max_len=args.prompt_len + args.max_new + 4, seed=7,
                      frozen_noise=True)
    cfg = base_spec.build_config()
    params = init_params(lm.specs(cfg), jax.random.PRNGKey(0))
    reqs = _requests(cfg, args.requests, args.prompt_len, args.max_new)

    base_eng = base_spec.build_engine(cfg, params)
    base = _run(base_eng, reqs)
    spec_eng = base_spec.replace(draft_placement=DRAFT_CORNER,
                                 spec_k=args.spec_k).build_engine(cfg, params)
    spec = _run(spec_eng, reqs)

    token_identity = all(
        np.array_equal(a.tokens, b.tokens)
        for a, b in zip(base["results"], spec["results"]))

    base_analog_uj = base["corners_uj"].get(TARGET_CORNER, 0.0)
    spec_analog_uj = spec["corners_uj"].get(TARGET_CORNER, 0.0)
    draft_uj = spec_eng.draft_total_energy_pj * 1e-6
    target_uj = spec["total_uj"] - draft_uj
    base_per_tok = base_analog_uj / max(base["tokens"], 1)
    spec_per_tok = spec_analog_uj / max(spec["tokens"], 1)

    section = {
        "arch": args.arch,
        "layers": args.layers,
        "batch": args.batch,
        "requests": args.requests,
        "prompt_len": args.prompt_len,
        "max_new": args.max_new,
        "spec_k": args.spec_k,
        "target_corner": TARGET_CORNER,
        "draft_corner": DRAFT_CORNER,
        "accept_rate": round(spec_eng.accept_rate, 4),
        "accept_len_hist": spec_eng.accept_len_hist.tolist(),
        "token_identity": token_identity,
        "energy_conserved": bool(base["energy_conserved"]
                                 and spec["energy_conserved"]),
        # draft/verify split of the speculative run (uJ; CI checks the sum)
        "draft_energy_uj": round(draft_uj, 6),
        "target_energy_uj": round(target_uj, 6),
        "total_energy_uj": round(spec["total_uj"], 6),
        "baseline": {
            "analog_uj_per_token": round(base_per_tok, 6),
            "total_uj_per_token": round(base["total_uj"]
                                        / max(base["tokens"], 1), 6),
            "tok_s": round(base["tok_s"], 2),
            "corners_uj": {k: round(v, 6)
                           for k, v in base["corners_uj"].items()},
        },
        "speculative": {
            "analog_uj_per_token": round(spec_per_tok, 6),
            "total_uj_per_token": round(spec["total_uj"]
                                        / max(spec["tokens"], 1), 6),
            "tok_s": round(spec["tok_s"], 2),
            "corners_uj": {k: round(v, 6)
                           for k, v in spec["corners_uj"].items()},
        },
        "analog_uj_per_token_improvement": round(base_per_tok - spec_per_tok,
                                                 6),
    }

    if args.out:
        report = {}
        if os.path.exists(args.out):
            with open(args.out) as f:
                report = json.load(f)
        report["speculative"] = section
        with open(args.out, "w") as f:
            json.dump(report, f, indent=2)
    print(json.dumps({"speculative": section}, indent=2))


if __name__ == "__main__":
    main()
