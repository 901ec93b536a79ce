"""Declarative scenario-matrix executor + frontier reporting (ROADMAP item 4).

    PYTHONPATH=src python benchmarks/matrix.py [--smoke] [--out BENCH_serve.json]

One executor replaces N hand-written bench scenarios: a
:class:`repro.serve.MatrixSpec` (JSON round-trippable — ``--matrix FILE``
loads one; docs/benchmarks.md documents the schema) expands a base
:class:`ScenarioSpec` over declared axes into cells, and every cell runs
through the same engine stack (``ServeSpec.build_engine`` →
``ServingEngine``/``SpeculativeEngine``, Poisson cells through
``StreamingServer``), emitting one structured metrics dict:

* throughput — ``decode_tok_per_s`` (wall-clock; machine-dependent, never
  value-gated) and TTFT/ITL percentiles for open-loop cells,
* energy — ``uj_per_token`` (per-request billed, analytic/exact),
  ``engine_total_uj`` and the per-corner split, plus the conservation flag
  (per-request + idle == total, partials included),
* accuracy — ``accuracy_proxy``: the ablation harness trains one ideal CNN
  and evaluates it deployed on each device corner the cell's placement
  uses; the cell scores its *worst* corner (the deployment-accuracy floor
  of serving on that placement),
* identity — cells differing only along the matrix's ``identity_axes`` ran
  the same workload through different memory/kernel paths, so at
  temperature 0 + frozen noise + per-row DAC scale their tokens must match
  (the paged-vs-contiguous property, generalized to every axis slice).

``repro.analysis.frontier`` then reduces the cells to the Pareto frontier
per EMT surface (placement / corner / mode), written with the cells into
``BENCH_serve.json::matrix`` and rendered as a markdown artifact.  Two
legacy report sections (``shared_prefix``, ``poisson_load``) are also
emitted *from matrix cells* under ``matrix.legacy`` in the structure their
pre-matrix gates accept — one way to define a benchmark, not five.

The default matrix covers {placement x shared-prefix ratio x KV variant
(contiguous / paged+fused / paged+prefix-cache)} plus an open-loop Poisson
cell; ``--smoke`` shrinks it to the 2x2 CI slice (+ the Poisson cell).
``scripts/check_bench_json.py`` gates the section through the ``matrix``
entry of its gate registry.
"""
from __future__ import annotations

import argparse
import dataclasses
import hashlib
import json
import os
import time
from collections import Counter

import numpy as np

from repro.analysis.frontier import frontier_markdown, frontier_report
from repro.launch.cache import enable_compile_cache
from repro.serve.engine import GenRequest, prefill_bucket
from repro.serve.scheduler import RejectedError
from repro.serve.server import StreamingServer
from repro.serve.spec import MatrixSpec, ScenarioSpec, ServeSpec

try:  # package import (tests) vs script execution (CI, CLI)
    from benchmarks.bench_latency import _pct_ms
except ImportError:
    from bench_latency import _pct_ms


# -- the default matrix ------------------------------------------------------

KV_AXIS = {
    "contiguous": {"label": "contiguous",
                   "set": {"serve.paged": False, "serve.prefix_cache": False,
                           "serve.fused_paged_attn": False}},
    "paged_fused": {"label": "paged_fused",
                    "set": {"serve.paged": True, "serve.block_size": 8,
                            "serve.fused_paged_attn": True,
                            "serve.prefix_cache": False}},
    "paged_prefix": {"label": "paged_prefix",
                     "set": {"serve.paged": True, "serve.block_size": 8,
                             "serve.fused_paged_attn": True,
                             "serve.prefix_cache": True}},
}


def default_matrix(smoke: bool = False) -> MatrixSpec:
    """{placement x shared-prefix ratio x KV variant} + one Poisson cell.

    The base is the repo's determinism setting (frozen noise + per-row DAC
    scale + temperature 0 + all-global stack) so the KV axis is an identity
    axis: every KV variant of a slice must produce the same tokens.
    Arrivals are staggered two steps so a prefix-cache cell's header blocks
    register before the next admission (the realistic serving regime).
    """
    serve = ServeSpec(arch="gemma3-1b", mode="analog", smoke=True,
                      all_global=True, a_per_row=True, frozen_noise=True,
                      seed=7, batch_size=4, prefill_chunk=16,
                      paged_attn_impl="ref")
    base = ScenarioSpec(name="grid", serve=serve, arrival="stagger",
                        stagger=2, n_requests=4 if smoke else 8,
                        prompt_lo=32, prompt_hi=32,
                        max_new=4 if smoke else 8, workload_seed=11)
    axes = {
        "shared_prefix_ratio": (0.0, 0.5),
        "kv": ((KV_AXIS["paged_fused"], KV_AXIS["paged_prefix"]) if smoke
               else (KV_AXIS["contiguous"], KV_AXIS["paged_fused"],
                     KV_AXIS["paged_prefix"])),
    }
    if not smoke:
        axes = {"serve.placement": (None, "mixed"), **axes}
    poisson = ScenarioSpec(
        name="poisson", arrival="poisson",
        serve=serve.replace(paged=True, block_size=8, max_pending=16),
        rate_rps=20.0 if smoke else 4.0, n_requests=8 if smoke else 16,
        prompt_lo=6, prompt_hi=20, max_new=6 if smoke else 12,
        workload_seed=5)
    return MatrixSpec(name="serve-frontier-smoke" if smoke
                      else "serve-frontier", base=base, axes=axes,
                      identity_axes=("kv",), extra_cells=(poisson,))


# -- workload ----------------------------------------------------------------

def make_requests(cell: ScenarioSpec, vocab: int):
    """Deterministic request list for a cell: an optional shared header
    (``shared_prefix_ratio`` of ``prompt_lo``) + unique tails, lengths
    uniform in [prompt_lo, prompt_hi].  Depends only on the workload fields
    (never on serve/engine knobs), so cells in one identity group serve the
    exact same requests."""
    rng = np.random.default_rng(cell.workload_seed + 1_000)
    header = rng.integers(0, vocab, cell.header_len).astype(np.int32)
    kw = cell.serve.request_kwargs()
    reqs = []
    for i in range(cell.n_requests):
        n = int(rng.integers(cell.prompt_lo, cell.prompt_hi + 1))
        tail = rng.integers(0, vocab,
                            max(1, n - cell.header_len)).astype(np.int32)
        reqs.append(GenRequest(prompt=np.concatenate([header, tail]),
                               max_new=cell.max_new, seed=i, **kw))
    return reqs


def _warm(eng, reqs):
    """Compile every prefill bucket / view depth the run touches, then open
    the books fresh (the same discipline as the latency bench's warmup)."""
    buckets = sorted({prefill_bucket(len(r.prompt)) for r in reqs})
    deepest = max(r.max_new for r in reqs)
    for n in buckets:
        eng.submit(GenRequest(prompt=np.zeros(n, np.int32), max_new=deepest))
        eng.drain()
    for n in buckets:
        eng.submit(GenRequest(prompt=np.zeros(n, np.int32), max_new=deepest))
    eng.drain()
    eng.reset_metrics()


# -- accuracy proxy ----------------------------------------------------------
#
# One ideal-trained CNN (the ablation harness's `traditional` method on the
# vgg_small task), deployed per device corner via the rho graft — cached per
# corner, so a whole matrix pays one short training run plus one evaluation
# per distinct corner.  The proxy is *relative* (which placement degrades
# accuracy, and by how much), matching the paper's Fig. 9 framing; absolute
# values are synthetic-task accuracies.

_PROXY_CACHE: dict = {}


def _ablation():
    try:
        from benchmarks import ablation_lib
    except ImportError:
        import ablation_lib
    return ablation_lib


def _ideal_cnn(steps: int):
    key = ("__ideal__", steps)
    if key not in _PROXY_CACHE:
        ab = _ablation()
        from repro.configs.paper_cnn import vgg_small
        cfg = ab.method_config(vgg_small(), "traditional", 4.0)
        _PROXY_CACHE[key] = (cfg, ab.train_cnn(cfg, steps=steps))
    return _PROXY_CACHE[key]


def _corner_acc(corner: str, mode: str, *, steps: int, batches: int) -> float:
    key = (corner, mode, steps, batches)
    if key in _PROXY_CACHE:
        return _PROXY_CACHE[key]
    ab = _ablation()
    cfg, params = _ideal_cnn(steps)
    if mode in ("ideal", "fp32"):
        acc, _ = ab.evaluate(cfg, params, batches=batches)
    else:
        if corner in ("", mode):      # default (paper PCM-like) cell
            emt = ab._emt(mode, 4.0, trainable=False)
        else:
            from repro.core.placement import emt_for_corner
            emt = emt_for_corner(corner, mode)
        dep = dataclasses.replace(cfg, emt=emt)
        acc, _ = ab.evaluate(dep, ab._with_rho(dep, params), batches=batches)
    _PROXY_CACHE[key] = float(acc)
    return _PROXY_CACHE[key]


def accuracy_proxy(cfg, *, steps: int, batches: int):
    """(worst-corner accuracy, {corner: accuracy}) for a serving config."""
    pairs = sorted({(c, m) for _, c, m in cfg.placement_plan()})
    by_corner = {c or m: _corner_acc(c, m, steps=steps, batches=batches)
                 for c, m in pairs}
    return min(by_corner.values()), by_corner


# -- per-cell execution ------------------------------------------------------

def _params_key(spec: ServeSpec):
    """Cells sharing weights: everything that shapes lm.specs(cfg)."""
    return (spec.arch, spec.smoke, spec.mode, spec.device, spec.placement,
            spec.all_global, json.dumps(spec.model_overrides, sort_keys=True))


def _token_fingerprint(tokens: dict) -> str:
    h = hashlib.sha1()
    for rid in sorted(tokens):
        h.update(np.asarray(tokens[rid], np.int64).tobytes())
        h.update(b"|")
    return h.hexdigest()[:16]


def run_cell(cell: ScenarioSpec, *, params_cache: dict, proxy_steps: int,
             proxy_batches: int, with_proxy: bool = True):
    """Run one cell; returns (metrics dict, {rid: token array})."""
    import jax

    from repro.models import lm
    from repro.nn.param import init_params

    spec = cell.serve
    cfg = spec.build_config()
    key = _params_key(spec)
    if key not in params_cache:
        params_cache[key] = init_params(lm.specs(cfg), jax.random.PRNGKey(0))
    params = params_cache[key]
    max_len = spec.max_len or prefill_bucket(cell.prompt_hi) + cell.max_new
    eng = spec.build_engine(cfg, params, max_len=max_len)
    reqs = make_requests(cell, cfg.vocab_size)
    _warm(eng, reqs)

    handles, rejected = [], 0
    if cell.arrival == "poisson":
        rng = np.random.default_rng(cell.workload_seed)
        arrivals = np.cumsum(rng.exponential(1.0 / cell.rate_rps,
                                             len(reqs)))
        with StreamingServer(eng, max_pending=spec.max_pending) as srv:
            t0 = time.monotonic()
            for r, at in zip(reqs, arrivals):
                delay = t0 + at - time.monotonic()
                if delay > 0:
                    time.sleep(delay)
                try:
                    handles.append(srv.submit(r, deadline_s=spec.deadline_s))
                except RejectedError:
                    rejected += 1
            results = [h.result(timeout=600) for h in handles]
            wall = time.monotonic() - t0
    else:
        stagger = cell.stagger if cell.arrival == "stagger" else 0
        t0 = time.monotonic()
        results = eng.serve(reqs, stagger=stagger)
        wall = time.monotonic() - t0

    tokens = {r.rid: np.asarray(r.tokens) for r in results}
    toks = sum(len(t) for t in tokens.values())
    billed_uj = sum(r.energy_pj for r in results) * 1e-6
    em = eng.metrics()
    out = {
        "name": cell.name,
        "coords": [list(c) for c in cell.coords],
        "emt_label": spec.emt_label,
        "arrival": cell.arrival,
        "n_requests": len(reqs),
        "shared_prefix_ratio": cell.shared_prefix_ratio,
        "header_len": cell.header_len,
        "kv": "paged" if spec.paged else "contiguous",
        "prefix_cache": spec.prefix_cache,
        "tokens": toks,
        "wall_s": round(wall, 3),
        "decode_tok_per_s": round(toks / wall, 2) if wall else None,
        "steps": em["steps"],
        "peak_concurrent": em["peak_concurrent"],
        "total_uj": round(billed_uj, 4),
        "idle_uj": round(em["idle_energy_pj"] * 1e-6, 4),
        "engine_total_uj": round(em["total_energy_pj"] * 1e-6, 4),
        "uj_per_token": round(billed_uj / max(toks, 1), 5),
        "uj_per_token_by_corner": {
            k: round(v * 1e-6 / max(toks, 1), 5)
            for k, v in sorted(em["corner_energy_pj"].items())},
        "prefill_tokens_computed": em["prefill_tokens_total"],
        "cached_prefix_tokens": em["cached_prefix_tokens"],
        "energy_conserved": eng.energy_conserved(results),
        "done_reasons": dict(sorted(Counter(
            r.done_reason for r in results).items())),
        "token_fingerprint": _token_fingerprint(tokens),
    }
    if cell.arrival == "poisson":
        out["rejected"] = rejected
        out["offered_rate_rps"] = cell.rate_rps
        out["throughput_tok_per_s"] = out.pop("decode_tok_per_s")
        out["decode_tok_per_s"] = out["throughput_tok_per_s"]
        out["ttft_ms"] = _pct_ms([h.ttft_s for h in handles
                                  if h.ttft_s is not None])
        out["inter_token_ms"] = _pct_ms([d for h in handles
                                         for d in h.itl_s])
    if spec.draft_placement is not None:
        out["speculation"] = {k: em[k] for k in
                              ("accept_rate", "spec_rounds",
                               "spec_proposed_total", "spec_accepted_total",
                               "accept_len_hist", "draft_total_energy_pj")}
    if with_proxy:
        out["accuracy_proxy"], out["accuracy_by_corner"] = accuracy_proxy(
            cfg, steps=proxy_steps, batches=proxy_batches)
    return out, tokens


# -- cross-cell reductions ---------------------------------------------------

def check_identity(matrix: MatrixSpec, cells, metrics, tokens):
    """Token identity across each identity-axis slice: cells whose coords
    match outside ``identity_axes`` served the same workload, so their token
    streams must agree request-for-request.  Stamps ``token_identity`` on
    every grouped cell; returns the per-group summary."""
    groups: dict = {}
    for i, c in enumerate(cells):
        if not c.coords:
            continue
        groups.setdefault(c.group_key(matrix.identity_axes), []).append(i)
    report = {}
    for gkey, idx in sorted(groups.items()):
        ref = tokens[idx[0]]
        same = all(
            set(tokens[i]) == set(ref)
            and all(np.array_equal(tokens[i][r], ref[r]) for r in ref)
            for i in idx[1:])
        for i in idx:
            metrics[i]["token_identity"] = bool(same)
        label = "/".join(f"{a}={v}" for a, v in gkey) or "all"
        report[label] = {"cells": [metrics[i]["name"] for i in idx],
                         "identical": bool(same)}
    return report


def _cell_at(cells, metrics, **coords):
    for c, m in zip(cells, metrics):
        have = dict(c.coords)
        if all(have.get(k) == v for k, v in coords.items()):
            yield c, m


def legacy_sections(matrix: MatrixSpec, cells, metrics):
    """Re-emit pre-matrix report sections from matrix cells, in the exact
    structure their existing gates accept (the proof the matrix subsumes
    the hand-written scenarios)."""
    legacy = {}
    # shared_prefix: the shared=0.5 KV slice on the default placement
    slice_ = [(c, m) for c, m in _cell_at(cells, metrics,
                                          shared_prefix_ratio="0.5")
              if dict(c.coords).get("serve.placement", "none") == "none"]
    by_kv = {dict(c.coords)["kv"]: (c, m) for c, m in slice_
             if "kv" in dict(c.coords)}
    if {"paged_fused", "paged_prefix"} <= set(by_kv):
        off_c, off = by_kv["paged_fused"]
        _, on = by_kv["paged_prefix"]

        def sub(m):
            return {k: m[k] for k in
                    ("prefill_tokens_computed", "cached_prefix_tokens",
                     "tokens", "total_uj", "uj_per_token",
                     "decode_tok_per_s")}
        legacy["shared_prefix"] = {
            "source": "matrix",
            "n_requests": off_c.n_requests,
            "header_len": off_c.header_len,
            "shared_fraction": off_c.shared_prefix_ratio,
            "stagger": off_c.stagger,
            "cache_off": sub(off),
            "cache_on": sub(on),
            # every cell in the slice (contiguous included when the full
            # matrix runs it) decoded identical tokens
            "token_identity_paged_vs_contiguous": all(
                m.get("token_identity", False) for _, m in by_kv.values()),
            "prefill_tokens_ratio": round(
                off["prefill_tokens_computed"]
                / max(on["prefill_tokens_computed"], 1), 2),
            "uj_per_token_ratio": round(
                off["uj_per_token"] / max(on["uj_per_token"], 1e-12), 3),
        }
    # poisson_load: the open-loop extra cell
    for c, m in zip(cells, metrics):
        if c.arrival != "poisson":
            continue
        legacy["poisson_load"] = {
            "source": "matrix",
            "offered_rate_rps": c.rate_rps,
            "n_requests": c.n_requests,
            "submitted": c.n_requests - m.get("rejected", 0),
            "rejected": m.get("rejected", 0),
            "done_reasons": m["done_reasons"],
            "tokens": m["tokens"],
            "wall_s": m["wall_s"],
            "throughput_tok_per_s": m.get("throughput_tok_per_s"),
            "peak_concurrent": m["peak_concurrent"],
            "ttft_ms": m.get("ttft_ms"),
            "inter_token_ms": m.get("inter_token_ms"),
            "total_uj": m["total_uj"],
            "idle_uj": m["idle_uj"],
            "energy_conserved_with_partials": m["energy_conserved"],
        }
        break
    return legacy


def run_matrix(matrix: MatrixSpec, *, only=None, proxy_steps=60,
               proxy_batches=4, with_proxy=True, verbose=True):
    """Expand + execute a matrix; returns the ``matrix`` report section."""
    cells = matrix.expand()
    if only:
        known = {c.name for c in cells}
        unknown = sorted(set(only) - known)
        if unknown:
            raise SystemExit(f"unknown cell(s) {unknown}; known: "
                             f"{sorted(known)}")
        cells = [c for c in cells if c.name in only]
    params_cache: dict = {}
    metrics, tokens = [], []
    for cell in cells:
        t0 = time.time()
        m, toks = run_cell(cell, params_cache=params_cache,
                           proxy_steps=proxy_steps,
                           proxy_batches=proxy_batches,
                           with_proxy=with_proxy)
        metrics.append(m)
        tokens.append(toks)
        if verbose:
            print(f"cell {m['name']}: {m['tokens']} tok, "
                  f"{m['decode_tok_per_s']} tok/s, "
                  f"{m['uj_per_token']} uJ/tok "
                  f"[{time.time() - t0:.1f}s]", flush=True)
    identity = check_identity(matrix, cells, metrics, tokens)
    section = {
        "spec": matrix.to_dict(),
        "cells": metrics,
        "identity": identity,
        "frontier": frontier_report(metrics),
        "legacy": legacy_sections(matrix, cells, metrics),
    }
    return section


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--matrix", default=None,
                    help="JSON MatrixSpec file (default: built-in serve "
                         "frontier matrix; see docs/benchmarks.md)")
    ap.add_argument("--out", default="BENCH_serve.json",
                    help="merged into this report under 'matrix'")
    ap.add_argument("--markdown", default="FRONTIER_matrix.md",
                    help="frontier table artifact ('' disables)")
    ap.add_argument("--only", default=None,
                    help="comma-separated cell names to run (unknown names "
                         "error with the known list)")
    ap.add_argument("--list", action="store_true",
                    help="print the expanded cell names and exit")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny 2x2 slice (+ Poisson cell) for the CI "
                         "matrix-smoke job")
    ap.add_argument("--no-proxy", action="store_true",
                    help="skip the accuracy proxy (frontier degenerates to "
                         "throughput vs energy)")
    ap.add_argument("--proxy-steps", type=int, default=None,
                    help="CNN training steps behind the accuracy proxy "
                         "(default 30 smoke / 120 full)")
    args = ap.parse_args()
    enable_compile_cache()

    if args.matrix:
        with open(args.matrix) as f:
            matrix = MatrixSpec.from_dict(json.load(f))
    else:
        matrix = default_matrix(smoke=args.smoke)
    if args.list:
        for c in matrix.expand():
            print(c.name)
        return
    only = [n for n in (args.only or "").split(",") if n] or None
    proxy_steps = args.proxy_steps or (30 if args.smoke else 120)
    section = run_matrix(matrix, only=only, proxy_steps=proxy_steps,
                         proxy_batches=2 if args.smoke else 8,
                         with_proxy=not args.no_proxy)

    report = {}
    if os.path.exists(args.out):
        with open(args.out) as f:
            report = json.load(f)
    report["matrix"] = section
    with open(args.out, "w") as f:
        json.dump(report, f, indent=2)
    if args.markdown:
        md = ["# Serving trade-off frontier",
              "",
              f"matrix `{matrix.name}`: {len(section['cells'])} cells; "
              f"axes: " + ", ".join(
                  f"{a['metric']} ({a['goal']})"
                  for a in section["frontier"]["axes"]),
              "",
              frontier_markdown(section["cells"], section["frontier"]), ""]
        with open(args.markdown, "w") as f:
            f.write("\n".join(md))
    print(json.dumps({"frontier": section["frontier"],
                      "identity": section["identity"]}, indent=2))


if __name__ == "__main__":
    main()
