"""Benchmark harness — one function per paper table/figure + system benches.

    PYTHONPATH=src python -m benchmarks.run [--only fig9,kernels] [--fast]

Prints ``name,us_per_call,derived`` CSV rows (derived = the table/figure quantity
the row reproduces). Heavy benches honor --fast for CI-scale runs.
"""
from __future__ import annotations

import argparse
import sys
import time


def _row(name, us, derived):
    print(f"{name},{us:.1f},{derived}")
    sys.stdout.flush()


# ---------------------------------------------------------------- Fig. 9
def bench_fig9_ablation(fast=False):
    """Accuracy vs energy for traditional/A/A+B/A+B+C (paper Fig. 9)."""
    from benchmarks.ablation_lib import run_method
    from repro.configs.paper_cnn import vgg_small
    cfg = vgg_small()
    steps = 80 if fast else 220
    rows = []
    for method, kw in [
        ("traditional", dict(rho=4.0, eval_rho=4.0)),
        ("A", dict(rho=4.0)),
        ("A+B", dict(rho=4.0, lam=3e-8)),
        ("A+B+C", dict(rho=4.0, lam=3e-8)),
    ]:
        t0 = time.time()
        r = run_method(cfg, method, steps=steps, **kw)
        us = (time.time() - t0) * 1e6
        _row(f"fig9/{method}", us,
             f"acc={r['acc']:.3f};energy_uJ={r['energy_uj']:.4f};"
             f"rho={r['rho']:.2f}")
        rows.append(r)
    order = {r["method"]: r for r in rows}
    _row("fig9/acc_ordering", 0,
         f"traditional<=A holds={order['traditional']['acc'] <= order['A']['acc'] + 0.02}")
    _row("fig9/energy_A+B+C<A+B", 0,
         f"holds={order['A+B+C']['energy_uj'] < order['A+B']['energy_uj']}")
    return rows


# ---------------------------------------------------------------- Fig. 10
def bench_fig10_robustness(fast=False):
    """Weak/normal/strong fluctuation intensity (paper Fig. 10)."""
    from benchmarks.ablation_lib import run_method
    from repro.configs.paper_cnn import resnet_small
    cfg = resnet_small()
    steps = 70 if fast else 180
    for intensity in ("weak", "normal", "strong"):
        t0 = time.time()
        r = run_method(cfg, "A+B", rho=4.0, lam=3e-8, steps=steps,
                       intensity=intensity)
        us = (time.time() - t0) * 1e6
        _row(f"fig10/A+B/{intensity}", us,
             f"acc={r['acc']:.3f};energy_uJ={r['energy_uj']:.4f};"
             f"rho={r['rho']:.2f}")


# ---------------------------------------------------------------- Fig. 7
def bench_fig7_energy_reg(fast=False):
    """rho and sum|w| descend under the energy regularizer (paper Fig. 7)."""
    import jax
    import jax.numpy as jnp
    from repro.core import EMTConfig, emt_dense, dense_specs
    from repro.core.regularizer import rho_from_raw
    from repro.nn.param import init_params
    from repro.train.optimizer import Optimizer, OptimizerConfig

    cfg = EMTConfig(mode="analog", rho_init=8.0)
    specs = dense_specs(64, 64, cfg)
    params = init_params(specs, jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (128, 64))
    y_t = x @ init_params(specs, jax.random.PRNGKey(2))["w"]
    opt = Optimizer(OptimizerConfig(name="adamw"))
    ost = opt.init(params)
    lam = 2e-4

    @jax.jit
    def step(params, ost, s):
        def loss(p):
            y, aux = emt_dense(p, x, cfg, tag="t", seed=s)
            return jnp.mean((y - y_t) ** 2) + lam * aux["reg"]
        l, g = jax.value_and_grad(loss)(params)
        params, ost = opt.update(g, ost, params, 3e-3, s.astype(jnp.int32))
        return params, ost, l

    rho0 = float(rho_from_raw(params["rho_raw"]))
    w0 = float(jnp.sum(jnp.abs(params["w"])))
    t0 = time.time()
    steps = 100 if fast else 400
    for s in range(steps):
        params, ost, l = step(params, ost, jnp.uint32(s))
    us = (time.time() - t0) * 1e6 / steps
    rho1 = float(rho_from_raw(params["rho_raw"]))
    w1 = float(jnp.sum(jnp.abs(params["w"])))
    _row("fig7/energy_reg_descent", us,
         f"rho:{rho0:.2f}->{rho1:.2f};sum_w:{w0:.1f}->{w1:.1f};"
         f"both_decreased={rho1 < rho0 and w1 < w0}")


# ---------------------------------------------------------------- Tables 1-2
def bench_tables(fast=False):
    """Energy / #cells / delay structure of paper Tables 1 & 2.

    #cells and delay come from the analytic device model on the paper's full
    CNN configs; the energy/accuracy trade-off is measured on the small
    (CPU-trainable) variants of the same families.
    """
    from benchmarks.ablation_lib import run_method
    from repro.configs.paper_cnn import (vgg16_cifar, resnet18_cifar,
                                         vgg_small, resnet_small)
    from repro.models import cnn
    from repro.nn.param import abstract_params
    from repro.utils import tree_param_count

    for name, full_cfg, small_cfg in [
            ("vgg16", vgg16_cifar(), vgg_small()),
            ("resnet18", resnet18_cifar(), resnet_small())]:
        cells = tree_param_count(abstract_params(cnn.specs(full_cfg)))
        delay_a = 2.8                                   # single analog read pass
        delay_c = delay_a * (full_cfg.emt.quant.a_bits - 1) / 1.4  # bit-serial
        steps = 70 if fast else 180
        r_ab = run_method(small_cfg, "A+B", rho=4.0, lam=3e-8, steps=steps)
        r_abc = run_method(small_cfg, "A+B+C", rho=4.0, lam=3e-8, steps=steps)
        _row(f"table1/{name}/cells", 0, f"cells={cells/1e6:.2f}M")
        _row(f"table1/{name}/A+B", r_ab["train_s"] * 1e6,
             f"energy_uJ={r_ab['energy_uj']:.4f};delay_us={delay_a};"
             f"acc={r_ab['acc']:.3f}")
        _row(f"table1/{name}/A+B+C", r_abc["train_s"] * 1e6,
             f"energy_uJ={r_abc['energy_uj']:.4f};delay_us={delay_c:.1f};"
             f"acc={r_abc['acc']:.3f}")
        _row(f"table1/{name}/energy_ratio", 0,
             f"A+B_over_A+B+C="
             f"{r_ab['energy_uj']/max(r_abc['energy_uj'],1e-9):.1f}x")


# ---------------------------------------------------------------- kernels
def bench_kernels(fast=False):
    import jax
    import jax.numpy as jnp
    from repro.core.device import DeviceModel
    from repro.kernels import ops, ref

    dev = DeviceModel()
    m = k = n = 256 if fast else 512
    x = jax.random.normal(jax.random.PRNGKey(0), (m, k))
    w = jax.random.normal(jax.random.PRNGKey(1), (k, n))
    xq = jnp.round(jnp.clip(x * 20, -127, 127))

    for name, fn in [
        ("ref/emt_matmul", lambda: ref.emt_matmul_ref(x, w, 4.0, device=dev)),
        ("ref/bitserial", lambda: ref.emt_bitserial_ref(xq, w, 4.0, device=dev,
                                                        bits=7)),
        ("jnp/ideal_matmul", lambda: x @ w),
    ]:
        jfn = jax.jit(fn)
        jax.block_until_ready(jfn())  # compile
        t0 = time.time()
        reps = 5
        for _ in range(reps):
            jax.block_until_ready(jfn())
        us = (time.time() - t0) / reps * 1e6
        flops = 2 * m * k * n * (7 if "bitserial" in name else 1)
        _row(f"kernel/{name}", us, f"gflops_cpu={flops/us/1e3:.2f}")


# ---------------------------------------------------------------- roofline
def bench_roofline(fast=False):
    """Summarize the dry-run roofline table (reads experiments/dryrun/*.json)."""
    import glob
    import json
    import os
    pat = os.path.join(os.path.dirname(__file__), "..", "experiments",
                       "dryrun", "*.json")
    files = sorted(glob.glob(pat))
    if not files:
        _row("roofline/none", 0, "no dryrun results yet")
        return
    for f in files:
        with open(f) as fh:
            rec = json.load(fh)
        if rec.get("status") != "ok":
            _row(f"roofline/{os.path.basename(f)}", 0, "status=error")
            continue
        r = rec["roofline"]
        _row(f"roofline/{rec['arch']}/{rec['shape']}/{rec['mesh']}",
             rec["compile_s"] * 1e6,
             f"dom={r['dominant']};bound_ms={r['step_time_lower_bound_s']*1e3:.1f};"
             f"frac={r['roofline_fraction']:.3f};useful={r['useful_flops_ratio']:.3f};"
             f"peak_GB={rec['peak_bytes_per_chip']/2**30:.2f}")


BENCHES = {
    "fig7": bench_fig7_energy_reg,
    "fig9": bench_fig9_ablation,
    "fig10": bench_fig10_robustness,
    "tables": bench_tables,
    "kernels": bench_kernels,
    "roofline": bench_roofline,
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="")
    ap.add_argument("--fast", action="store_true")
    args = ap.parse_args()
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    names = [n for n in args.only.split(",") if n] or list(BENCHES)
    unknown = [n for n in names if n not in BENCHES]
    if unknown:
        raise SystemExit(f"unknown bench name(s) {unknown}; known: "
                         f"{', '.join(BENCHES)}")
    print("name,us_per_call,derived")
    for n in names:
        BENCHES[n](fast=args.fast)


if __name__ == "__main__":
    main()
