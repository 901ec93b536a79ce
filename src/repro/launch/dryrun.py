"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

Proves the distribution config is coherent without hardware:
``.lower().compile()`` must succeed on the 16x16 single-pod mesh AND the 2x16x16
multi-pod mesh for every assigned cell; per-cell JSON records memory analysis,
HLO cost analysis, collective bytes, and roofline terms (EXPERIMENTS.md reads
these).

Usage:
    python -m repro.launch.dryrun --arch llama3-405b --shape train_4k
    python -m repro.launch.dryrun --arch llama3-405b --shape train_4k --multi-pod
    python -m repro.launch.dryrun --all [--jobs 4]      # driver: subprocess/cell
"""
# The forced device count MUST precede any other import that touches jax.
import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", ""))

import argparse
import json
import subprocess
import sys
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.analysis.hlo import analyze_collectives
from repro.analysis.roofline import Roofline, model_flops, active_params
from repro.configs import ARCHS, arch_shapes, get_config
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import input_specs
from repro.models import lm
from repro.models.config import SHAPES
from repro.nn.param import abstract_params, param_shardings
from repro.parallel.sharding import RULES, batch_shardings, cache_shardings
from repro.serve.engine import make_prefill_step, make_decode_step
from repro.train.optimizer import OptimizerConfig
from repro.train.step import TrainConfig, jit_train_step
from repro.utils import tree_param_count

OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "experiments", "dryrun")
TRAIN_RULES = os.environ.get("REPRO_TRAIN_RULES", "train_fsdp_tp")
SERVE_RULES = os.environ.get("REPRO_SERVE_RULES", "serve_2d")
EMT_RNG = os.environ.get("REPRO_EMT_RNG", "hash")
EMT_MODE = os.environ.get("REPRO_EMT_MODE", "analog")
# the chip the pod meshes stand for (roofline peaks; analysis/roofline.py)
TARGET_DEVICE_KIND = "TPU v5 lite"


def _measure(lowered, label: str) -> dict:
    t0 = time.time()
    compiled = lowered.compile()
    compile_s = time.time() - t0
    cost = compiled.cost_analysis()
    mem = compiled.memory_analysis()
    text = compiled.as_text()
    coll = analyze_collectives(text)
    return {
        "label": label,
        "compile_s": round(compile_s, 2),
        "flops_per_chip": float(cost.get("flops", 0.0)),
        "bytes_per_chip": float(cost.get("bytes accessed", 0.0)),
        "peak_bytes_per_chip": int(mem.peak_memory_in_bytes),
        "arg_bytes_per_chip": int(mem.argument_size_in_bytes),
        "temp_bytes_per_chip": int(mem.temp_size_in_bytes),
        "output_bytes_per_chip": int(mem.output_size_in_bytes),
        **coll,
    }


def run_cell(arch: str, shape_name: str, multi_pod: bool) -> dict:
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = int(np.prod(mesh.devices.shape))
    t_start = time.time()

    if shape.kind == "train":
        cfg = get_config(arch, emt_mode=EMT_MODE, rng=EMT_RNG)
        n_params_probe = tree_param_count(abstract_params(lm.specs(cfg)))
        opt_name = "adafactor" if n_params_probe > 30e9 else "adamw"
        tcfg = TrainConfig(opt=OptimizerConfig(name=opt_name))
        bspecs = input_specs(cfg, shape)
        with mesh:
            jitted, state_sh, astate, _ = jit_train_step(
                cfg, tcfg, mesh, bspecs, rules_name=TRAIN_RULES)
            lowered = jitted.lower(astate, bspecs)
            res = _measure(lowered, "train_step")
        n_params = n_params_probe
        extra = {"optimizer": opt_name, "rules": TRAIN_RULES}
    else:
        cfg = get_config(arch, emt_mode=EMT_MODE, rng=EMT_RNG,
                         energy_accounting="off",
                         store_int8=os.environ.get("REPRO_SERVE_INT8") == "1")
        rules = RULES[SERVE_RULES]
        pspecs = lm.specs(cfg)
        aparams = abstract_params(pspecs)
        n_params = tree_param_count(aparams)
        psh = param_shardings(pspecs, mesh, rules)
        ins = input_specs(cfg, shape)
        with mesh:
            if shape.kind == "prefill":
                csp = lm.init_cache_specs(cfg, shape.global_batch, shape.seq_len)
                csh = cache_shardings(csp, mesh, rules)
                bsh = batch_shardings(ins, mesh, rules)
                step = make_prefill_step(cfg, mesh, rules)
                jitted = jax.jit(step, in_shardings=(psh, bsh, csh, None),
                                 out_shardings=(csh, None, None))
                lowered = jitted.lower(
                    aparams, ins, csp, jax.ShapeDtypeStruct((), jnp.uint32))
                res = _measure(lowered, "prefill_step")
            else:
                csp = ins["cache"]
                csh = cache_shardings(csp, mesh, rules)
                tsh = NamedSharding(mesh, P(
                    ("pod", "data") if multi_pod else "data")) \
                    if shape.global_batch % (chips // 16) == 0 and \
                    shape.global_batch > 1 else NamedSharding(mesh, P(None))
                step = make_decode_step(cfg, mesh, rules)
                jitted = jax.jit(step,
                                 in_shardings=(psh, csh, tsh, None, None),
                                 out_shardings=(None, csh, None),
                                 donate_argnums=(1,))
                lowered = jitted.lower(
                    aparams, csp, ins["tokens"],
                    jax.ShapeDtypeStruct((), jnp.int32),
                    jax.ShapeDtypeStruct((), jnp.uint32))
                res = _measure(lowered, "serve_step")
        extra = {"rules": SERVE_RULES}

    n_active = active_params(cfg, n_params)
    mf = model_flops(cfg, shape, n_params, n_active)
    roof = Roofline(
        flops_per_chip=res["flops_per_chip"],
        bytes_per_chip=res["bytes_per_chip"],
        coll_bytes_per_chip=res["collective_bytes_per_chip"],
        chips=chips,
        model_flops_global=mf,
        device_kind=TARGET_DEVICE_KIND,
    ).terms()

    return {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "chips": chips,
        "status": "ok",
        "n_params": int(n_params), "n_active": int(n_active),
        "emt_mode": EMT_MODE, "emt_rng": EMT_RNG,
        "wall_s": round(time.time() - t_start, 1),
        **extra, **res, "roofline": roof,
    }


def cell_filename(arch, shape, multi_pod):
    mesh = "2x16x16" if multi_pod else "16x16"
    return f"{arch}__{shape}__{mesh}.json"


def _cell_cost(arch, shape_name):
    """Rough compile-cost estimate — the driver runs cheap cells first so a
    time-bounded run completes the maximum number of cells."""
    cfg = get_config(arch, emt_mode="ideal")
    kind_w = {"train": 4.0, "prefill": 2.5, "decode": 1.0}[SHAPES[shape_name].kind]
    return kind_w * cfg.num_layers * (cfg.d_model ** 0.5)


def all_cells(include_multipod=True):
    cells = []
    for arch in ARCHS:
        for shape in arch_shapes(arch):
            cells.append((arch, shape, False))
            if include_multipod:
                cells.append((arch, shape, True))
    cells.sort(key=lambda c: _cell_cost(c[0], c[1]))
    return cells


def run_driver(jobs: int, force: bool, timeout: int, only_missing=True):
    os.makedirs(OUT_DIR, exist_ok=True)
    cells = all_cells()
    pending = []
    for arch, shape, mp in cells:
        path = os.path.join(OUT_DIR, cell_filename(arch, shape, mp))
        if not force and only_missing and os.path.exists(path):
            with open(path) as f:
                if json.load(f).get("status") == "ok":
                    continue
        pending.append((arch, shape, mp))
    print(f"[driver] {len(pending)}/{len(cells)} cells to run, jobs={jobs}")

    procs = {}
    idx = 0
    failures = []
    while idx < len(pending) or procs:
        while idx < len(pending) and len(procs) < jobs:
            arch, shape, mp = pending[idx]
            path = os.path.join(OUT_DIR, cell_filename(arch, shape, mp))
            if os.path.exists(path):            # done meanwhile (re-entrancy)
                try:
                    with open(path) as f:
                        if json.load(f).get("status") == "ok":
                            idx += 1
                            continue
                except Exception:
                    pass
            cmd = [sys.executable, "-m", "repro.launch.dryrun",
                   "--arch", arch, "--shape", shape]
            if mp:
                cmd.append("--multi-pod")
            p = subprocess.Popen(cmd, stdout=subprocess.DEVNULL,
                                 stderr=subprocess.PIPE, text=True,
                                 env=dict(os.environ))
            procs[p] = (arch, shape, mp, time.time())
            idx += 1
        time.sleep(3)
        for p in list(procs):
            arch, shape, mp, t0 = procs[p]
            if p.poll() is not None:
                del procs[p]
                tag = f"{arch}/{shape}/{'mp' if mp else 'sp'}"
                if p.returncode == 0:
                    print(f"[driver] OK   {tag}  ({time.time()-t0:.0f}s)")
                else:
                    err = p.stderr.read()[-2000:]
                    failures.append((tag, err))
                    print(f"[driver] FAIL {tag}\n{err}")
            elif time.time() - t0 > timeout:
                p.kill()
                failures.append((f"{arch}/{shape}", "timeout"))
                print(f"[driver] TIMEOUT {arch}/{shape}")
                del procs[p]
    print(f"[driver] done, {len(failures)} failures")
    return failures


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list(ARCHS))
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--jobs", type=int, default=3)
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--timeout", type=int, default=3000)
    args = ap.parse_args()

    if args.all:
        failures = run_driver(args.jobs, args.force, args.timeout)
        sys.exit(1 if failures else 0)

    os.makedirs(OUT_DIR, exist_ok=True)
    path = os.path.join(OUT_DIR,
                        cell_filename(args.arch, args.shape, args.multi_pod))
    try:
        rec = run_cell(args.arch, args.shape, args.multi_pod)
    except Exception:
        rec = {"arch": args.arch, "shape": args.shape,
               "mesh": "2x16x16" if args.multi_pod else "16x16",
               "status": "error", "error": traceback.format_exc()[-4000:]}
        with open(path, "w") as f:
            json.dump(rec, f, indent=1)
        print(json.dumps({k: rec[k] for k in ("arch", "shape", "status")}))
        raise
    with open(path, "w") as f:
        json.dump(rec, f, indent=1)
    roof = rec["roofline"]
    print(json.dumps({
        "cell": f"{rec['arch']}/{rec['shape']}/{rec['mesh']}",
        "compile_s": rec["compile_s"],
        "peak_gb": round(rec["peak_bytes_per_chip"] / 2**30, 2),
        "dominant": roof["dominant"],
        "terms_ms": {k: round(v * 1e3, 3) for k, v in roof.items()
                     if k.endswith("_s") and not k.startswith("step")},
        "useful": round(roof["useful_flops_ratio"], 3),
        "while": rec["num_while"],
    }))


if __name__ == "__main__":
    main()
