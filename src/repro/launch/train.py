"""Training launcher.

    python -m repro.launch.train --arch gemma3-1b --smoke --steps 50
    python -m repro.launch.train --arch llama3-405b --data 16 --model 16 ...

On this CPU box only --smoke scales are runnable; full configs are exercised via
the dry-run (launch/dryrun.py). The loop is the fault-tolerant one (auto-resume,
async checkpoints, SIGTERM-safe, straggler watchdog).
"""
from __future__ import annotations

import argparse
import os

import jax
import jax.numpy as jnp

from repro.configs import ARCHS, get_config
from repro.data.synthetic import SyntheticLM
from repro.launch.cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.parallel.sharding import RULES
from repro.train.optimizer import OptimizerConfig
from repro.train.step import TrainConfig, make_train_step, init_state
from repro.train.loop import LoopConfig, train_loop


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list(ARCHS), default="gemma3-1b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--lam", type=float, default=1e-6)
    ap.add_argument("--emt-mode", default="analog",
                    choices=["ideal", "analog", "bitserial"])
    ap.add_argument("--device", default=None,
                    help="registered technology corner for all layers")
    ap.add_argument("--placement", default=None,
                    help="heterogeneous per-layer placement preset "
                         "(configs PLACEMENTS; overrides --emt-mode/--device)")
    ap.add_argument("--rng", default="hash", choices=["hash", "threefry"])
    ap.add_argument("--rules", default="train_fsdp_tp", choices=list(RULES))
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--opt", default="adamw",
                    choices=["adamw", "sgd", "adafactor"])
    args = ap.parse_args()
    enable_compile_cache()
    if args.placement and args.device:
        ap.error("--placement and --device are mutually exclusive "
                 "(a placement names its corners per layer)")

    if args.placement:
        cfg = get_config(args.arch, rng=args.rng, smoke=args.smoke,
                         placement=args.placement)
    else:
        cfg = get_config(args.arch, emt_mode=args.emt_mode, rng=args.rng,
                         smoke=args.smoke, device=args.device)
    if args.placement:
        from repro.launch.serve import print_plan
        print_plan(cfg)
    if args.smoke:
        cfg = cfg.replace(dtype=jnp.float32)
    mesh = make_host_mesh()
    rules = RULES[args.rules]
    tcfg = TrainConfig(lam=args.lam, lr=args.lr, total_steps=args.steps,
                       opt=OptimizerConfig(name=args.opt))
    step_fn, opt = make_train_step(cfg, tcfg, mesh, rules)
    jitted = jax.jit(step_fn, donate_argnums=(0,))

    state = init_state(cfg, opt, jax.random.PRNGKey(0))
    data = SyntheticLM(vocab_size=cfg.vocab_size, seq_len=args.seq,
                       batch_size=args.batch, d_model=cfg.d_model,
                       input_kind=cfg.input_kind, encdec=cfg.is_encdec)
    lcfg = LoopConfig(total_steps=args.steps, ckpt_every=args.ckpt_every,
                      ckpt_dir=args.ckpt_dir,
                      metrics_path=os.path.join(args.ckpt_dir, "metrics.jsonl"))
    state, history = train_loop(state, jitted, data.batch_at, lcfg)
    if history:
        print(f"final: {history[-1]}")


if __name__ == "__main__":
    main()
