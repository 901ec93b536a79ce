"""Compile a configuration's step programs for a described TPU v5e, no chip.

    JAX_PLATFORMS=cpu python3 bench/rehearse.py --config deepseek-67b-analog \
        [--view 4096] [--chunk 32]

Lowers the engine's chunk step and paged decode step at the cell's sizes
(batch, prefill chunk, paged pools, block table at ``--view`` positions)
with shapes only, compiles them for one chip of the ``v5e:2x2`` topology,
and prints the compile seconds and ``memory_analysis()`` of each.  Nothing
runs, so no time on the chip is measured here.
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--view", type=int, default=0,
                    help="block-table view in positions (default max_len)")
    ap.add_argument("--chunk", type=int, default=0,
                    help="prefill chunk (default the configuration's)")
    ap.add_argument("--which", default="chunk,decode")
    args = ap.parse_args()
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, str(ROOT / "src"))
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from repro.models import lm
    from repro.nn.param import abstract_params
    from repro.serve.engine import make_chunk_step, make_paged_decode_step
    from repro.serve.spec import ServeSpec

    jax.config.update("jax_enable_compilation_cache", False)
    conf = json.loads((ROOT / "bench" / "configs" /
                       f"{args.config}.json").read_text())
    spec = ServeSpec.from_dict(conf["serve"])
    if args.chunk:
        spec = spec.replace(prefill_chunk=args.chunk)
    cfg = spec.build_config()
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    dev = SingleDeviceSharding(topo.devices[0])

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=dev)

    B, C, bs, L = (spec.batch_size, spec.prefill_chunk, spec.block_size,
                   spec.max_len)
    view = args.view or L
    lens = lm.paged_lens(cfg, L)
    nblocks = B * (-(-L // bs))
    params = jax.tree.map(lambda s: sds(s.shape, s.dtype),
                          abstract_params(lm.specs(cfg)))
    cache = jax.tree.map(
        lambda s: sds(s.shape, s.dtype),
        lm.init_paged_cache_specs(cfg, B, L, bs, nblocks, 0))
    i32, u32, f32 = jnp.int32, jnp.uint32, jnp.float32
    vec = lambda dt: sds((B,), dt)  # noqa: E731
    tables = (sds((B, view // bs), i32), sds((B, 1), i32))
    out = {"config": args.config, "batch": B, "chunk": C, "view": view}
    for which in args.which.split(","):
        if which == "chunk":
            fn = jax.jit(make_chunk_step(cfg, None, None, lens),
                         static_argnames=("view_len", "sampled"))
            call = (params, cache, sds((B, C), i32), vec(i32), vec(i32),
                    vec(jnp.bool_), sds((), u32), vec(u32), vec(i32),
                    vec(f32), vec(i32), vec(f32), *tables)
        else:
            fn = jax.jit(make_paged_decode_step(cfg, None, None, lens),
                         static_argnames=("view_len", "sampled"))
            call = (params, cache, vec(i32), vec(i32), vec(jnp.bool_),
                    sds((), u32), vec(u32), vec(i32), vec(f32), vec(i32),
                    vec(f32), vec(i32), *tables)
        t0 = time.monotonic()
        try:
            lowered = fn.lower(*call, view_len=view, sampled=False)
            compiled = lowered.compile()
        except Exception as e:  # noqa: BLE001 — report what the compiler said
            out[which] = {"error": str(e)[:2000]}
            print(json.dumps(out[which]), flush=True)
            continue
        m = compiled.memory_analysis()
        out[which] = {
            "compile_s": round(time.monotonic() - t0, 1),
            "argument_bytes": m.argument_size_in_bytes,
            "output_bytes": m.output_size_in_bytes,
            "alias_bytes": m.alias_size_in_bytes,
            "temp_bytes": m.temp_size_in_bytes,
            "code_bytes": m.generated_code_size_in_bytes,
            "tpu_custom_calls": compiled.as_text().count("tpu_custom_call"),
        }
        print(which, json.dumps(out[which]), flush=True)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
