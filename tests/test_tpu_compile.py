"""Ahead-of-time compiles of every Pallas kernel for a TPU v5e chip.

The TPU compiler ships with jax and compiles for a chip that is described
(``v5e:2x2``), not attached, so these run on a CPU-only host.  They catch
what interpret mode cannot: block shapes off the (8, 128) tiling, slices the
DMA engine refuses, casts and ops the kernel compiler does not lower.
Shapes are the ones the serving engine passes at published widths: gemma3-1b
(MQA, kv 1, head_dim 256, fp32 pools, 4 query heads) and a GQA shape (kv 8,
head_dim 128, bf16 pools).

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every test worker imports this file.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.device import DEFAULT_DEVICE
from repro.kernels import ops

# (B, KV, G, hd, pool dtype, pool blocks, table width) at block_size 16
ATTN_SHAPES = {
    "gemma3-1b-mqa": (4, 1, 4, 256, jnp.float32, 257, 64),
    "gqa-kv8-bf16": (8, 8, 4, 128, jnp.bfloat16, 513, 64),
}
BLOCK_SIZE = 16
PREFILL_CHUNK = 16


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of these compiles
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _attn_args(one_chip, name):
    B, KV, G, hd, dt, nb, T = ATTN_SHAPES[name]

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool = s((nb, BLOCK_SIZE, KV, hd), dt)
    return dict(q=s((B, KV, G, hd), jnp.float32), pool=pool,
                table=s((B, T), jnp.int32),
                mask=s((B, T * BLOCK_SIZE), jnp.float32),
                new=s((B, KV, hd), dt), wpos=s((B,), jnp.int32),
                active=s((B,), jnp.bool_),
                qc=s((B, PREFILL_CHUNK, KV * G, hd), jnp.float32),
                qpos=s((B, PREFILL_CHUNK), jnp.int32))


@pytest.mark.parametrize("name", sorted(ATTN_SHAPES))
def test_paged_decode_compiles(one_chip, name):
    a = _attn_args(one_chip, name)
    _compile(lambda *x: ops.paged_attention_decode(*x, impl="pallas"),
             a["q"], a["pool"], a["pool"], a["table"], a["mask"], a["new"],
             a["new"], a["wpos"], a["active"])


@pytest.mark.parametrize("name", sorted(ATTN_SHAPES))
def test_paged_attention_read_only_compiles(one_chip, name):
    a = _attn_args(one_chip, name)
    _compile(lambda *x: ops.paged_attention(*x, impl="pallas"),
             a["q"], a["pool"], a["pool"], a["table"], a["mask"])


@pytest.mark.parametrize("name", sorted(ATTN_SHAPES))
def test_paged_prefill_compiles(one_chip, name):
    a = _attn_args(one_chip, name)
    _compile(lambda *x: ops.paged_prefill(*x, impl="pallas"),
             a["qc"], a["pool"], a["pool"], a["table"], a["qpos"])


@pytest.mark.parametrize("kernel", ["emt_matmul", "emt_bitserial_matmul"])
def test_emt_matmul_compiles(one_chip, kernel):
    # one gemma3-1b MLP projection (d_model 1152 -> d_ff 6912), 16 rows
    x = jax.ShapeDtypeStruct((16, 1152), jnp.float32, sharding=one_chip)
    w = jax.ShapeDtypeStruct((1152, 6912), jnp.float32, sharding=one_chip)
    rho = jax.ShapeDtypeStruct((), jnp.float32, sharding=one_chip)
    op = getattr(ops, kernel)
    _compile(lambda x, w, r: op(x, w, r, device=DEFAULT_DEVICE), x, w, rho)


# a one-layer analog deepseek-67b stack at the kernel's head width (128): the
# serving engine's own jitted paged decode step, lowered with shapes only
DECODE_SPEC = dict(arch="deepseek-67b", mode="analog", smoke=True,
                   a_per_row=True, frozen_noise=True, batch_size=8,
                   max_len=64, paged=True, block_size=BLOCK_SIZE,
                   prefill_chunk=PREFILL_CHUNK,
                   model_overrides=dict(num_layers=1, vocab_size=512,
                                        d_model=256, num_heads=4,
                                        num_kv_heads=2, head_dim=128,
                                        d_ff=512))


def _lower_decode_step(impl, sharding=None):
    """Lower the engine's paged decode step (``ServingEngine._decode``) at
    the engine's own argument shapes, placed by ``sharding`` if given."""
    import numpy as np

    from repro.serve.spec import ServeSpec

    eng = ServeSpec(**DECODE_SPEC, paged_attn_impl=impl).build_engine()
    tables, kw = eng._paged_tables([eng.max_len])
    B = eng.batch_size
    host = (np.zeros(B, np.int32), np.zeros(B, np.int32),
            np.ones(B, bool), np.uint32(0), np.zeros(B, np.uint32),
            np.zeros(B, np.int32), np.zeros(B, np.float32),
            np.zeros(B, np.int32), np.ones(B, np.float32),
            np.zeros(B, np.int32))
    args = (eng.step_params, eng.cache, *host, *tables)
    if sharding is not None:
        args = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            np.shape(a), a.dtype, sharding=sharding), args)
    return eng._decode.lower(*args, sampled=False, **kw)


def test_decode_step_module_name():
    # bench/readers.py finds the decode step's executions by this name
    text = _lower_decode_step("ref").as_text()
    assert "module @jit_paged_decode_step" in text


def test_decode_step_compiles_with_named_kernel(one_chip):
    # the decode kernel keeps the op name the roofline reader matches
    # (paged_attention_decode or paged_attention_decode.<n>), whatever
    # wraps it
    hlo = _lower_decode_step("pallas", one_chip).compile().as_text()
    assert hlo.startswith("HloModule jit_paged_decode_step")
    assert re.search(r"%paged_attention_decode(\.\d+)? = .*tpu_custom_call",
                     hlo)
