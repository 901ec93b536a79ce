"""EMT dense layer — the paper's techniques A/B/C as a drop-in matmul.

``emt_dense`` replaces every projection in the framework's models (attention QKV/O,
GLU MLPs, MoE experts, routers, de/embeddings, SSM in/out projections, im2col convs).

Modes
-----
* ``ideal``      — plain (optionally fake-quantized) matmul; the GPU/baseline.
* ``analog``     — one crossbar read per MAC with RTN fluctuation (technique A), and
                   a trainable per-layer energy coefficient rho (technique B).
* ``bitserial``  — technique C: bit-serial decomposed reads with independent
                   fluctuation per bit-plane (lower sigma *and* lower energy, at a
                   latency cost).

Every call returns ``(y, aux)`` where ``aux`` carries the differentiable
energy-regularization term (Eq. 13), the analytic energy estimate in pJ, cell and
read counts — aggregated up the model with :func:`add_aux`.

Every op of a call carries the layer's ``tag`` and its phase in its name stack
(``jax.named_scope``, HLO ``op_name`` metadata, so profiler traces group ops per
projection and phase): ``emt.weights`` (quantize or int8 dequantize),
``emt.inputs`` (DAC levels and their read counts), ``emt.noise`` (read
fluctuation), ``emt.crossbar`` (the matmul) and ``emt.energy`` (the accounting
from the weights).  Scopes change neither the program's ops nor their fusion.

The weight-only reductions -- the per-column max|w| behind the quantization
scale, and sum|wq| / max|wq| behind the energy estimate -- read every weight
in full.  A serving tree carries them precomputed (:func:`with_weight_stats`,
run once when ``ServingEngine`` is built), so in a served step ``emt.weights``
and ``emt.energy`` hold only elementwise and scalar work.  A layer dict without
them (training, or a quantization they were not computed for) reduces in-step.
"""
from __future__ import annotations

import dataclasses
import functools
import zlib
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.device import DeviceModel, DEFAULT_DEVICE
from repro.core.noise import NoiseConfig, fluctuate
from repro.core.quant import QuantConfig, quantize_weights, quant_levels
from repro.core import decompose, regularizer
from repro.nn.param import ParamSpec, fan_in_init, constant_init


@dataclasses.dataclass(frozen=True)
class EMTConfig:
    """How EMT simulation applies to the model's dense layers."""
    mode: str = "ideal"                      # ideal | analog | bitserial
    quant: QuantConfig = QuantConfig()
    noise: NoiseConfig = NoiseConfig()
    device: DeviceModel = DEFAULT_DEVICE
    rho_init: float = 4.0
    trainable_rho: bool = True
    use_pallas: bool = False                 # kernels only run/validate on TPU or interpret
    pallas_interpret: bool = False
    crossbar_tile: int = 128                 # physical array tile (alpha accounting)
    # "full": per-step sum|w| reductions (training needs them for the technique-B
    # loss anyway). "off": skip in-step accounting — serving uses precomputed
    # static per-layer sum|w| tables instead of re-reading all weights per token.
    energy_accounting: str = "full"
    # Beyond-paper serving optimization: store weights as int8 levels + per-column
    # scale (exactly the conductance levels an EMT crossbar stores) and dequantize
    # on-chip — halves weight HBM streaming for memory-bound decode. Serve-only.
    store_int8: bool = False
    # Technology-corner label (core/device.py registry) — stamps this layer's
    # energy/reads/cells into the per-corner aux breakdown. Empty: fall back
    # to the mode name.
    corner: str = ""

    @property
    def active(self) -> bool:
        return self.mode != "ideal"

    @property
    def corner_label(self) -> str:
        return self.corner or self.mode

    def replace(self, **kw) -> "EMTConfig":
        return dataclasses.replace(self, **kw)


IDEAL = EMTConfig(mode="ideal", quant=QuantConfig(enabled=False))


def _tag_plane(tag: str) -> int:
    """Stable per-layer noise plane derived from the layer's name."""
    return zlib.crc32(tag.encode()) & 0x7FFFFFF


def _int8_init(base_init):
    """Initialize int8 conductance levels by quantizing a float init."""
    def init(key, shape, dtype):
        wf = base_init(key, shape, jnp.float32)
        scale = jnp.max(jnp.abs(wf), axis=0, keepdims=True) / 127.0
        return jnp.clip(jnp.round(wf / jnp.maximum(scale, 1e-8)),
                        -127, 127).astype(jnp.int8)
    return init


def dense_specs(d_in: int, d_out: int, cfg: EMTConfig, *,
                axes=(None, None), dtype=jnp.float32, bias: bool = False,
                init=None) -> dict:
    """ParamSpec dict for one EMT dense layer (w [, b] [, rho_raw]).

    With cfg.store_int8 (serve-only), `w` is stored as int8 conductance levels
    plus a per-output-column fp32 scale — the exact representation an EMT
    crossbar holds — halving weight HBM streaming vs bf16.
    """
    base_init = init or fan_in_init(fan_axis=0)
    if cfg.active and cfg.store_int8:
        specs = {
            "w_int8": ParamSpec((d_in, d_out), jnp.int8, tuple(axes),
                                _int8_init(base_init)),
            "w_scale": ParamSpec((1, d_out), jnp.float32, (None, axes[1]),
                                 constant_init(1.0 / 127.0)),
        }
    else:
        specs = {
            "w": ParamSpec((d_in, d_out), dtype, tuple(axes), base_init),
        }
    if bias:
        specs["b"] = ParamSpec((d_out,), dtype, (axes[1],), constant_init(0.0))
    if cfg.active:
        specs["rho_raw"] = ParamSpec(
            (), jnp.float32, (), constant_init(regularizer.rho_init_raw(cfg.rho_init)))
    return specs


def quantize_tree_for_serving(params):
    """Convert a trained float checkpoint into int8 weight-streaming form:
    every dict holding 'w' (+'rho_raw') becomes {'w_int8','w_scale',...}."""
    if isinstance(params, dict):
        if "w" in params and "rho_raw" in params:
            w = params["w"].astype(jnp.float32)
            scale = jnp.max(jnp.abs(w), axis=0, keepdims=True) / 127.0
            q = jnp.clip(jnp.round(w / jnp.maximum(scale, 1e-8)),
                         -127, 127).astype(jnp.int8)
            out = {k: v for k, v in params.items() if k != "w"}
            out["w_int8"] = q
            out["w_scale"] = scale
            return out
        return {k: quantize_tree_for_serving(v) for k, v in params.items()}
    return params


def new_aux():
    # kv_reads: K/V cache elements actually read by decode attention (billed
    # only for mask-visible logical positions — zero-block gathers for
    # unallocated/padded block-table entries are free; models/attention.py).
    return {"energy_pj": jnp.float32(0.0), "reg": jnp.float32(0.0),
            "reads": jnp.float32(0.0), "kv_reads": jnp.float32(0.0),
            "cells": 0, "rho_sum": jnp.float32(0.0),
            "rho_layers": 0, "aux_loss": jnp.float32(0.0), "corners": {}}


def corner_entry(energy_pj, reads, cells):
    return {"energy_pj": jnp.float32(energy_pj), "reads": jnp.float32(reads),
            "cells": cells}


def add_aux(a, b):
    out = {k: a[k] + b[k] for k in a if k != "corners"}
    # per-corner breakdown: union-merge (corner labels are static python
    # strings from the placement, so the pytree structure stays jit-stable)
    corners = {k: dict(v) for k, v in a.get("corners", {}).items()}
    for name, c in b.get("corners", {}).items():
        if name in corners:
            corners[name] = {k: corners[name][k] + c[k] for k in c}
        else:
            corners[name] = dict(c)
    out["corners"] = corners
    return out


def _tokens(x) -> int:
    return int(np.prod(x.shape[:-1]))


def emt_dense(params: dict, x, cfg: EMTConfig, *, tag: str,
              seed=0, key: Optional[jax.Array] = None):
    """Apply the layer. Returns (y, aux).

    tag:  unique layer name — seeds the per-layer noise plane (hash backend) or the
          fold_in constant (threefry backend), and scopes the layer's ops.
    seed: uint32 scalar (traced is fine) — typically derived from the training step,
          so technique A sees fresh fluctuation data every batch.
    """
    with jax.named_scope(tag):
        return _emt_dense(params, x, cfg, tag=tag, seed=seed, key=key)


def _emt_dense(params, x, cfg, *, tag, seed, key):
    int8_weights = "w_int8" in params
    prepared = None if int8_weights else _prepared_stats(params, cfg.quant)
    w = params["w_int8"] if int8_weights else params["w"]
    aux = new_aux()
    d_in, d_out = w.shape
    plane = _tag_plane(tag)

    if not cfg.active:
        with jax.named_scope("emt.crossbar"):
            y = x @ w
        if "b" in params:
            y = y + params["b"]
        return y, aux

    rho = regularizer.rho_from_raw(params["rho_raw"])
    if not cfg.trainable_rho:
        rho = jax.lax.stop_gradient(rho)

    # --- weights onto the crossbar: quantize (stored conductances) ----------
    with jax.named_scope("emt.weights"):
        if int8_weights:
            # already stored as conductance levels; dequantize on-chip (fuses into
            # the matmul input on TPU — weight HBM traffic stays int8-sized)
            wq = (w.astype(x.dtype) * params["w_scale"].astype(x.dtype))
        else:
            wq, _ = quantize_weights(
                w, cfg.quant, amax=None if prepared is None else prepared[0])
    # --- activations onto the input lines: quantized DAC levels -------------
    # per_row: each batch row (token) gets its own DAC scale, so quantization
    # never couples co-tenant rows (occupancy-independent serving); per-tensor
    # is the paper's default and marginally cheaper.
    with jax.named_scope("emt.inputs"):
        a_axis = -1 if cfg.quant.a_per_row else None
        levels, a_scale = quant_levels(x, cfg.quant.a_bits, axis=a_axis)

    n_tokens = _tokens(x)
    if cfg.mode == "analog":
        with jax.named_scope("emt.noise"):
            if key is not None:
                key = jax.random.fold_in(key, plane)
            wn = fluctuate(wq, rho, cfg.device, cfg.noise, key=key,
                           seed=seed, plane=plane)
        with jax.named_scope("emt.crossbar"):
            y = (levels * a_scale) @ wn
        # mean analog input level in LEVEL units (x = sum_p delta_p 2^p, Eq. 14) so
        # it is directly comparable with the bit-serial popcount of Eq. 19.
        with jax.named_scope("emt.inputs"):
            x_level = jax.lax.stop_gradient(jnp.mean(jnp.abs(levels)))
        reads_per_cell = float(n_tokens)
    elif cfg.mode == "bitserial":
        if cfg.use_pallas:
            from repro.kernels import ops as kops  # lazy: kernels depend on core
            # the kernel draws each bit-plane's noise inside its matmul
            with jax.named_scope("emt.crossbar"):
                y_raw = kops.emt_bitserial_matmul(
                    levels.reshape(-1, d_in), wq, rho, device=cfg.device,
                    bits=cfg.quant.a_bits - 1, seed=seed, base_plane=plane,
                    interpret=cfg.pallas_interpret)
                y_raw = y_raw.reshape(*x.shape[:-1], d_out)
        else:
            # scopes each bit-plane's emt.noise and emt.crossbar itself
            y_raw = decompose.bitserial_matmul_ref(
                levels, wq, rho, cfg.device, cfg.quant.a_bits - 1,
                seed=seed, base_plane=plane)
        with jax.named_scope("emt.crossbar"):
            y = y_raw * a_scale
        # energy counts actual bit reads (Eq. 19): popcount of levels
        with jax.named_scope("emt.inputs"):
            pops = decompose.popcount_levels(jnp.abs(levels), cfg.quant.a_bits - 1)
            x_level = jax.lax.stop_gradient(jnp.mean(pops))
        reads_per_cell = float(n_tokens)  # per bit handled via x_level popcount
    else:
        raise ValueError(f"unknown EMT mode {cfg.mode!r}")

    if "b" in params:
        y = y + params["b"]

    if cfg.energy_accounting == "off":
        aux["cells"] = int(d_in * d_out)
        aux["corners"] = {cfg.corner_label: corner_entry(0.0, 0.0, aux["cells"])}
        return y, aux

    # --- accounting ----------------------------------------------------------
    with jax.named_scope("emt.energy"):
        norms = _abs_norms(wq) if prepared is None else prepared[1]
        w_norm = jax.lax.stop_gradient(
            norms["abs_sum"] / jnp.maximum(norms["abs_max"], 1e-8))
        rho_sg = jax.lax.stop_gradient(rho)
        # tile count of this layer on the crossbar fabric (fractional for layers
        # smaller than one tile — they still only bias a fraction of a macro)
        n_tiles = (d_in / cfg.crossbar_tile) * max(1.0, d_out / cfg.crossbar_tile)
        aux["energy_pj"] = (
            cfg.device.mac_energy(rho_sg, w_norm, x_level, reads_per_cell)
            + cfg.device.peripheral_energy(n_tokens * n_tiles)
            # static macro-activation cost: paid once per tile per step window,
            # NOT per streamed lane — this is what multi-lane verify amortizes.
            + cfg.device.static_energy(n_tiles))
        aux["energy_pj"] = jnp.float32(aux["energy_pj"])
        # Technique B loss term (Eq. 13): alpha * rho * sum|w|, alpha = reads per token
        # (normalized per-token so lambda has a model-size-independent meaning).
        aux["reg"] = regularizer.layer_reg_term(
            wq, rho, alpha=1.0,
            abs_sum=None if prepared is None else norms["abs_sum"]) / d_out
    aux["reads"] = jnp.float32(n_tokens * d_in)
    aux["cells"] = int(d_in * d_out)
    aux["rho_sum"] = rho_sg
    aux["rho_layers"] = 1
    aux["corners"] = {cfg.corner_label: corner_entry(
        aux["energy_pj"], aux["reads"], aux["cells"])}
    return y, aux


# ---------------------------------------------------------------------------
# weight-only statistics of a serving tree
# ---------------------------------------------------------------------------
# Weights do not change while serving, so everything a call derives from the
# weights alone -- the per-column max|w| behind the quantization scale, and
# sum|wq| / max|wq| behind the energy estimate and technique B's term -- can
# be computed once and stored beside the weights under STATS:
#
#     {"amax": max|w| per column, (1, d_out),
#      stats_key(quant): {"abs_sum": sum|wq| (fp32), "abs_max": max|wq|}, ...}
#
# A call reads them when its quantization's key is there, and reduces its
# weight in-step otherwise (training trees never carry them).
STATS = "emt_stats"


def stats_key(quant: QuantConfig) -> str:
    """Name of the statistics a weight quantization reads: sum|wq| and
    max|wq| depend on the bit width and the scale's granularity alone."""
    if not quant.enabled:
        return "float"
    return f"w{quant.w_bits}_{'channel' if quant.per_channel else 'tensor'}"


def _abs_norms(wq):
    return {"abs_sum": jnp.sum(jnp.abs(wq.astype(jnp.float32))),
            "abs_max": jnp.max(jnp.abs(wq))}


def _prepared_stats(params, quant: QuantConfig):
    """(amax, norms) stored for ``quant`` in a layer dict, else None."""
    stats = params.get(STATS)
    if stats is None or stats_key(quant) not in stats:
        return None
    return stats["amax"], stats[stats_key(quant)]


def _weight_stats(w, quants) -> dict:
    """The statistics of one weight (d_in, d_out) for each QuantConfig of
    ``quants``.  Axes in front of the matrix stack layers and are kept."""
    if w.ndim > 2:
        return jax.vmap(lambda v: _weight_stats(v, quants))(w)
    amax = jnp.max(jnp.abs(w), axis=0, keepdims=True)
    out = {"amax": amax}
    for q in quants:
        wq, _ = quantize_weights(w, q, amax=amax)
        out[stats_key(q)] = _abs_norms(wq)
    return out


def _stat_quants(placement) -> tuple:
    """The distinct weight quantizations (one per :func:`stats_key`) of the
    active layer configs of a DevicePlacement."""
    quants = {}
    for c in [r.emt for r in placement.rules] + [placement.default]:
        if c.active:
            quants.setdefault(stats_key(c.quant), c.quant)
    return tuple(quants.values())


def _at(tree, path):
    for k in path:
        if not isinstance(tree, dict) or k not in tree:
            return None
        tree = tree[k]
    return tree


def _dense_layers(tree, path=()):
    """(path, dict) of each EMT dense layer: a float weight ``w`` beside its
    ``rho_raw``, whose axes (if any) stack layers in front of the matrix."""
    if not isinstance(tree, dict):
        return
    if ("w" in tree and "rho_raw" in tree
            and np.ndim(tree["w"]) == np.ndim(tree["rho_raw"]) + 2):
        yield path, tree
        return
    for k, v in tree.items():
        yield from _dense_layers(v, path + (k,))


@functools.partial(jax.jit, static_argnames=("quants", "tied"))
def _tree_stats(params, quants, tied):
    out = {path: _weight_stats(layer["w"], quants)
           for path, layer in _dense_layers(params)}
    for path, source in tied:
        out[path] = _weight_stats(_at(params, source).T, quants)
    return out


def with_weight_stats(params, placement, tied=()):
    """``params`` with the weight statistics of every EMT dense layer beside
    its weights, for each quantization the active layers of ``placement``
    (a DevicePlacement) use.

    ``tied``: (layer path, source path) pairs of layers whose weight is the
    transpose of another leaf, supplied at call time (a tied LM head).  Only
    the statistics come out of the device program; the returned tree's new
    layer dicts point at the caller's weight buffers, so nothing is copied."""
    quants = _stat_quants(placement)
    if not quants:
        return params
    tied = tuple((path, source) for path, source in tied
                 if "rho_raw" in (_at(params, path) or {}))
    stats = _tree_stats(params, quants, tied)

    def attach(node, path):
        if path in stats:
            return {**node, STATS: stats[path]}
        if isinstance(node, dict):
            return {k: attach(v, path + (k,)) for k, v in node.items()}
        return node
    return attach(params, ())


def stat_layers(params) -> tuple:
    """(prepared, inline) counts of a tree's EMT layers -- dicts carrying a
    ``rho_raw``, a stacked dict counting each layer, an MoE expert stack one
    -- whose calls read stored weight statistics, and that reduce their
    weights in-step."""
    prepared = inline = 0
    for node in _dicts(params):
        if "rho_raw" in node:
            n = int(np.prod(np.shape(node["rho_raw"])))
            if STATS in node:
                prepared += n
            else:
                inline += n
    return prepared, inline


def _dicts(tree):
    if isinstance(tree, dict):
        yield tree
        for k, v in tree.items():
            if k != STATS:
                yield from _dicts(v)
