"""EMT dense layer: modes, accounting, technique-B gradients, energy ordering."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import EMTConfig, emt_dense, dense_specs, QuantConfig
from repro.core.emt_linear import (STATS, add_aux, new_aux, stat_layers,
                                   stats_key, with_weight_stats)
from repro.core.placement import as_placement
from repro.core.regularizer import rho_from_raw, rho_init_raw
from repro.nn.param import init_params

X = jax.random.normal(jax.random.PRNGKey(1), (4, 16, 64))


def _layer(mode, **kw):
    cfg = EMTConfig(mode=mode, **kw)
    specs = dense_specs(64, 32, cfg, bias=True)
    return cfg, init_params(specs, jax.random.PRNGKey(0))


@pytest.mark.parametrize("mode", ["ideal", "analog", "bitserial"])
def test_modes_finite_and_shaped(mode):
    cfg, params = _layer(mode)
    y, aux = emt_dense(params, X, cfg, tag="t", seed=3)
    assert y.shape == (4, 16, 32)
    assert bool(jnp.all(jnp.isfinite(y)))
    if mode != "ideal":
        assert aux["cells"] == 64 * 32
        assert float(aux["energy_pj"]) > 0


def test_analog_converges_to_ideal_at_high_rho():
    cfg, params = _layer("analog", rho_init=1e9)
    y, _ = emt_dense(params, X, cfg, tag="t", seed=3)
    y_ideal = X @ params["w"] + params["b"]
    rel = float(jnp.linalg.norm(y - y_ideal) / jnp.linalg.norm(y_ideal))
    assert rel < 0.03      # residual is 8-bit quantization only


def test_noise_decreases_with_rho():
    errs = []
    for rho in (0.5, 4.0, 64.0):
        cfg, params = _layer("analog", rho_init=rho,
                             quant=QuantConfig(enabled=False))
        y, _ = emt_dense(params, X, cfg, tag="t", seed=3)
        errs.append(float(jnp.linalg.norm(y - (X @ params["w"] + params["b"]))))
    assert errs[0] > errs[1] > errs[2]


def test_bitserial_energy_below_analog():
    cfg_a, params = _layer("analog")
    cfg_b = EMTConfig(mode="bitserial")
    _, aux_a = emt_dense(params, X, cfg_a, tag="t", seed=3)
    _, aux_b = emt_dense(params, X, cfg_b, tag="t", seed=3)
    assert float(aux_b["energy_pj"]) < float(aux_a["energy_pj"])   # Eq. 20


def test_reg_term_gradients_reduce_rho_and_weights():
    """Fig. 7: descending lam*reg shrinks both rho and sum|w|."""
    cfg, params = _layer("analog")

    def reg_loss(p):
        _, aux = emt_dense(p, X, cfg, tag="t", seed=3)
        return aux["reg"]

    g = jax.grad(reg_loss)(params)
    assert float(g["rho_raw"]) > 0                   # pushes rho down
    # weight gradient has the sign of w (|w| subgradient)
    mask = jnp.abs(params["w"]) > 1e-3
    agree = jnp.mean((jnp.sign(g["w"]) == jnp.sign(params["w"]))[mask])
    assert float(agree) > 0.99


def test_energy_accounting_off():
    cfg = EMTConfig(mode="analog", energy_accounting="off")
    specs = dense_specs(64, 32, cfg)
    params = init_params(specs, jax.random.PRNGKey(0))
    y, aux = emt_dense(params, X, cfg, tag="t", seed=1)
    assert float(aux["energy_pj"]) == 0.0
    assert aux["cells"] == 64 * 32


def test_rho_raw_roundtrip():
    for rho in (0.01, 1.0, 4.0, 100.0):
        assert abs(float(rho_from_raw(jnp.float32(rho_init_raw(rho)))) - rho) \
            < 1e-3 * rho + 1e-5


def test_aux_merge():
    a, b = new_aux(), new_aux()
    a["cells"], b["cells"] = 3, 4
    assert add_aux(a, b)["cells"] == 7


@pytest.mark.parametrize("mode", ["analog", "bitserial"])
def test_phase_scopes_in_op_metadata(mode):
    """Every phase of a call is a named scope under the layer's tag in the
    ops' HLO op_name metadata, which is what profiler traces group by."""
    cfg, params = _layer(mode)
    lowered = jax.jit(lambda p, x: emt_dense(
        p, x, cfg, tag="layer_000/ffn/wg", seed=3)).lower(params, X)
    op_names = set(re.findall(r'loc\("([^"]*)"',
                              lowered.as_text(debug_info=True)))
    for phase in ("weights", "inputs", "noise", "crossbar", "energy"):
        assert any(f"layer_000/ffn/wg/emt.{phase}/" in n for n in op_names), \
            (phase, sorted(op_names))


# ---------------------------------------------------------------------------
# weight-only statistics computed once (emt_linear.with_weight_stats)
# ---------------------------------------------------------------------------
def _prepared(layer, cfg):
    return with_weight_stats({"l": layer}, as_placement(cfg))["l"]


def _close(a, b, rtol=1e-6):
    a, b = float(a), float(b)
    return abs(a - b) <= rtol * max(abs(a), abs(b))


@pytest.mark.parametrize("mode", ["analog", "bitserial"])
@pytest.mark.parametrize("per_channel", [True, False])
@pytest.mark.parametrize("w_bits", [8, 4])
def test_prepared_stats_match_in_step(mode, per_channel, w_bits):
    cfg, params = _layer(mode, quant=QuantConfig(w_bits=w_bits,
                                                 per_channel=per_channel))
    prepared = _prepared(params, cfg)
    assert prepared["w"] is params["w"]              # weights are not copied
    assert stats_key(cfg.quant) in prepared[STATS]
    f = jax.jit(lambda p, x: emt_dense(p, x, cfg, tag="t", seed=3))
    y0, a0 = f(params, X)
    y1, a1 = f(prepared, X)
    np.testing.assert_array_equal(np.asarray(y0), np.asarray(y1))
    assert _close(a0["energy_pj"], a1["energy_pj"])
    assert _close(a0["reg"], a1["reg"])


def _reductions(params, cfg):
    fn = jax.jit(lambda p: emt_dense(p, X, cfg, tag="t", seed=3))
    return fn.lower(params).as_text().count("stablehlo.reduce")


def test_prepared_stats_key_mismatch_falls_back_in_step():
    cfg, params = _layer("analog")
    # statistics stored for 4-bit weights; the call quantizes to 8 bits
    other = cfg.replace(quant=QuantConfig(w_bits=4))
    prepared = _prepared(params, other)
    assert stats_key(cfg.quant) not in prepared[STATS]
    f = jax.jit(lambda p, x: emt_dense(p, x, cfg, tag="t", seed=3))
    y0, a0 = f(params, X)
    y1, a1 = f(prepared, X)
    np.testing.assert_array_equal(np.asarray(y0), np.asarray(y1))
    assert float(a0["energy_pj"]) == float(a1["energy_pj"])
    assert float(a0["reg"]) == float(a1["reg"])
    # the fallback reduces the weight in-step, as a matching entry does not
    matched = _prepared(params, cfg)
    assert _reductions(prepared, cfg) == _reductions(params, cfg) \
        > _reductions(matched, cfg)


def test_prepared_stats_of_a_stacked_tree_are_per_layer():
    cfg = EMTConfig(mode="analog")
    layers = [init_params(dense_specs(64, 32, cfg), jax.random.PRNGKey(i))
              for i in range(3)]
    stacked = jax.tree.map(lambda *v: jnp.stack(v), *layers)
    prepared = with_weight_stats({"blocks": stacked},
                                 as_placement(cfg))["blocks"]
    key = stats_key(cfg.quant)
    assert prepared[STATS]["amax"].shape == (3, 1, 32)
    assert prepared[STATS][key]["abs_sum"].shape == (3,)
    assert stat_layers({"blocks": prepared}) == (3, 0)
    for i, layer in enumerate(layers):
        one = jax.tree.map(lambda v: v[i], prepared)
        solo = _prepared(layer, cfg)[STATS]
        np.testing.assert_array_equal(one[STATS]["amax"], solo["amax"])
        assert _close(one[STATS][key]["abs_sum"], solo[key]["abs_sum"])
        assert float(one[STATS][key]["abs_max"]) == \
            float(solo[key]["abs_max"])
        y0, a0 = emt_dense(layer, X, cfg, tag="t", seed=3)
        y1, a1 = emt_dense(one, X, cfg, tag="t", seed=3)
        np.testing.assert_array_equal(np.asarray(y0), np.asarray(y1))
        assert _close(a0["energy_pj"], a1["energy_pj"])


def test_training_and_ideal_trees_carry_no_stats():
    cfg, params = _layer("ideal")
    assert with_weight_stats({"l": params}, as_placement(cfg)) == {"l": params}
    cfg, params = _layer("analog")
    assert stat_layers({"l": params}) == (0, 1)
