"""The serving engine computes each EMT dense layer's weight-only statistics
once, when it is built: its step programs read them instead of reducing
every weight again, and serve the same tokens and the same energy as step
programs that reduce in-step."""
import re

import numpy as np
import pytest

from repro.core.emt_linear import stat_layers
from repro.serve.engine import GenRequest
from repro.serve.spec import ServeSpec


def _spec(arch):
    # deepseek: untied head; gemma3 (all-global): the head is the tied
    # embedding table, whose statistics are the table's transpose
    return ServeSpec(arch=arch, mode="analog", smoke=True, all_global=True,
                     a_per_row=True, frozen_noise=True, batch_size=2,
                     max_len=32, paged=True, block_size=4, prefill_chunk=4,
                     model_overrides=dict(num_layers=2, vocab_size=96))


def _requests(vocab):
    rng = np.random.default_rng(3)
    return [GenRequest(prompt=rng.integers(0, vocab, n).astype(np.int32),
                       max_new=m) for n, m in ((5, 6), (9, 4), (3, 7))]


def _weight_shapes(cfg, params):
    shapes = {tuple(np.shape(leaf)) for leaf in _weights(params)}
    if cfg.tie_embeddings:
        shapes.add(tuple(np.shape(params["embed"]["table"]))[::-1])
    return shapes


def _weights(tree):
    if isinstance(tree, dict):
        if "w" in tree and "rho_raw" in tree:
            yield tree["w"]
        for v in tree.values():
            yield from _weights(v)


def _decode_reductions(eng, params):
    """Operand shapes of every reduce in the engine's lowered paged decode
    step, run on ``params``."""
    tables, kw = eng._paged_tables([eng.max_len])
    B = eng.batch_size
    host = (np.zeros(B, np.int32), np.zeros(B, np.int32), np.ones(B, bool),
            np.uint32(0), np.zeros(B, np.uint32), np.zeros(B, np.int32),
            np.zeros(B, np.float32), np.zeros(B, np.int32),
            np.ones(B, np.float32), np.zeros(B, np.int32))
    text = eng._decode.lower(params, eng.cache, *host, *tables,
                             sampled=False, **kw).as_text()
    return {tuple(int(d) for d in m.split("x")[:-1])
            for m in re.findall(r"stablehlo\.reduce\(.*?: \(tensor<([^>]*)>",
                                text)}


@pytest.mark.parametrize("arch", ["deepseek-67b", "gemma3-1b"])
def test_engine_serves_on_weight_stats_computed_once(arch):
    spec = _spec(arch)
    cfg = spec.build_config()
    eng = spec.build_engine(cfg)
    # the same step programs on the caller's tree: every step reduces in-step
    ref = spec.build_engine(cfg, eng.params)
    ref.step_params = ref.params

    reqs = _requests(cfg.vocab_size)
    got = eng.serve(reqs, stagger=2)
    want = ref.serve(reqs, stagger=2)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g.tokens, w.tokens)
        np.testing.assert_allclose(g.energy_pj, w.energy_pj, rtol=1e-6)
        assert g.energy_pj > 0
    total = sum(r.energy_pj for r in got) + eng.idle_energy_pj
    np.testing.assert_allclose(total, eng.total_energy_pj, rtol=1e-6)
    assert eng.energy_conserved(got)

    # no reduce of the decode step reads a whole weight matrix; the in-step
    # program does (so the check can see one)
    weights = _weight_shapes(cfg, eng.params)
    assert not weights & _decode_reductions(eng, eng.step_params)
    assert weights <= _decode_reductions(eng, eng.params)

    # every active EMT matrix (2 layers x 7 projections + the head) reads
    # prepared statistics; none reduces in-step
    m = eng.metrics()
    assert m["emt_prepared_layers"] == 7 * cfg.num_layers + 1
    assert m["emt_prepared_layers"] == sum(stat_layers(eng.params))
    assert m["emt_inline_layers"] == 0
