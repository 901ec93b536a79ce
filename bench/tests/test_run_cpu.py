"""A whole run of the harness at a test size on the CPU, its look for a chip
skipped: correct as served, and not correct with the timed path broken or
with the control in the program's place, judged by the statistic and limit
of the cell's own limits file."""
import json
import subprocess
import sys
from types import SimpleNamespace

import numpy as np
import pytest

import control
import run
from conftest import BENCH

CPU = {"platform": "cpu", "kind": "cpu", "count": 1}
SEED = 2 ** 31 + 17
LIMITS = run.load_json(BENCH / "limits" / "analog-reason.json")
END_TO_END = [{"name": "out_tok_s", "unit": "tokens/s"},
              {"name": "setup_s", "unit": "s"}]


def cell(in_flight=True):
    mix = run.load_json(BENCH / "tests" / "tiny-backlog.json")
    return SimpleNamespace(
        name="tiny", chips=1,
        conf=run.load_json(BENCH / "tests" / "tiny-analog.json"),
        mix=dict(mix, in_flight=in_flight), limits=LIMITS,
        end_to_end=END_TO_END, per_layer=[])


def tails(res):
    return [res["checks"][name]["value"] for name in LIMITS]


@pytest.mark.parametrize("in_flight", [False, True])
def test_run_is_correct(in_flight):
    c = cell(in_flight)
    res = run.run(c, SEED, 2.0, False, CPU)
    assert res["correct"], res["checks"]
    assert list(res)[-1] == "checks"
    assert set(res["checks"]) >= set(LIMITS)
    assert res["checks"]["window_compiles"]["value"] == 0
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {m["name"] for m in c.end_to_end}
    json.dumps(res)


def _alter_tokens(monkeypatch):
    """A token altered where it is produced: every sampled token + 1."""
    from repro.serve import engine, sampling

    real = sampling.greedy_tokens

    def wrong(logits):
        return (real(logits) + 1) % logits.shape[-1]

    monkeypatch.setattr(sampling, "greedy_tokens", wrong)
    monkeypatch.setattr(engine.sampling, "greedy_tokens", wrong)


def _state_unchanged(monkeypatch):
    """A step that returns its state unchanged: no K/V is ever written."""
    from repro.models import lm

    for name in ("chunk_step", "decode_step"):
        real = getattr(lm, name)

        def frozen(params, cache, *a, _real=real, **k):
            logits, _, aux = _real(params, cache, *a, **k)
            return logits, cache, aux

        monkeypatch.setattr(lm, name, frozen)


@pytest.mark.parametrize("fault", [_alter_tokens, _state_unchanged])
def test_broken_path_is_not_correct(monkeypatch, fault):
    fault(monkeypatch)
    res = run.run(cell(), SEED, 2.0, False, CPU)
    assert not res["correct"]
    assert all(v > lim["limit"] for v, lim in zip(tails(res),
                                                   LIMITS.values()))


def test_control_reads_above_the_program():
    # At this size the logits spread about ten times less than at the
    # cell's widths, so the bfloat16 control's gaps stay under the cell's
    # tau here; that it fails `correct` is read on the chip (PERF.md).
    # Here: it is judged by the same comparison, with the cell's limits,
    # and it puts other tokens first than the float32 program does.
    c = cell()
    rec = run.measure(c, SEED + 1, 2.0, False, CPU)
    r = control.readings(c, rec, run.noise_seed(SEED + 1))
    assert r["program"]["correct"], r["program"]["checks"]
    assert r["program"]["changed"] == 0
    assert set(r["control"]["checks"]) == set(r["program"]["checks"])
    assert set(r["control"]["checks"]) >= set(LIMITS)
    assert r["control"]["tokens"] == r["program"]["tokens"] > 0
    assert r["control"]["changed"] > r["program"]["changed"]


def test_no_accelerator_no_result(tmp_path):
    # JAX sees only the CPU here: the run refuses and prints nothing
    out = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload",
         "analog-reason", "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=300,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"})
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "accelerator" in out.stderr


def test_sample_holds_the_longest():
    fin = [(np.zeros(3, np.int32), np.zeros(n, np.int32), n)
           for n in (4, 9, 2, 7, 5)]
    got = run.check.sample(fin, 3, 5)
    assert len(got) == 3 and len(got[0][1]) == 9
    assert run.check.sample(fin, 3, 5) == got
