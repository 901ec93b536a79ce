"""What decides ``correct``: the served tokens against the plain reference.

Once the window has closed and the served state is freed, a sample drawn
from the seed of the finished requests (the one with the most served
tokens always among them) is run through the configuration's reference,
over each prompt with its served tokens.  At each served position the gap
by which the served token's reference logit lies below the reference's
best logit is read.  The statistics of those gaps that the cell's limits
file (``bench/limits/<cell>.json``) names are held to their limits, which
were set from the program's readings and the control's
(``bench/control.py``, which judges the control through this same
comparison):

* ``gap_tail`` (or ``gap_tail@<name>``): the share of served tokens more
  than ``tau`` logits below the reference's best.

Besides: no backend compile inside the window, every finished request
carries its ``max_new`` tokens, and the sample is not empty.
"""
from __future__ import annotations

from typing import List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

import weights


class ConfigMismatch(ValueError):
    """The served program does not run what the configuration file states."""


def config_matches(conf: dict, cfg, ref) -> None:
    """The program's model config holds the file's published widths and the
    EMT physics the reference simulates, as the reference module judges;
    else the reference would check another model."""
    bad = ref.config_matches(conf, cfg)
    if bad:
        raise ConfigMismatch(
            f"{conf['name']}: program vs file (got, want): {bad}")


def tree_matches(shapes, conf: dict, ref) -> None:
    """The program's parameter leaves are exactly those the reference
    regenerates, with the same shapes."""
    flat, _ = jax.tree_util.tree_flatten_with_path(shapes)
    got = {weights.path_str(p): tuple(s.shape) for p, s in flat}
    want = ref.expected_tree(conf)
    if got != want:
        diff = sorted(set(got.items()) ^ set(want.items()))[:8]
        raise ConfigMismatch(f"parameter tree differs from the reference's: "
                             f"{diff}")


def sample(finished: list, k: int, seed: int) -> list:
    """k finished requests drawn from the seed, the longest served among
    them.  ``finished``: (prompt, served tokens, max_new) triples."""
    if not finished:
        return []
    longest = max(range(len(finished)), key=lambda i: len(finished[i][1]))
    rest = [i for i in range(len(finished)) if i != longest]
    rng = np.random.default_rng([seed, 11])
    pick = rng.permutation(rest)[:max(0, k - 1)].tolist()
    return [finished[i] for i in [longest] + sorted(pick)]


def sequences(chosen: list) -> Tuple[List[np.ndarray], List[np.ndarray],
                                     np.ndarray]:
    """Each prompt with its served tokens but the last, the positions whose
    logits chose a served token, and those tokens."""
    seqs, rows, served = [], [], []
    for prompt, toks, _ in chosen:
        P, n = len(prompt), len(toks)
        seqs.append(np.concatenate([prompt, toks[:-1]]).astype(np.int32))
        rows.append(np.arange(P - 1, P - 1 + n))
        served.append(toks)
    return seqs, rows, np.concatenate(served).astype(np.int32)


def reference(rec, conf: dict, noise_seed: int, dtype=jnp.float32,
              precision=None):
    init = rec.ref.make_init(conf)
    return rec.ref.Reference(
        conf, lambda shapes: weights.make_leaves(rec.seed, shapes, init),
        noise_seed, rec.max_len, dtype=dtype, precision=precision)


def gaps(logits, tokens) -> np.ndarray:
    """Reference best logit minus the reference logit of ``tokens``."""
    best = jnp.max(logits, axis=-1)
    got = jnp.take_along_axis(logits, jnp.asarray(tokens)[:, None], -1)[:, 0]
    return np.asarray(best - got, np.float64)


STATS = {
    "gap_tail": lambda g, lim: float(np.mean(g > lim["tau"])),
}


def compare(cell, rec, noise_seed: int, control: bool = False) -> dict:
    """Every compared number with its limit, in a fixed order.  With
    ``control``, the tokens judged at each served position are not the
    served ones but the bfloat16 reference's first choice there: the
    control put in the program's place."""
    return judge(cell, rec, noise_seed, control)[0]


def judge(cell, rec, noise_seed: int, control: bool = False):
    """:func:`compare`'s checks, with the tokens judged and the tokens
    served at the sampled positions."""
    kinds = {name.split("@")[0] for name in cell.limits}
    if not cell.limits or kinds - set(STATS):
        raise ConfigMismatch(f"{cell.name}: limits name {sorted(cell.limits)}"
                             f"; known statistics {sorted(STATS)}")
    chosen = sample(rec.finished, int(cell.mix["check_sample"]), rec.seed)
    short = sum(1 for _, toks, m in rec.finished if len(toks) != m)
    out = {}
    judged = served = np.zeros(0, np.int32)
    if chosen:
        seqs, rows, served = sequences(chosen)
        judged = served
        if control:
            low = reference(rec, cell.conf, noise_seed,
                            dtype=jnp.bfloat16).logits(seqs, rows)
            judged = np.asarray(jnp.argmax(low, axis=-1), np.int32)
            del low
        logits = reference(rec, cell.conf, noise_seed).logits(seqs, rows)
        g = gaps(logits, judged)
        del logits
    for name, lim in cell.limits.items():
        # nothing compared reads far above any limit
        v = STATS[name.split("@")[0]](g, lim) if chosen else 1e30
        out[name] = {"value": v, "limit": float(lim["limit"])}
    return {
        **out,
        "unchecked": {"value": float(not chosen), "limit": 0.0},
        "short_answers": {"value": float(short), "limit": 0.0},
        "window_compiles": {"value": float(rec.window_compiles),
                            "limit": 0.0},
    }, judged, served


def correct(checks: dict) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values())
