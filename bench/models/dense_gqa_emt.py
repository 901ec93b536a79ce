"""Plain reference: a dense GQA decoder whose projections are EMT crossbars.

A straightforward ``jax.numpy`` forward pass over whole sequences, with no
cache, no batching of requests and no kernels, written from the
configuration file alone (it imports nothing of the served program):

* token embedding, then per layer: RMSNorm, GQA attention with rotary
  positions (halves rotated, theta from the file) and a causal softmax,
  residual add, RMSNorm, SwiGLU MLP, residual add; a final RMSNorm and the
  head over the vocabulary slice;
* every projection (q, k, v, o, gate, up, down, head) is an EMT crossbar
  read: weights quantized per output column to ``w_bits`` symmetric
  levels, inputs per token row to ``a_bits`` levels, and each weight read
  through a two-state fluctuation ``w * (1 + a * sigma(rho))`` whose state
  is a counter hash of (seed, layer plane, row, column); ``analog``, the
  one mode written here, reads each weight once per product.

Float32 runs use the matmul precision the configuration states
(``matmul_precision``: the TPU's ``default``, one bfloat16 pass per product
with float32 accumulation, is what the served program computes); the
control runs the same code in bfloat16.  Rows are processed in fixed
blocks and attention one sequence at a time, so the compiled shapes do not
depend on the sample.

Beside the forward pass the module holds what else the harness needs to
know of this architecture: whether the served program's model config is
the one the file states (:func:`config_matches`), its parameter tree
(:func:`expected_tree`), and the work its steps need
(:func:`attention_call`, :func:`model_flops`).
"""
from __future__ import annotations

import math
import zlib
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

ROW_BLOCK = 512
NEG = -1e30

# counter hash (two rounds of a 32-bit avalanche finalizer over a Weyl
# counter of (row, col, plane, seed))
_C1, _C2, _C3 = 0x9E3779B9, 0x85EBCA6B, 0xC2B2AE35
_M1, _M2 = 0x7FEB352D, 0x846CA68B


def _finalize(x):
    x = x ^ (x >> 16)
    x = x * jnp.uint32(_M1)
    x = x ^ (x >> 15)
    x = x * jnp.uint32(_M2)
    return x ^ (x >> 16)


def hash_bits(seed, plane, k: int, n: int):
    """uint32 hash of every (row, col) of a k x n array; ``seed`` and
    ``plane`` are uint32 scalars."""
    rows = jax.lax.broadcasted_iota(jnp.uint32, (k, n), 0)
    cols = jax.lax.broadcasted_iota(jnp.uint32, (k, n), 1)
    h = (rows * jnp.uint32(_C1)) ^ (cols * jnp.uint32(_C2))
    h = h ^ (plane * jnp.uint32(_C3)) ^ seed
    return _finalize(_finalize(h) ^ jnp.uint32(0x68E31DA4))


def normalized_states(offsets, probs):
    """State offsets shifted and scaled to mean 0 and variance 1 under
    their probabilities."""
    a = np.asarray(offsets, np.float64)
    p = np.asarray(probs, np.float64)
    p = p / p.sum()
    a = a - (p * a).sum()
    var = (p * a * a).sum()
    if var > 0:
        a = a / math.sqrt(var)
    return tuple(float(v) for v in a), tuple(float(v) for v in p)


def state_offsets(seed, plane, k: int, n: int, offsets, probs):
    """The fluctuation state offset of every (row, col) of a k x n array."""
    bits = hash_bits(seed, plane, k, n)
    hi = (bits >> 16).astype(jnp.int32).astype(jnp.float32)
    lo = (bits & jnp.uint32(0xFFFF)).astype(jnp.int32).astype(jnp.float32)
    u = (hi * 65536.0 + lo) * (1.0 / 4294967296.0)
    out = jnp.full((k, n), offsets[0], jnp.float32)
    cum = 0.0
    for i, p in enumerate(probs[:-1]):
        cum += p
        out = jnp.where(u >= cum, offsets[i + 1], out)
    return out


def layer_plane(tag: str) -> int:
    """A layer's noise plane, from its name."""
    return zlib.crc32(tag.encode()) & 0x7FFFFFF


def rho_raw_of(rho: float, rho_min: float) -> float:
    """Inverse of rho = softplus(raw) + rho_min."""
    x = max(rho - rho_min, 1e-6)
    return float(np.log(np.expm1(x))) if x < 30 else float(x)


# ---------------------------------------------------------------------------
# the served program's parameter tree: names and initial values
# ---------------------------------------------------------------------------
def layer_paths(cfg: dict, i: int) -> dict:
    """``{path: shape}`` of layer i's leaves in the served parameter tree."""
    D, F = cfg["hidden_size"], cfg["intermediate_size"]
    H, KV = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = D // H
    base = f"decoder/layer_{i:03d}"
    out = {f"{base}/norm1/scale": (D,), f"{base}/norm2/scale": (D,)}
    for name, shape in (("attn/wq", (D, H * hd)), ("attn/wk", (D, KV * hd)),
                        ("attn/wv", (D, KV * hd)), ("attn/wo", (H * hd, D)),
                        ("ffn/wg", (D, F)), ("ffn/wu", (D, F)),
                        ("ffn/wd", (F, D))):
        out[f"{base}/{name}/w"] = shape
        out[f"{base}/{name}/rho_raw"] = ()
    return out


def expected_tree(cfg: dict) -> dict:
    """``{path: shape}`` of every leaf of the served parameter tree."""
    D, V = cfg["hidden_size"], cfg["vocab_size"]
    out = {"embed/table": (V, D), "final_norm/scale": (D,),
           "lm_head/w": (D, V), "lm_head/rho_raw": ()}
    for i in range(cfg["num_hidden_layers"]):
        out.update(layer_paths(cfg, i))
    return out


def make_init(cfg: dict):
    """Leaf path and shape -> ``("normal", std)`` or ``("const", value)``."""
    raw = rho_raw_of(cfg["emt"]["rho"], cfg["emt"]["rho_min"])

    def init(path: str, shape):
        if path.endswith("/rho_raw"):
            return "const", raw
        if path.endswith("/scale"):
            return "const", 1.0
        if path in ("embed/table", "lm_head/w"):
            return "normal", 0.02
        if path.endswith("/w") and len(shape) == 2:
            return "normal", 1.0 / math.sqrt(shape[0])
        raise ValueError(f"no initial value for leaf {path!r} {shape}")

    return init


# ---------------------------------------------------------------------------
# blocks of the forward pass (jitted, fixed shapes)
# ---------------------------------------------------------------------------
def _levels(x, bits: int, dt):
    qmax = 2.0 ** (bits - 1) - 1.0
    scale = jnp.maximum(jnp.max(jnp.abs(x), axis=-1, keepdims=True),
                        1e-8) / qmax
    return jnp.clip(jnp.round(x / scale), -qmax, qmax).astype(dt), \
        scale.astype(dt)


def _wq(w, bits: int):
    qmax = 2.0 ** (bits - 1) - 1.0
    scale = jnp.maximum(jnp.max(jnp.abs(w), axis=0, keepdims=True),
                        1e-8) / qmax
    return jnp.clip(jnp.round(w / scale), -qmax, qmax) * scale


@partial(jax.jit, static_argnames=("spec", "dt"))
def read_weights(w, rho_raw, seed, plane, *, spec, dt):
    """The crossbar's read of a weight: quantized, then fluctuated."""
    (bits, rho_min, amp, beta, offsets, probs) = spec
    w = w.astype(dt)
    wq = _wq(w, bits)
    rho = jax.nn.softplus(rho_raw.astype(dt)) + rho_min
    sig = amp / jnp.power(jnp.maximum(rho, rho_min), beta)
    offs = state_offsets(seed, plane, w.shape[0], w.shape[1], offsets,
                         probs).astype(dt)
    return wq * (1.0 + offs * sig)


@partial(jax.jit, static_argnames=("bits", "dt"))
def analog_rows(x, wn, *, bits, dt):
    lv, s = _levels(x.astype(dt), bits, dt)
    return (lv * s) @ wn


@partial(jax.jit, static_argnames=("eps",))
def rmsnorm(x, scale, *, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(x.dtype)


@partial(jax.jit, static_argnames=("theta", "heads"))
def rope(x, pos, *, theta, heads):
    T = x.shape[0]
    x = x.reshape(T, heads, -1)
    hd = x.shape[-1]
    freqs = jnp.asarray(1.0 / (theta ** (np.arange(0, hd, 2) / hd)),
                        jnp.float32)
    ang = pos[:, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)
    return out.astype(x.dtype).reshape(T, -1)


@partial(jax.jit, static_argnames=("heads", "kv_heads", "qblock"))
def causal_attention(q, k, v, *, heads, kv_heads, qblock):
    """One sequence, padded to a fixed length: q (L, H*hd), k/v (L, KV*hd)."""
    L = q.shape[0]
    hd = q.shape[1] // heads
    G = heads // kv_heads
    q = q.reshape(L // qblock, qblock, kv_heads, G, hd)
    k = k.reshape(L, kv_heads, hd)
    v = v.reshape(L, kv_heads, hd)
    kpos = jnp.arange(L)

    def block(args):
        qb, b = args
        s = jnp.einsum("qkgh,skh->kgqs", qb, k) / np.sqrt(hd)
        qpos = b * qblock + jnp.arange(qblock)
        s = jnp.where(kpos[None, :] <= qpos[:, None], s, NEG)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("kgqs,skh->qkgh", p.astype(v.dtype), v)

    out = jax.lax.map(block, (q, jnp.arange(L // qblock)))
    return out.reshape(L, heads * hd)


@partial(jax.jit, static_argnames=("heads", "kv_heads", "qblock", "L"),
         donate_argnums=(0,))
def attend_into(o, q, k, v, start, *, heads, kv_heads, qblock, L):
    """Attend the sequence whose rows start at ``start`` (a window of ``L``
    rows; rows past the sequence belong to the next one and only ever
    reach queries past it) and write the window into ``o``.  Sequences are
    written in order, so each overwrites its predecessor's spill."""
    win = [jax.lax.dynamic_slice_in_dim(a, start, L) for a in (q, k, v)]
    att = causal_attention(*win, heads=heads, kv_heads=kv_heads,
                           qblock=qblock)
    return jax.lax.dynamic_update_slice_in_dim(o, att.astype(o.dtype),
                                               start, 0)


@jax.jit
def swiglu(g, u):
    return jax.nn.silu(g) * u


# ---------------------------------------------------------------------------
# the forward pass
# ---------------------------------------------------------------------------
class Reference:
    """Logits of chosen rows of whole sequences, from the seed alone.

    ``make_leaves(named_shapes)`` draws the weights the served program was
    given (the harness's seeded generator); ``noise_seed`` is the
    fluctuation seed the configuration serves with."""

    def __init__(self, cfg: dict, make_leaves, noise_seed: int, max_len: int,
                 dtype=jnp.float32, precision: str | None = None):
        self.cfg = cfg
        self.precision = precision or (
            cfg["matmul_precision"] if jnp.dtype(dtype) == jnp.float32
            else "default")
        self.make_leaves = make_leaves
        self.seed = jnp.uint32(noise_seed)
        self.max_len = int(max_len)
        self.dt = jnp.dtype(dtype)
        emt = cfg["emt"]
        self.bits_w, self.bits_a = emt["w_bits"], emt["a_bits"]
        offs, probs = normalized_states(emt["state_offsets"],
                                        emt["state_probs"])
        self.kind = {}
        self.spec = {}
        for part, e in emt["layers"].items():
            self.kind[part] = e["mode"]
            self.spec[part] = (self.bits_w, emt["rho_min"],
                               e["amplitude"] * emt["intensity_scale"],
                               e["beta"], offs, probs)

    def _blocks(self, x):
        T = x.shape[0]
        return [x[i:i + ROW_BLOCK] for i in range(0, T, ROW_BLOCK)]

    def reader(self, w, rho_raw, tag: str, part: str):
        """``rows(x_block) -> y_block``: the analog EMT read of ``x @ w``;
        the fluctuated weight is made once."""
        dt, bits = self.dt, self.bits_a
        if self.kind[part] != "analog":
            raise ValueError(f"unsupported EMT mode {self.kind[part]!r}")
        wn = read_weights(w, rho_raw, self.seed, jnp.uint32(layer_plane(tag)),
                          spec=self.spec[part], dt=dt)
        return lambda b: analog_rows(b, wn, bits=bits, dt=dt)

    def dense(self, x, w, rho_raw, tag: str, part: str):
        """EMT read of ``x @ w`` for every row, in fixed row blocks."""
        rows = self.reader(w, rho_raw, tag, part)
        return jnp.concatenate([rows(b) for b in self._blocks(x)])

    def logits(self, seqs, rows):
        """``seqs``: token arrays; ``rows[i]``: positions of sequence i whose
        logits are wanted.  Returns (sum of len(rows[i]), vocab) logits."""
        with jax.default_matmul_precision(self.precision):
            return self._logits(seqs, rows)

    def _logits(self, seqs, rows):
        cfg, dt = self.cfg, self.dt
        H, KV = cfg["num_attention_heads"], cfg["num_key_value_heads"]
        eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
        lens = [len(s) for s in seqs]
        if max(lens) > self.max_len:
            raise ValueError(f"sequence of {max(lens)} > {self.max_len}")
        starts = np.concatenate([[0], np.cumsum(lens)])
        T = int(starts[-1])
        Tp = -(-T // ROW_BLOCK) * ROW_BLOCK
        toks = np.zeros(Tp, np.int32)
        pos = np.zeros(Tp, np.int32)
        for s, a in zip(seqs, starts):
            toks[a:a + len(s)] = s
            pos[a:a + len(s)] = np.arange(len(s))
        pos = jnp.asarray(pos)
        table = self.make_leaves({"embed/table": (cfg["vocab_size"],
                                                  cfg["hidden_size"])})
        h = jnp.take(table["embed/table"], jnp.asarray(toks), axis=0)
        h = h.astype(dt)
        del table
        for i in range(cfg["num_hidden_layers"]):
            W = {k.split("/", 2)[2]: v.astype(dt) if v.ndim else v
                 for k, v in self.make_leaves(layer_paths(cfg, i)).items()}
            tag = f"dec/layer_{i:03d}"

            def proj(x, name, part, sub):
                return self.dense(x, W[f"{sub}/{name}/w"],
                                  W[f"{sub}/{name}/rho_raw"],
                                  f"{tag}/{part}/{name}", part)

            x = rmsnorm(h, W["norm1/scale"], eps=eps)
            q = rope(proj(x, "wq", "attn", "attn"), pos, theta=theta, heads=H)
            k = rope(proj(x, "wk", "attn", "attn"), pos, theta=theta,
                     heads=KV)
            v = proj(x, "wv", "attn", "attn")
            L = self.max_len
            q, k, v = (jnp.pad(a, ((0, L), (0, 0))) for a in (q, k, v))
            o = jnp.zeros_like(q)
            for a in starts[:-1]:
                o = attend_into(o, q, k, v, jnp.int32(a), heads=H,
                                kv_heads=KV, qblock=min(ROW_BLOCK, L), L=L)
            del q, k, v
            h = h + proj(o[:Tp], "wo", "attn", "attn")
            x = rmsnorm(h, W["norm2/scale"], eps=eps)
            # the MLP a row block at a time: its (rows, d_ff) activations
            # never exist for the whole sample
            g, u, d = (self.reader(W[f"ffn/{n}/w"], W[f"ffn/{n}/rho_raw"],
                                   f"{tag}/mlp/{n}", "mlp")
                       for n in ("wg", "wu", "wd"))
            h = h + jnp.concatenate([d(swiglu(g(b), u(b)))
                                     for b in self._blocks(x)])
            del W, x, o, g, u, d
        sel = np.concatenate([a + np.asarray(r, np.int64)
                              for a, r in zip(starts[:-1], rows)])
        R = len(sel)
        Rp = -(-R // ROW_BLOCK) * ROW_BLOCK
        hs = jnp.take(h, jnp.asarray(np.pad(sel, (0, Rp - R))), axis=0)
        del h
        last = self.make_leaves({"final_norm/scale": (cfg["hidden_size"],),
                                 "lm_head/w": (cfg["hidden_size"],
                                               cfg["vocab_size"]),
                                 "lm_head/rho_raw": ()})
        hs = rmsnorm(hs, last["final_norm/scale"], eps=eps)
        out = self.dense(hs, last["lm_head/w"].astype(dt),
                         last["lm_head/rho_raw"], "unembed", "unembed")
        return out[:R].astype(jnp.float32)


# ---------------------------------------------------------------------------
# the served program's model config against the file
# ---------------------------------------------------------------------------
# a weight of each EMT part of the served model, by the program's path
_EMT_PATHS = {"attn": "dec/layer_000/attn/wq", "mlp": "dec/layer_000/mlp/wg",
              "unembed": "unembed"}


def config_matches(cfg: dict, model) -> dict:
    """``{field: (program's, file's)}`` of every way the served program's
    model config ``model`` departs from this architecture at the file's
    sizes and EMT physics; empty where it runs what the file states."""
    D, H = cfg["hidden_size"], cfg["num_attention_heads"]
    want = {
        "d_model": D, "d_ff": cfg["intermediate_size"], "num_heads": H,
        "num_kv_heads": cfg["num_key_value_heads"], "head_dim": D // H,
        "num_layers": cfg["num_hidden_layers"],
        "vocab_size": cfg["vocab_size"], "rope_theta": cfg["rope_theta"],
        "norm_eps": cfg["rms_norm_eps"], "act": cfg["hidden_act"],
        "tie_embeddings": cfg["tie_word_embeddings"],
        "sliding_window": 0, "qk_norm": False, "embed_scale": False,
        "attn_softcap": 0.0, "final_softcap": 0.0, "rope_type": "default",
        "num_experts": 0, "is_encdec": False,
    }
    bad = {k: (getattr(model, k), v) for k, v in want.items()
           if getattr(model, k) != v}
    if set(model.blocks()) not in ({"attn"}, {"global"}):
        bad["blocks"] = (model.blocks(), "all full attention")
    emt = cfg["emt"]
    for part, path in _EMT_PATHS.items():
        e, w = model.emt_at(path), emt["layers"][part]
        got = {"mode": e.mode, "w_bits": e.quant.w_bits,
               "a_bits": e.quant.a_bits, "a_per_row": e.quant.a_per_row,
               "amplitude": e.device.amplitude * e.device.intensity_scale,
               "beta": e.device.beta, "rho": e.rho_init,
               "noise": e.noise.backend, "per_channel": e.quant.per_channel,
               "states": len(e.device.state_offsets)}
        exp = {"mode": w["mode"], "w_bits": emt["w_bits"],
               "a_bits": emt["a_bits"], "a_per_row": emt["a_per_row"],
               "amplitude": w["amplitude"] * emt["intensity_scale"],
               "beta": w["beta"], "rho": emt["rho"], "noise": "hash",
               "per_channel": True, "states": len(emt["state_offsets"])}
        bad.update({f"{part}.{k}": (got[k], exp[k]) for k in exp
                    if got[k] != exp[k]})
    return bad


# ---------------------------------------------------------------------------
# the work a step needs, from its live rows
# ---------------------------------------------------------------------------
# Counted from what each step does for its live rows (each row's context and
# new tokens), never from the compiled program, the view bucket or the block
# padding: a change of implementation must not change the count.  A step row
# is ``(start, ntok, logits)``: the row writes and attends ``ntok`` new
# positions ``start .. start + ntok - 1`` and, where ``logits``, its last
# one goes through the head (it samples a token).

KV_BYTES = 4           # float32 caches, as the configuration states


def layer_params(cfg: dict) -> int:
    D, F = cfg["hidden_size"], cfg["intermediate_size"]
    H, KV = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = D // H
    return D * H * hd * 2 + D * KV * hd * 2 + 3 * D * F


def head_params(cfg: dict) -> int:
    return cfg["hidden_size"] * cfg["vocab_size"]


def _pairs(start: int, ntok: int) -> int:
    """(query, key) pairs of ``ntok`` new positions from ``start``: new
    position p attends to positions 0..p."""
    return ntok * start + ntok * (ntok + 1) // 2


def attention_call(cfg: dict, rows) -> tuple:
    """(FLOPs, bytes) of one layer's paged attention over the step's rows.

    QK^T and PV are ``2 * 2 * heads * head_dim`` FLOPs per (query, key)
    pair.  Bytes: each row's K and V for positions ``0 .. start + ntok -
    1`` read once, its new K and V written, its queries read and its
    outputs written (float32)."""
    H, KV = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg["hidden_size"] // H
    flops = byts = 0.0
    for start, ntok, _ in rows:
        if ntok <= 0:
            continue
        flops += 4.0 * H * hd * _pairs(start, ntok)
        byts += 2.0 * (start + ntok) * KV * hd * KV_BYTES  # K and V read
        byts += 2.0 * ntok * KV * hd * KV_BYTES            # new K, V written
        byts += 2.0 * ntok * H * hd * 4                    # queries, outputs
    return flops, byts


def model_flops(cfg: dict, rows) -> float:
    """Model FLOPs of one step: 2 x parameters touched per new token, the
    head for each row that samples, and attention over each new token's
    live context.  The simulation's extra work (quantizing, fluctuation
    draws) is not model work."""
    H, L = cfg["num_attention_heads"], cfg["num_hidden_layers"]
    hd = cfg["hidden_size"] // H
    total = 0.0
    for start, ntok, logits in rows:
        if ntok <= 0:
            continue
        total += 2.0 * layer_params(cfg) * L * ntok
        if logits:
            total += 2.0 * head_params(cfg)
        total += 4.0 * H * hd * _pairs(start, ntok) * L
    return total
