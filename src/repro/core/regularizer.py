"""Technique B — energy regularization (paper §4.2, Eq. 13).

    L(w, rho) = L0(w, rho) + lambda * sum_t alpha_t * rho * |w_t|

* ``rho`` is a *trainable* per-layer energy coefficient, parametrized through a
  softplus so it stays positive; gradient descent co-optimizes accuracy (through the
  fluctuation amplitude ``sigma_rel(rho)`` in the forward pass) and energy (through
  this term) — Fig. 7.
* ``alpha_t`` is the number of reads of cell ``t`` per inference — for a dense layer
  computing T tokens it is T (one analog read per output row per token), times the
  bit count under bit-serial decomposition.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


RHO_MIN = 1e-3


def rho_init_raw(rho0: float) -> float:
    """Inverse softplus so that softplus(raw) + RHO_MIN == rho0."""
    x = max(rho0 - RHO_MIN, 1e-6)
    return float(np.log(np.expm1(x))) if x < 30 else float(x)


def rho_from_raw(rho_raw):
    return jax.nn.softplus(rho_raw) + RHO_MIN


def layer_reg_term(w, rho, alpha: float, abs_sum=None):
    """alpha * rho * sum|w|  — differentiable in both w and rho.

    ``abs_sum``: a precomputed sum|w| (a serving tree's weight statistics;
    no gradient reaches ``w`` through it)."""
    if abs_sum is None:
        abs_sum = jnp.sum(jnp.abs(w.astype(jnp.float32)))
    return alpha * rho * abs_sum


def total_energy_loss(reg_terms, lam: float):
    """lambda * sum over layers; reg_terms is a list/pytree of scalars."""
    total = sum(jax.tree.leaves(reg_terms)) if reg_terms else 0.0
    return lam * total
