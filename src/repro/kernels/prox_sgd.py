"""Pallas TPU kernel: fused prox-regularized SGD+momentum update (Eq. 4).

The inner loop of every PRoBit+ client performs, per parameter,

    g      = grad + lam * (w - w_global)
    m'     = mu * m + g
    w'     = w - eta * m'

Unfused this is 4 HBM-bound elementwise passes; the fused kernel streams
each operand exactly once (4 reads, 2 writes per element).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 1024


def _kernel(w_ref, w0_ref, g_ref, m_ref, eta_lam_mu_ref, w_out_ref, m_out_ref):
    eta = eta_lam_mu_ref[0]
    lam = eta_lam_mu_ref[1]
    mu = eta_lam_mu_ref[2]
    w = w_ref[...]
    g = g_ref[...] + lam * (w - w0_ref[...])
    new_m = mu * m_ref[...] + g
    m_out_ref[...] = new_m
    w_out_ref[...] = w - eta * new_m


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def prox_sgd_2d(
    w: jax.Array,
    w0: jax.Array,
    grad: jax.Array,
    momentum: jax.Array,
    eta_lam_mu: jax.Array,
    *,
    block_rows: int = 8,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """All tensor args (rows, 1024) f32; eta_lam_mu (3,) f32 in SMEM."""
    rows = w.shape[0]
    block_rows = min(block_rows, rows)
    grid = (pl.cdiv(rows, block_rows),)
    spec = pl.BlockSpec((block_rows, LANES), lambda r: (r, 0))
    w_new, m_new = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            spec,
            spec,
            spec,
            spec,
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=[spec, spec],
        out_shape=[
            jax.ShapeDtypeStruct((rows, LANES), jnp.float32),
            jax.ShapeDtypeStruct((rows, LANES), jnp.float32),
        ],
        interpret=interpret,
    )(w, w0, grad, momentum, eta_lam_mu)
    return w_new, m_new
