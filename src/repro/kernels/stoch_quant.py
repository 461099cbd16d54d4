"""Pallas TPU kernel: fused EF-add + stochastic one-bit quantize + bit pack.

This is the client-side hot loop of PRoBit+: every parameter of the model
difference is binarized (Eq. 5) and packed 8/byte before upload. Fusing
the steps keeps the f32 delta in VMEM and writes only N/8 bytes back to
HBM — a 4x reduction in HBM write traffic vs. materializing int8 codes.
The EF variant (:func:`stoch_quant_ef_2d`) additionally folds the
error-feedback carry in and emits the next residual ``eff - c * b`` from
the same VMEM-resident block, so a sparsified/EF client touches HBM once
per parameter instead of three times (quantize, re-unpack, subtract).

Layout: the flat parameter vector is viewed as ``(rows, 1024)``; packing
reduces 1024 lanes of f32 to 128 lanes of uint8, both tile-aligned. Byte
``j`` of a row holds lanes ``8j..8j+7``, LSB first — the wire order. Mosaic
cannot reshape lanes into a trailing 8, so the 8-lane group sum runs as a
matmul with a constant 0/power-of-two matrix (:func:`pack_weights`) on
the MXU, exact at any precision.

Dispatch policy (see :mod:`repro.kernels.ops`): compiled Pallas on TPU,
the pure-JAX wire in :mod:`repro.kernels.ref` elsewhere; ``interpret=True``
is for kernel-correctness tests only and never auto-selected.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANES = 1024  # f32 elements per row; packs to 128 uint8 lanes


def _binarize(d, b, u):
    """Eq.-5 bits for one VMEM block; identical arithmetic to
    ``repro.core.quantizer.binarize_prob`` (clip, zero-b guard) so kernel
    and pure wires agree bit-for-bit given the same uniforms."""
    safe_b = jnp.where(b > 0, b, 1.0)
    p = jnp.where(b > 0, 0.5 + 0.5 * jnp.clip(d, -b, b) / safe_b, 0.5)
    return u < p


def pack_weights() -> jax.Array:
    """(1024, 128) f32 matrix ``W[l, j] = 2**(l % 8)`` iff ``l // 8 == j``.

    ``bits @ W`` is the LSB-first byte of every 8 consecutive lanes: the
    8-lane group reduction runs on the MXU, so the kernel never reshapes
    lanes into a trailing 8 (Mosaic cannot). Every operand is 0, 1 or a
    power of two up to 128 and every sum is at most 255, so the product
    is exact at any matmul precision."""
    lane = jnp.arange(LANES, dtype=jnp.int32)[:, None]
    byte = jnp.arange(LANES // 8, dtype=jnp.int32)[None, :]
    return jnp.where(lane // 8 == byte, 1 << (lane % 8), 0).astype(jnp.float32)


def _pack(bits, w):
    byte = jnp.dot(
        bits.astype(jnp.float32), w, preferred_element_type=jnp.float32
    )
    return byte.astype(jnp.int32).astype(jnp.uint8)


def _kernel(delta_ref, b_ref, u_ref, w_ref, out_ref):
    d = delta_ref[...].astype(jnp.float32)
    b = b_ref[...].astype(jnp.float32)
    out_ref[...] = _pack(_binarize(d, b, u_ref[...]), w_ref[...])


def _ef_kernel(delta_ref, res_ref, b_ref, u_ref, w_ref, out_ref, new_res_ref):
    eff = delta_ref[...].astype(jnp.float32) + res_ref[...].astype(jnp.float32)
    b = b_ref[...].astype(jnp.float32)
    bits = _binarize(eff, b, u_ref[...])
    out_ref[...] = _pack(bits, w_ref[...])
    new_res_ref[...] = eff - jnp.where(bits, b, -b)


_W_SPEC = pl.BlockSpec((LANES, LANES // 8), lambda r: (0, 0))


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def stoch_quant_pack_2d(
    delta: jax.Array,
    b: jax.Array,
    uniforms: jax.Array,
    *,
    block_rows: int = 8,
    interpret: bool = False,
) -> jax.Array:
    """delta/b/uniforms: (rows, 1024); returns packed (rows, 128) uint8."""
    rows = delta.shape[0]
    assert delta.shape == (rows, LANES) == b.shape == uniforms.shape
    block_rows = min(block_rows, rows)
    grid = (pl.cdiv(rows, block_rows),)
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_rows, LANES), lambda r: (r, 0)),
            pl.BlockSpec((block_rows, LANES), lambda r: (r, 0)),
            pl.BlockSpec((block_rows, LANES), lambda r: (r, 0)),
            _W_SPEC,
        ],
        out_specs=pl.BlockSpec((block_rows, LANES // 8), lambda r: (r, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, LANES // 8), jnp.uint8),
        interpret=interpret,
    )(delta, b, uniforms, pack_weights())


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def stoch_quant_ef_2d(
    delta: jax.Array,
    residual: jax.Array,
    b: jax.Array,
    uniforms: jax.Array,
    *,
    block_rows: int = 8,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Fused EF compress: eff = delta + residual, pack Eq.-5 bits of eff,
    and emit the next carry ``eff - c * b`` in one pass.

    All inputs (rows, 1024) f32; returns (packed (rows, 128) uint8,
    new_residual (rows, 1024) f32).
    """
    rows = delta.shape[0]
    assert (
        delta.shape == (rows, LANES) == residual.shape == b.shape == uniforms.shape
    )
    block_rows = min(block_rows, rows)
    grid = (pl.cdiv(rows, block_rows),)
    spec_in = pl.BlockSpec((block_rows, LANES), lambda r: (r, 0))
    return pl.pallas_call(
        _ef_kernel,
        grid=grid,
        in_specs=[spec_in] * 4 + [_W_SPEC],
        out_specs=[
            pl.BlockSpec((block_rows, LANES // 8), lambda r: (r, 0)),
            pl.BlockSpec((block_rows, LANES), lambda r: (r, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rows, LANES // 8), jnp.uint8),
            jax.ShapeDtypeStruct((rows, LANES), jnp.float32),
        ],
        interpret=interpret,
    )(delta, residual, b, uniforms, pack_weights())
