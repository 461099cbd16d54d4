"""Pallas TPU kernel: server-side vote count for the ML estimate (Eq. 13).

Reads the (M, N/8) packed uint8 code matrix in (client-block, column-block)
tiles and counts the +1 votes N_i: each byte widens to int32 and bit
plane ``k`` sums over the client (sublane) axis. The counts accumulate
exactly in an int32 output block across the client-block grid axis, laid
out bit-plane-major — row ``k`` of a (8, 128) block holds coordinates
``8j + k`` — because Mosaic cannot interleave lanes — and XLA permutes
them to wire order. ``theta_hat = (2 N_i - M) / M * b_i`` is applied in
XLA by the caller (:func:`repro.kernels.ops.bit_aggregate`) on the same
(n,) operands as the ref engine: XLA reassociates that expression when
``b`` is one broadcast value (it folds ``b`` in before dividing by M),
so it must see the same graph on either engine to round the same way.

The grid is (column-rows, client-steps) with the client axis innermost:
each output block is revisited ``m_steps`` times while Pallas's grid
pipelining double-buffers the next packed tile's HBM->VMEM copy behind the
current count. HBM read traffic is M * N/8 bytes (vs 4 * M * N for a
full-precision FedAvg reduce) — the paper's 32x wire claim realized at the
memory-system level.

Dispatch policy (see :mod:`repro.kernels.ops`): compiled Pallas on TPU,
the pure-JAX wire in :mod:`repro.kernels.ref` elsewhere; ``interpret=True``
is for kernel-correctness tests only and never auto-selected.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BYTE_BLOCK = 128  # uint8 lanes per grid step -> 1024 output elements
LANES = BYTE_BLOCK * 8
M_BLOCK = 256  # clients per grid step (multiple of 8)


def _kernel(packed_ref, out_ref):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    x = packed_ref[...].astype(jnp.int32)  # (mb, 128): one byte per lane
    for k in range(8):
        votes = jnp.sum((x >> k) & 1, axis=0, keepdims=True)  # (1, 128)
        out_ref[0, k : k + 1, :] += votes


@functools.partial(jax.jit, static_argnames=("m_block", "interpret"))
def bit_count_2d(
    packed: jax.Array,
    *,
    m_block: int = M_BLOCK,
    interpret: bool = False,
) -> jax.Array:
    """packed: (M, C) uint8 with C % 128 == 0 -> (C // 128, 1024) int32.

    The +1 vote count of every coordinate, in wire order: the 2D view of
    the flat N = 8 * C count. M may be any positive count (client rows are
    zero-padded to a whole number of ``m_block`` tiles; zero bytes add
    zero votes).
    """
    m, c = packed.shape
    assert c % BYTE_BLOCK == 0
    rows = c // BYTE_BLOCK
    assert m_block % 8 == 0
    mb = min(m_block, ((m + 7) // 8) * 8)
    m_pad = ((m + mb - 1) // mb) * mb
    packed = jnp.pad(packed, ((0, m_pad - m), (0, 0)))
    counts = pl.pallas_call(
        _kernel,
        grid=(rows, m_pad // mb),
        in_specs=[pl.BlockSpec((mb, BYTE_BLOCK), lambda r, i: (i, r))],
        out_specs=pl.BlockSpec((1, 8, BYTE_BLOCK), lambda r, i: (r, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, 8, BYTE_BLOCK), jnp.int32),
        interpret=interpret,
    )(packed)
    # bit-plane-major (row, bit k, byte j) -> wire order: coordinate 8j+k
    return counts.transpose(0, 2, 1).reshape(rows, LANES)


@functools.partial(jax.jit, static_argnames=("m_block", "interpret"))
def bit_aggregate_2d(
    packed: jax.Array,
    b2d: jax.Array,
    *,
    m_block: int = M_BLOCK,
    interpret: bool = False,
) -> jax.Array:
    """packed: (M, C) uint8 with C % 128 == 0; b2d: (C/128, 1024) f32.

    Returns theta_hat shaped (C // 128, 1024) f32 — the 2D view of the
    flat N = 8 * C estimate, from :func:`bit_count_2d`'s counts and the
    true M.
    """
    m = packed.shape[0]
    assert b2d.shape == (packed.shape[1] // BYTE_BLOCK, LANES)
    counts = bit_count_2d(packed, m_block=m_block, interpret=interpret)
    return (2.0 * counts.astype(jnp.float32) - m) / m * b2d
