"""Pure-JAX reference wire: oracles for every Pallas kernel in this package.

Two jobs, one implementation:

1. **Semantics oracle.** Tests sweep shapes/dtypes and assert the Pallas
   outputs (run in ``interpret`` mode on CPU) match these bit-for-bit.
2. **Dispatch target.** On any backend without a Mosaic compiler (CPU,
   GPU today), :mod:`repro.kernels.ops` routes ``use_kernels=True`` here
   instead of at interpret-mode Pallas — interpret mode emulates the
   kernel lane-by-lane and is orders of magnitude slower than compiled
   XLA, so it is reserved for explicit kernel-correctness tests.

To guarantee the oracle can never drift from the production pure-JAX wire,
these functions are thin compositions of the :mod:`repro.core.quantizer`
primitives (``binarize_prob``, ``_pack_bool_lastdim``) and
``jax.lax.population_count`` rather than re-implementations. Imports are deferred to call time to keep
``repro.kernels`` importable without ``repro.core`` (and vice versa).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def stoch_quant_compress_ref(
    delta: jax.Array,
    b: jax.Array,
    uniforms: jax.Array,
    residual: jax.Array | None = None,
    *,
    want_residual: bool = False,
) -> tuple[jax.Array, jax.Array | None]:
    """Fused EF-add + Eq.-5 binarize + LSB-first 8:1 bit pack.

    Args:
      delta: (N,) float — model difference (N divisible by 8).
      b: (N,) float — public quantization range (>= 0).
      uniforms: (N,) float32 in [0, 1).
      residual: optional (N,) float error-feedback carry, added to delta
        before binarization (eff = delta + residual).
      want_residual: also return the next EF carry ``eff - c * b``.
    Returns:
      ((N // 8,) uint8 packed codes, (N,) f32 residual or None);
      bit=1 encodes c=+1.
    """
    from ..core.quantizer import _pack_bool_lastdim, binarize_prob

    eff = delta.astype(jnp.float32)
    if residual is not None:
        eff = eff + residual.astype(jnp.float32)
    b = jnp.broadcast_to(b, eff.shape).astype(jnp.float32)
    bits = uniforms < binarize_prob(eff, b)
    packed = _pack_bool_lastdim(bits)
    if not want_residual:
        return packed, None
    return packed, eff - jnp.where(bits, b, -b)


def stoch_quant_pack_ref(
    delta: jax.Array, b: jax.Array, uniforms: jax.Array
) -> jax.Array:
    """Eq.-5 binarize + pack without error feedback (kept for kernel tests)."""
    packed, _ = stoch_quant_compress_ref(delta, b, uniforms)
    return packed


def bit_aggregate_ref(packed: jax.Array, b: jax.Array) -> jax.Array:
    """Popcount-sum M clients' packed codes, then ML-estimate (Eq. 13).

    The vote count is a per-coordinate *column* sum of the bit matrix, so
    ``population_count`` (which sums a byte's 8 bits, i.e. across 8
    coordinates) applies after an octet bit-transpose: 8 clients' bit-k's
    re-pack into one client-major byte whose popcount counts 8 votes at
    once. Delegates to :func:`repro.core.quantizer.packed_counts`, the
    d-chunked production reduction, so ref and pure-JAX counts are the
    same code path by construction.

    Args:
      packed: (M, N // 8) uint8.
      b: (N,) float32.
    Returns:
      (N,) float32 — theta_hat = (2 N_i - M) / M * b_i.
    """
    from ..core.quantizer import packed_counts

    m = packed.shape[0]
    counts = packed_counts(packed)[: b.shape[0]]
    return (2.0 * counts.astype(jnp.float32) - m) / m * b.astype(jnp.float32)


def kbit_quant_compress_ref(
    delta: jax.Array,
    b: jax.Array,
    uniforms: jax.Array,
    *,
    bits: int,
    residual: jax.Array | None = None,
    want_residual: bool = False,
) -> tuple[jax.Array, jax.Array | None]:
    """k-bit sibling of :func:`stoch_quant_compress_ref` (one client).

    Stochastic-rounds onto the uniform ``2**bits``-level grid in [-b, b]
    and packs the level index as ``bits`` one-bit planes (plane-major,
    each plane the exact one-bit pack) — see
    :func:`repro.core.quantizer.quantize_levels` /
    :func:`repro.core.quantizer.pack_levels`. ``bits=1`` reproduces the
    one-bit ref wire byte-for-byte (level 1 == code +1, plane 0 == the
    sign-bit plane).

    Args:
      delta: (N,) float, N divisible by 8.
      b: (N,) float public range.
      uniforms: (N,) float32 in [0, 1) — the rounding draws.
      residual: optional EF carry added to delta first.
      want_residual: also return ``eff - dequantize(level)``.
    Returns:
      ((bits * N // 8,) uint8 packed planes, (N,) f32 residual or None).
    """
    from ..core.quantizer import dequantize_levels, pack_levels, quantize_levels

    eff = delta.astype(jnp.float32)
    if residual is not None:
        eff = eff + residual.astype(jnp.float32)
    b = jnp.broadcast_to(b, eff.shape).astype(jnp.float32)
    levels = quantize_levels(uniforms, eff, b, bits)
    packed = pack_levels(levels, bits)
    if not want_residual:
        return packed, None
    return packed, eff - dequantize_levels(levels, b, bits)


def kbit_aggregate_ref(packed: jax.Array, b: jax.Array, bits: int) -> jax.Array:
    """Popcount-count each bit plane, then the L-level ML estimate.

    The plane-major wire keeps the octet-transpose popcount reduction
    (:func:`repro.core.quantizer.packed_counts`) valid verbatim: the flat
    count of an ``(M, bits * P)`` wire *is* the per-plane vote count laid
    out plane-major, and ``sum_p 2**p N_p`` is the level-histogram mean
    the estimate needs.

    Args:
      packed: (M, bits * P) uint8, P = N // 8.
      b: (N,) float32.
    Returns:
      (N,) float32 — :func:`repro.core.aggregation.kbit_estimate_from_counts`.
    """
    from ..core.aggregation import kbit_estimate_from_counts
    from ..core.quantizer import packed_counts

    m = packed.shape[0]
    n = b.shape[0]
    flat = packed_counts(packed)
    plane_counts = flat.reshape(bits, -1)[:, :n]
    return kbit_estimate_from_counts(plane_counts, m, b, bits)


def prox_sgd_ref(
    w: jax.Array,
    w0: jax.Array,
    grad: jax.Array,
    momentum: jax.Array,
    eta: float,
    lam: float,
    mu: float,
) -> tuple[jax.Array, jax.Array]:
    """Fused prox-regularized SGD+momentum step (paper Eq. 4 local solver).

    g_total = grad + lam * (w - w0)
    momentum' = mu * momentum + g_total
    w' = w - eta * momentum'
    """
    g = grad + lam * (w - w0)
    new_m = mu * momentum + g
    return w - eta * new_m, new_m
