"""Aggregation pipeline: client compressors, server aggregators, registry.

Architecture — the packed-wire contract
=======================================

Every aggregation path in this repo (CPU simulation in ``fl/runtime.py``,
the Pallas kernels in ``kernels/``, the sharded mesh step in
``launch/fl_step.py``, and the microbenchmarks) speaks one protocol,
split into two halves joined by an explicit wire format:

``ClientCompressor``
    error feedback -> top-k selection -> stochastic binarize (Eq. 5) ->
    uint8 bit-pack. Emits one of three wire formats:

    * :class:`PackedWire` — the **canonical** format: an
      ``(M, bits * d_pad/8)`` uint8 matrix of LSB-first packed codes plus
      the public range vector ``b`` (d,) and the static per-value width
      ``bits`` (``wire_bits`` in {1, 2, 4}; 1 is the paper's wire,
      bit-exact with pre-k-bit history). ``bits`` bits/parameter on the
      wire — the paper's 32x upload saving vs f32 at k=1, realized in
      memory traffic too because both producer and consumer work in
      d-chunks (:func:`repro.core.quantizer.packed_binarize_batch` /
      :func:`repro.core.quantizer.packed_quantize_batch` /
      :func:`repro.core.quantizer.packed_counts`) and the dense (M, d)
      code tensor never materializes. k > 1 levels travel as ``bits``
      one-bit planes concatenated plane-major along the byte axis, so the
      count protocol below consumes them unchanged.
    * :class:`HeteroWire` — HeteroSAg-style per-client bit-widths: the
      cohort is partitioned into contiguous groups of equal ``bits``,
      each group an independent :class:`PackedWire`; the server
      aggregates per group and MLE-merges with inverse-variance weights
      ``M_g * (2**k_g - 1)**2``.
    * :class:`SparseWire` — top-k variant: per-client index sets plus
      packed codes (beyond-paper extension, see ``core/sparse.py``).
    * :class:`DenseWire` — full-precision passthrough for the FedAvg /
      Fed-GM baselines.

``ServerAggregator``
    unpack / vote-count -> estimate. For bit-based schemes the shared hot
    path is the chunked vote count ``N_i``; the per-scheme estimate is a
    pure function of ``(counts, M, b)``:

    * PRoBit+  : ``(2 N_i - M)/M * b_i``            (ML estimate, Eq. 13)
    * signSGD-MV: ``step * sign(2 N_i - M)``        [Bernstein et al. 2019]
    * RSA      : ``step * (2 N_i - M)``             [Li et al. 2019]

    FedAvg / Fed-GM consume :class:`DenseWire` directly.

    At k > 1 the count carry of a ``bits * d_pad/8``-byte wire row is the
    flattened **per-plane** vote count — the sufficient statistic of the
    (L, d) per-level histogram's mean (``sum_l l N_l = sum_p 2^p
    N_plane_p``) — and PRoBit+'s finalize becomes the L-level multinomial
    ML estimate :func:`kbit_estimate_from_counts`, which reduces to Eq. 13
    at k = 1 (the k = 1 path keeps the literal Eq. 13 code, bit-exact).

An :class:`AggregatorPipeline` bundles one compressor with one server
aggregator; :func:`build_pipeline` resolves a registered name
("probit_plus" | "fedavg" | "fed_gm" | "signsgd_mv" | "rsa") into a
configured pipeline. ``use_kernels=True`` swaps PRoBit+'s two halves for
the fused Pallas kernels (``kernels/stoch_quant.py`` client-side,
``kernels/bit_aggregate.py`` server-side; interpret mode on CPU) — same
wire, same estimate, different engine.

The standalone functions below (``probit_plus_aggregate`` etc.) remain
the mathematical reference implementations the pipelines and tests are
validated against.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Union

import jax
import jax.numpy as jnp

from .privacy import DPConfig, rr_gamma
from .quantizer import (
    PACK_CHUNK,
    WIRE_BITS,
    codes_to_counts,
    packed_binarize_batch,
    packed_counts,
    packed_quantize_batch,
    packed_sign_batch,
    packed_weighted_counts,
    padded_dim,
    stochastic_binarize,
    binarize_prob,
)
from .quantizer import wire_bytes as _wire_row_bytes

__all__ = [
    "ml_estimate_from_counts",
    "kbit_estimate_from_counts",
    "hetero_client_groups",
    "staleness_weights",
    "probit_plus_aggregate",
    "probit_plus_from_updates",
    "fedavg_aggregate",
    "geometric_median",
    "signsgd_mv_aggregate",
    "rsa_aggregate",
    "PackedWire",
    "HeteroWire",
    "SparseWire",
    "DenseWire",
    "ClientCompressor",
    "ServerAggregator",
    "AggregatorPipeline",
    "build_pipeline",
    "available_aggregators",
]


# ---------------------------------------------------------------------------
# PRoBit+ reference math
# ---------------------------------------------------------------------------

def ml_estimate_from_counts(counts: jax.Array, m: int, b: jax.Array) -> jax.Array:
    """Eq. 13: ``theta_hat_i = (2 N_i - M)/M * b_i``.

    This is the exact ML estimate of the mean parameter under the two-point
    likelihood (Eq. 12); it equals ``mean_m(c_i^m) * b_i``.
    """
    return (2.0 * counts.astype(jnp.float32) - m) / m * b


def kbit_estimate_from_counts(
    counts: jax.Array,
    m,
    b: jax.Array,
    bits: int,
    gamma: jax.Array | None = None,
) -> jax.Array:
    """Eq. 13 generalized to the L-level multinomial, from plane counts.

    ``counts`` is the ``(bits, d)`` per-plane vote count (plane ``p``
    counts bit ``p`` of each client's level index); the mean level
    ``sum_p 2^p N_p / M`` is the sufficient statistic the full (L, d)
    per-level histogram contributes to the grid-mean ML estimate::

        theta_hat_i = -b_i + (2 b_i / (L-1)) * mean_level_i

    — the sample mean of the dequantized levels, i.e. the ML estimate of
    the mean parameter constrained to [-b, b] (clipped there, so the
    estimate is always bounded by the public range; at k = 1 the formula
    collapses to ``(2 N - M)/M * b``, Eq. 13 — the k = 1 wire keeps the
    literal :func:`ml_estimate_from_counts` code path for bit-exactness).
    ``gamma`` debiases the randomized-response DP wire: the uniform level
    mix has grid mean 0, so ``E[v] = (1-gamma) * theta`` and the estimate
    rescales by ``1/(1-gamma)`` before clipping. Monotone non-decreasing
    in every count (all plane weights are positive), which the property
    tests assert.
    """
    n_steps = (1 << bits) - 1
    weights = (2.0 ** jnp.arange(bits, dtype=jnp.float32))[:, None]
    mean_level = jnp.sum(weights * counts.astype(jnp.float32), axis=0) / m
    b = jnp.broadcast_to(b, mean_level.shape).astype(jnp.float32)
    theta = -b + (2.0 * b / n_steps) * mean_level
    if gamma is not None:
        theta = theta / jnp.maximum(1.0 - gamma, 1e-6)
    return jnp.clip(theta, -b, b)


def hetero_client_groups(client_bits) -> tuple[tuple[int, int, int], ...]:
    """Run-length encode per-client bit-widths into contiguous groups.

    ``(k_0, k_1, ...)`` (one entry per cohort row) -> ``((start, stop,
    bits), ...)`` — the HeteroSAg-style client groups the compressor
    compresses independently and the server MLE-merges. Non-contiguous
    equal-bits clients simply form more groups (correctness is unchanged;
    sort the cohort by bit-width to minimize group count).
    """
    bits_list = tuple(int(k) for k in client_bits)
    for k in bits_list:
        if k not in WIRE_BITS:
            raise ValueError(
                f"per-client bit-widths must be in {WIRE_BITS}, got {k}"
            )
    groups: list[tuple[int, int, int]] = []
    start = 0
    for i in range(1, len(bits_list) + 1):
        if i == len(bits_list) or bits_list[i] != bits_list[start]:
            groups.append((start, i, bits_list[start]))
            start = i
    return tuple(groups)


def staleness_weights(
    ages: jax.Array, decay: jax.Array, valid: jax.Array | None = None
) -> jax.Array:
    """Polynomial staleness discount ``w(age) = (1 + age) ** (-decay)``.

    The weight an asynchronous server gives a buffered upload that is
    ``age`` rounds old (FedBuff-style; ``decay = 0.5`` is the classical
    ``1/sqrt(1+age)`` discount). Properties the async suite asserts:
    non-negative, monotone non-increasing in ``age`` for ``decay >= 0``,
    and exactly uniform (all ones) at ``decay = 0`` — which is what makes
    the zero-latency async round reduce to the synchronous one. ``valid``
    masks empty buffer slots to weight zero. Weights are normalized by
    their sum inside the weighted estimate, not here.
    """
    w = (1.0 + ages.astype(jnp.float32)) ** (-decay)
    if valid is not None:
        w = jnp.where(valid, w, 0.0)
    return w


def probit_plus_aggregate(codes: jax.Array, b: jax.Array) -> jax.Array:
    """Aggregate client one-bit codes ``(M, d)`` into ``theta_hat (d,)``."""
    m = codes.shape[0]
    return ml_estimate_from_counts(codes_to_counts(codes), m, b)


def probit_plus_from_updates(
    key: jax.Array, updates: jax.Array, b: jax.Array
) -> jax.Array:
    """End-to-end reference path: quantize each client then ML-aggregate."""
    keys = jax.random.split(key, updates.shape[0])
    codes = jax.vmap(stochastic_binarize, in_axes=(0, 0, None))(keys, updates, b)
    return probit_plus_aggregate(codes, b)


# ---------------------------------------------------------------------------
# Full-precision baselines
# ---------------------------------------------------------------------------

def fedavg_aggregate(
    updates: jax.Array, weights: jax.Array | None = None
) -> jax.Array:
    """FedAvg: (weighted) mean of the (M, d) client updates.

    ``weights`` is the staleness weighting of the buffered-async server.
    The weighted mean is computed as ``mean(u * w * (M / sum(w)))`` rather
    than ``sum(u * w) / sum(w)``: with unit weights the rescale is exactly
    1.0 and the call lowers to the *identical* op sequence as the
    unweighted ``jnp.mean`` (whose division XLA folds into a reciprocal
    multiply), which the async zero-latency parity test requires bit for
    bit.
    """
    if weights is None:
        return jnp.mean(updates, axis=0)
    wsum = jnp.sum(weights)
    scale = updates.shape[0] / jnp.maximum(wsum, 1e-12)
    mean = jnp.mean(updates * (weights * scale)[:, None], axis=0)
    return jnp.where(wsum > 0, mean, 0.0)


def geometric_median(
    updates: jax.Array,
    iters: int = 16,
    eps: float = 1e-8,
    weights: jax.Array | None = None,
) -> jax.Array:
    """Fed-GM [Yin et al. 2018]: geometric median via Weiszfeld iterations.

    Smoothed Weiszfeld: weights ``1/max(||u_m - y||, eps)``; ``iters`` fixed
    steps under ``lax.fori_loop`` (convergence is geometric; 16 suffices for
    aggregation noise levels in the paper's regime). Optional ``weights``
    compute the *weighted* geometric median (staleness-discounted async
    buffers): each Weiszfeld weight is scaled by the row weight, so
    zero-weight (empty/evicted) rows drop out of the fixed point.
    """
    y0 = fedavg_aggregate(updates, weights)

    def body(_, y):
        dist = jnp.sqrt(jnp.sum((updates - y) ** 2, axis=-1) + eps)
        w = 1.0 / dist if weights is None else weights / dist
        return jnp.sum(updates * w[:, None], axis=0) / jnp.maximum(
            jnp.sum(w), 1e-12
        )

    return jax.lax.fori_loop(0, iters, body, y0)


# ---------------------------------------------------------------------------
# Bit-based baselines (paper §VI-A)
# ---------------------------------------------------------------------------

def signsgd_mv_aggregate(codes: jax.Array, step: float = 0.01) -> jax.Array:
    """signSGD with Majority Vote [Bernstein et al. 2019].

    Clients upload ``sign(delta)``; the server takes the majority sign and
    applies a hand-tuned step size (paper sets 0.01). The manual step size is
    exactly the instability PRoBit+ removes.
    """
    vote = jnp.sign(jnp.sum(codes.astype(jnp.float32), axis=0))
    return step * vote


def rsa_aggregate(codes: jax.Array, step: float = 0.01) -> jax.Array:
    """RSA [Li et al. 2019] server step: accumulate client signs × step."""
    return step * jnp.sum(codes.astype(jnp.float32), axis=0)


# ---------------------------------------------------------------------------
# Wire formats
# ---------------------------------------------------------------------------

@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class PackedWire:
    """Canonical wire: (M, bits * d_pad/8) uint8 packed codes + range b.

    ``bits = 1`` is the paper's one-bit wire, byte-identical to the
    pre-k-bit format. ``bits > 1`` carries the level index as ``bits``
    one-bit planes concatenated plane-major along the byte axis, each
    plane packed exactly like the one-bit wire (chunk-ordered, byte-major,
    LSB-first) — see :func:`repro.core.quantizer.pack_levels`.
    """

    packed: jax.Array  # (M, bits * P) uint8, P * 8 >= d
    b: jax.Array  # (d,) f32 public quantization range
    d: int = dataclasses.field(metadata=dict(static=True))  # true dimension
    bits: int = dataclasses.field(default=1, metadata=dict(static=True))

    @property
    def n_clients(self) -> int:
        return self.packed.shape[0]

    @property
    def wire_bytes(self) -> int:
        return self.packed.shape[0] * self.packed.shape[1]


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class HeteroWire:
    """HeteroSAg-style heterogeneous wire: per-client bit-widths.

    The cohort is partitioned into contiguous groups of equal bit-width
    (:func:`hetero_client_groups`); each group travels as an independent
    :class:`PackedWire` over the same coordinate range. The server
    aggregates each group with its own L-level ML estimate and merges with
    inverse-variance weights ``M_g * (2**k_g - 1)**2`` (the per-level
    multinomial variance scales as ``step_g**2 / M_g`` and
    ``step_g = 2b/(L_g - 1)``).
    """

    wires: tuple  # tuple[PackedWire, ...], group order = cohort order

    @property
    def n_clients(self) -> int:
        return sum(w.n_clients for w in self.wires)

    @property
    def d(self) -> int:
        return self.wires[0].d

    @property
    def wire_bytes(self) -> int:
        return sum(w.wire_bytes for w in self.wires)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class SparseWire:
    """Top-k wire: per-client indices (M, k) + packed codes (M, ceil(k/8))."""

    indices: jax.Array  # (M, k) int32
    packed: jax.Array  # (M, ceil(k/8)) uint8
    b: jax.Array  # (d,) f32
    d: int = dataclasses.field(metadata=dict(static=True))
    k: int = dataclasses.field(metadata=dict(static=True))


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class DenseWire:
    """Full-precision passthrough (FedAvg / Fed-GM baselines)."""

    updates: jax.Array  # (M, d) f32


Wire = Union[PackedWire, HeteroWire, SparseWire, DenseWire]


# ---------------------------------------------------------------------------
# Client compressor
# ---------------------------------------------------------------------------

def _unpack_rows(packed: jax.Array, n: int) -> jax.Array:
    """(M, P) uint8 -> (M, n) ±1 int8 (test/sparse helper, materializes)."""
    from .quantizer import unpack_bits

    return jax.vmap(lambda p: unpack_bits(p, n))(packed)


@dataclasses.dataclass(frozen=True)
class ClientCompressor:
    """Client half of the pipeline: EF -> top-k -> binarize -> bit-pack.

    ``mode``:
      * "pack_stochastic" — PRoBit+ Eq. 5 compressor, packed wire;
      * "pack_sign"       — deterministic sign codes (signSGD-MV / RSA);
      * "dense"           — identity (full-precision baselines).
    """

    mode: str = "pack_stochastic"
    error_feedback: bool = False
    topk_frac: float = 1.0
    dp: DPConfig = DPConfig(0.0)
    b_mode: str = "dynamic"
    use_kernels: bool = False
    chunk: int = PACK_CHUNK
    # Quantizer draw width: 32 = f32 uniforms (canonical), 16 = uint16
    # draws against a uint32 threshold (half the RNG memory; see
    # quantizer.threshold_u16). Kernel and top-k wires require 32.
    rand_bits: int = 32
    # Wire width k in {1, 2, 4} bits/parameter. 1 is the paper's one-bit
    # wire (bit-exact with pre-k-bit history); k > 1 quantizes onto the
    # uniform 2**k-level grid and, under DP, mixes in L-level randomized
    # response (see privacy.rr_gamma).
    wire_bits: int = 1
    # HeteroSAg-style per-client bit-widths: one entry per cohort row,
    # each in WIRE_BITS. Overrides wire_bits; emits a HeteroWire.
    client_bits: tuple | None = None

    def __post_init__(self):
        if self.rand_bits not in (16, 32):
            raise ValueError(f"rand_bits must be 16 or 32, got {self.rand_bits}")
        if self.rand_bits == 16 and self.use_kernels:
            raise ValueError("rand_bits=16 is not supported on the kernel wire")
        if self.rand_bits == 16 and self.topk_frac < 1.0:
            raise ValueError("rand_bits=16 is not supported on the top-k wire")
        if self.wire_bits not in WIRE_BITS:
            raise ValueError(
                f"wire_bits must be one of {WIRE_BITS}, got {self.wire_bits}"
            )
        if self.wire_bits > 1:
            if self.mode != "pack_stochastic":
                raise ValueError(
                    "wire_bits > 1 requires the pack_stochastic wire "
                    f"(got mode={self.mode!r})"
                )
            if self.topk_frac < 1.0:
                raise ValueError("wire_bits > 1 is not supported on the top-k wire")
            if self.rand_bits != 32:
                raise ValueError("wire_bits > 1 requires rand_bits=32")
        if self.client_bits is not None:
            object.__setattr__(
                self, "client_bits", tuple(int(k) for k in self.client_bits)
            )
            hetero_client_groups(self.client_bits)  # validates each entry
            if self.mode != "pack_stochastic":
                raise ValueError(
                    "per-client bit-widths require the pack_stochastic wire"
                )
            if self.use_kernels:
                raise ValueError(
                    "per-client bit-widths are not supported on the kernel "
                    "wire (compress per-group without use_kernels)"
                )
            if self.topk_frac < 1.0:
                raise ValueError(
                    "per-client bit-widths are not supported on the top-k wire"
                )

    # The Eq.-5 bit probability — shared with the mesh path (fl_step).
    bit_probability = staticmethod(binarize_prob)

    def b_vector(self, d: int, b_scalar: jax.Array) -> jax.Array:
        """The public range vector for dimension ``d`` (non-oracle modes).

        The streaming round needs ``b`` once, outside the client-chunk
        scan, to finalize the accumulated counts; oracle mode maxes over
        the full client axis and therefore cannot stream.
        """
        if self.b_mode == "oracle":
            raise ValueError("oracle b depends on all updates and cannot stream")
        if self.mode == "pack_sign":
            return jnp.ones((d,), jnp.float32)
        return self._b_vector(jnp.zeros((1, d), jnp.float32), b_scalar)

    def wire_bytes(self, d: int) -> int | None:
        """Bytes per packed wire row for dimension ``d`` (None for dense).

        The async round buffer must be allocated before any wire exists;
        this mirrors the padding the compress path will apply (chunked
        pure-JAX padding, or the Pallas kernel's 128-byte lane alignment).
        """
        if self.mode == "dense":
            return None
        # pack_sign always compresses via the chunked packer, so the
        # kernel alignment applies only to the stochastic kernel wire
        if self.use_kernels and self.mode == "pack_stochastic":
            from ..kernels import ops as kops

            return _wire_row_bytes(d, self.wire_bits, d_pad=kops.padded_len(d))
        return _wire_row_bytes(d, self.wire_bits, d_pad=padded_dim(d, self.chunk))

    def _b_vector(self, eff: jax.Array, b_scalar: jax.Array) -> jax.Array:
        d = eff.shape[1]
        # k > 1 earns its (eps, 0) guarantee from randomized-response
        # mixing (privacy.rr_gamma), not the Theorem-3 b-floor margin,
        # so the range stays at the honest b.
        dp = self.dp if self.wire_bits == 1 else DPConfig(0.0)
        if self.b_mode == "oracle":
            from .bcontrol import oracle_b

            return oracle_b(eff, dp)
        b_eff = b_scalar
        if dp.enabled:
            b_eff = b_eff + (1.0 + 1.0 / dp.epsilon) * dp.l1_sensitivity
        return jnp.full((d,), b_eff, jnp.float32)

    def _gamma(self, b_vec: jax.Array) -> jax.Array | None:
        """RR mixing weight of the k-bit DP wire (None when not mixing)."""
        if self.wire_bits > 1 and self.dp.enabled:
            return rr_gamma(
                self.dp.epsilon, self.dp.l1_sensitivity, b_vec, self.wire_bits
            )
        return None

    @jax.named_scope("fl.compress")
    def compress(
        self,
        key: jax.Array,
        deltas: jax.Array,
        b_scalar: jax.Array,
        residuals: jax.Array,
        *,
        row_offset: jax.Array | int = 0,
    ) -> tuple[Wire, jax.Array]:
        """(M, d) updates -> (wire, residuals'). Residuals pass through
        unchanged unless error feedback is active (PRoBit+, no DP).

        ``row_offset`` rebases the per-client quantizer keys: a streaming
        round compressing cohort chunk ``[g0, g0 + C)`` passes ``g0`` so
        row ``i`` draws exactly the bits it would draw at cohort position
        ``g0 + i`` of an all-at-once compress (see
        :func:`~repro.core.quantizer.packed_binarize_batch`).
        """
        if self.mode == "dense":
            return DenseWire(updates=deltas), residuals
        if self.mode == "pack_sign":
            d = deltas.shape[1]
            wire = PackedWire(
                packed=packed_sign_batch(deltas, chunk=self.chunk),
                b=jnp.ones((d,), jnp.float32),
                d=d,
            )
            return wire, residuals

        if self.client_bits is not None:
            # HeteroSAg-style groups: compress each contiguous equal-bits
            # group through a homogeneous sub-compressor, rebasing the
            # counter-derived keys so each row draws the bits of its
            # global cohort position.
            if len(self.client_bits) != deltas.shape[0]:
                raise ValueError(
                    f"client_bits has {len(self.client_bits)} entries for "
                    f"a {deltas.shape[0]}-client cohort"
                )
            wires = []
            res_parts = []
            for start, stop, gbits in hetero_client_groups(self.client_bits):
                sub = dataclasses.replace(
                    self, client_bits=None, wire_bits=gbits
                )
                w, r = sub.compress(
                    key,
                    deltas[start:stop],
                    b_scalar,
                    residuals[start:stop],
                    row_offset=row_offset + start,
                )
                wires.append(w)
                res_parts.append(r)
            return HeteroWire(wires=tuple(wires)), jnp.concatenate(
                res_parts, axis=0
            )

        # PRoBit+ (pack_stochastic)
        m, d = deltas.shape
        use_ef = self.error_feedback and not self.dp.enabled
        eff = deltas + residuals if use_ef else deltas
        b_vec = self._b_vector(eff, b_scalar)

        if self.topk_frac < 1.0:
            from .sparse import topk_binarize
            from .quantizer import pack_bits

            k = max(int(d * self.topk_frac), 1)
            keys = jax.random.split(key, m)
            codes = None
            if self.use_kernels:
                from ..kernels import ops as kops

                # Same key/uniform schedule and top-k gather as
                # topk_binarize; the gathered values binarize + pack
                # through the kernel engine, so the sparse wire is
                # bit-identical to the pure path's vmap(pack_bits)(codes)
                # while the int8 code tensor never materializes.
                def one(ck, row):
                    _, idx = jax.lax.top_k(jnp.abs(row), k)
                    d_sel = jnp.take(row, idx)
                    b_sel = jnp.take(b_vec, idx)
                    u = jax.random.uniform(ck, (k,), dtype=jnp.float32)
                    pk = kops.quant_pack_u(d_sel, b_sel, u)
                    return idx.astype(jnp.int32), pk[: (k + 7) // 8]

                idx, packed_k = jax.vmap(one)(keys, eff)
            else:
                idx, codes = jax.vmap(topk_binarize, in_axes=(0, 0, None, None))(
                    keys, eff, b_vec, k
                )
                packed_k = jax.vmap(pack_bits)(codes)
            if use_ef:
                if codes is None:
                    codes = _unpack_rows(packed_k, k)
                rows = jnp.arange(m)[:, None]
                sent = jnp.zeros_like(eff).at[rows, idx].set(
                    codes.astype(jnp.float32)
                )
                # unreported coordinates carry their full delta forward
                residuals = eff - sent * b_vec
            wire = SparseWire(
                indices=idx,
                packed=packed_k,
                b=b_vec,
                d=d,
                k=k,
            )
            return wire, residuals

        if self.use_kernels:
            from ..kernels import ops as kops

            packed, res = kops.stoch_quant_compress_batch(
                key, eff, b_vec, row_offset=row_offset, chunk=self.chunk,
                want_residual=use_ef, bits=self.wire_bits,
                gamma=self._gamma(b_vec),
            )
            if use_ef:
                residuals = res
            return (
                PackedWire(packed=packed, b=b_vec, d=d, bits=self.wire_bits),
                residuals,
            )

        if self.wire_bits > 1:
            packed, res = packed_quantize_batch(
                key, eff, b_vec, bits=self.wire_bits, chunk=self.chunk,
                want_residual=use_ef, row_offset=row_offset,
                gamma=self._gamma(b_vec),
            )
            if use_ef:
                residuals = res
            return (
                PackedWire(packed=packed, b=b_vec, d=d, bits=self.wire_bits),
                residuals,
            )

        packed, res = packed_binarize_batch(
            key, eff, b_vec, chunk=self.chunk, want_residual=use_ef,
            row_offset=row_offset, rand_bits=self.rand_bits,
        )
        if use_ef:
            residuals = res
        return PackedWire(packed=packed, b=b_vec, d=d), residuals


# ---------------------------------------------------------------------------
# Server aggregators
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ServerAggregator:
    """Server half: count accumulation -> estimate.

    Count accumulation is the **first-class aggregation primitive**: the
    packed path of every bit scheme composes from

    * :meth:`init_counts` — a zero count carry for a ``P``-byte wire row;
    * :meth:`accumulate_counts` — fold one ``(C, P)`` wire chunk (any
      client subset) into the carry. Vote counts are additive over
      clients, so chunks may arrive in any split — a streaming round
      scans client-chunks through this with O(C * P) resident memory;
    * :meth:`finalize` — the per-scheme estimate from ``(counts, M, b)``.

    :meth:`aggregate` is the one-shot composition (single chunk = whole
    cohort), bit-identical to pre-streaming behavior. Bit-based schemes
    override :meth:`from_counts`; dense schemes override
    :meth:`from_dense` and advertise their streaming form via
    ``stream_kind``: ``"counts"`` (PRoBit+ / signSGD-MV / RSA stream
    exactly), ``"sum"`` (FedAvg streams a weighted running sum), or
    ``"buffer"`` (Fed-GM needs all rows resident — parity fallback only,
    not memory-bounded).

    ``weights`` (one per wire row) activates the weighted count path used
    by the buffered-asynchronous server and the fused heterogeneous-M /
    padded-chunk masks: the vote counts become
    ``N_i^w = sum_m w_m 1[c_i^m = +1]`` and the effective cohort size
    ``M^w = sum_m w_m``, both fed to the *same* per-scheme estimate —
    Eq. 13 and the signSGD-MV / RSA rules are all affine in ``(N, M)``, so
    the weighting folds into the counts and the wire format is untouched.
    With unit weights this is value-identical to the unweighted path.
    """

    chunk: int = PACK_CHUNK
    stream_kind = "counts"

    def from_counts(self, counts: jax.Array, m, b: jax.Array) -> jax.Array:
        raise NotImplementedError

    def from_dense(
        self, updates: jax.Array, weights: jax.Array | None = None
    ) -> jax.Array:
        raise NotImplementedError

    # -- streaming count protocol ------------------------------------------

    def init_counts(self, p_bytes: int, *, weighted: bool = False) -> jax.Array:
        """Zero vote-count carry for a ``p_bytes``-per-row packed wire.

        Count-dtype policy: int32 for the exact unweighted count (any
        cohort below 2**31 clients); f32 when per-row weights (staleness /
        active-client masks) fold in — f32 sums of 0/1-weighted bits stay
        exact below 2**24 contributing clients. The uint8 dtype of the
        *wire rows* must never leak into the accumulator: a uint8 count
        silently wraps mod 256 past 255 clients, exactly the large-M
        regime the paper's O(1/M) result targets.
        """
        return jnp.zeros((8 * p_bytes,), jnp.float32 if weighted else jnp.int32)

    @jax.named_scope("fl.count")
    def accumulate_counts(
        self,
        counts: jax.Array,
        wire_chunk: jax.Array,
        weights_chunk: jax.Array | None = None,
    ) -> jax.Array:
        """Fold one packed client-chunk ``(C, P)`` into the count carry."""
        if weights_chunk is None:
            return counts + packed_counts(wire_chunk, chunk=self.chunk)
        return counts + packed_weighted_counts(
            wire_chunk, weights_chunk, chunk=self.chunk
        )

    @jax.named_scope("fl.finalize")
    def finalize(self, counts: jax.Array, m, b: jax.Array) -> jax.Array:
        """Per-scheme estimate from accumulated counts (slices pad bits)."""
        return self.from_counts(counts[: b.shape[0]], m, b)

    # -- streaming dense-sum protocol (FedAvg) -----------------------------

    def init_stream_sum(self, d: int) -> tuple[jax.Array, jax.Array]:
        """Zero ``(sum_m w_m u_m, sum_m w_m)`` carry for dense streaming."""
        return jnp.zeros((d,), jnp.float32), jnp.float32(0.0)

    @jax.named_scope("fl.count")
    def accumulate_sum(self, carry, updates: jax.Array, weights_chunk: jax.Array):
        s, w = carry
        return (
            s + jnp.sum(updates * weights_chunk[:, None], axis=0),
            w + jnp.sum(weights_chunk),
        )

    @jax.named_scope("fl.finalize")
    def finalize_sum(self, carry) -> jax.Array:
        s, w = carry
        return jnp.where(w > 0, s / jnp.maximum(w, 1e-12), 0.0)

    # -- one-shot composition ----------------------------------------------

    def aggregate(
        self, wire: Wire, weights: jax.Array | None = None
    ) -> jax.Array:
        if isinstance(wire, DenseWire):
            return self.from_dense(wire.updates, weights)
        if isinstance(wire, SparseWire):
            raise TypeError(f"{type(self).__name__} cannot consume SparseWire")
        p_bytes = wire.packed.shape[1]
        if weights is None:
            counts = self.accumulate_counts(
                self.init_counts(p_bytes), wire.packed
            )
            return self.finalize(counts, wire.n_clients, wire.b)
        wcounts = self.accumulate_counts(
            self.init_counts(p_bytes, weighted=True), wire.packed, weights
        )
        with jax.named_scope("fl.finalize"):
            wsum = jnp.sum(weights.astype(jnp.float32))
            est = self.finalize(wcounts, jnp.maximum(wsum, 1e-12), wire.b)
            # An all-empty buffer (round 0 under heavy latency) estimates zero.
            return jnp.where(wsum > 0, est, 0.0)


@dataclasses.dataclass(frozen=True)
class ProBitPlusServer(ServerAggregator):
    """Eq. 13 ML estimate; optionally via the fused Pallas count kernel.

    ``wire_bits > 1`` switches :meth:`finalize` to the L-level multinomial
    estimate :func:`kbit_estimate_from_counts` — the count *accumulation*
    is untouched, because the plane-major k-bit wire makes the flat count
    carry exactly the per-plane vote counts. ``dp`` mirrors the
    compressor's config so the server can debias the randomized-response
    mix (same closed-form gamma from the public ``(eps, Delta_1, b, k)``).
    """

    use_kernels: bool = False
    wire_bits: int = 1
    dp: DPConfig = DPConfig(0.0)

    def from_counts(self, counts, m, b):
        return ml_estimate_from_counts(counts, m, b)

    @jax.named_scope("fl.finalize")
    def finalize(self, counts: jax.Array, m, b: jax.Array) -> jax.Array:
        if self.wire_bits == 1:
            return super().finalize(counts, m, b)
        d = b.shape[0]
        plane = counts.shape[0] // self.wire_bits
        plane_counts = counts.reshape(self.wire_bits, plane)[:, :d]
        gamma = None
        if self.dp.enabled:
            gamma = rr_gamma(
                self.dp.epsilon, self.dp.l1_sensitivity, b, self.wire_bits
            )
        return kbit_estimate_from_counts(
            plane_counts, m, b, self.wire_bits, gamma
        )

    def aggregate(self, wire: Wire, weights: jax.Array | None = None) -> jax.Array:
        if isinstance(wire, PackedWire) and wire.bits != self.wire_bits:
            # The wire's static width is authoritative (a pipeline built
            # at k=1 can still consume a k-bit wire and vice versa).
            srv = dataclasses.replace(self, wire_bits=wire.bits)
            return srv.aggregate(wire, weights)
        if isinstance(wire, HeteroWire):
            # Per-group L-level estimates, merged with inverse-variance
            # weights M_g * (2**k_g - 1)**2 (step_g**2 / M_g variance).
            num = jnp.zeros((wire.d,), jnp.float32)
            den = 0.0
            off = 0
            for w in wire.wires:
                srv = dataclasses.replace(
                    self, wire_bits=w.bits, use_kernels=False
                )
                wsel = (
                    None if weights is None else weights[off : off + w.n_clients]
                )
                gw = w.n_clients * ((1 << w.bits) - 1) ** 2
                num = num + gw * srv.aggregate(w, wsel)
                den += gw
                off += w.n_clients
            return num / den
        if isinstance(wire, SparseWire):
            if weights is not None:
                raise TypeError("weighted aggregation needs a dense PackedWire")
            from .sparse import sparse_aggregate

            codes = _unpack_rows(wire.packed, wire.k)
            return sparse_aggregate(wire.indices, codes, wire.b, wire.d)
        if weights is not None:
            # The fused count kernel has no weighted variant; the chunked
            # pure-JAX weighted count consumes the same packed wire.
            return super().aggregate(wire, weights)
        if (
            self.use_kernels
            and isinstance(wire, PackedWire)
            and wire.bits == 1
        ):
            from ..kernels import ops as kops

            # The kernel expects 1024-lane (128-byte) alignment; a wire from
            # the chunked pure-JAX compressor may carry more (or fewer) pad
            # bytes. Pad bits encode coordinates >= d, which bit_aggregate
            # slices off, so realigning is lossless.
            pbytes = kops.padded_len(wire.d) // 8
            packed = wire.packed
            with jax.named_scope("fl.count"):
                if packed.shape[1] > pbytes:
                    packed = packed[:, :pbytes]
                elif packed.shape[1] < pbytes:
                    packed = jnp.pad(
                        packed, ((0, 0), (0, pbytes - packed.shape[1]))
                    )
            return kops.bit_aggregate(packed, wire.b, wire.d)
        return super().aggregate(wire)


@dataclasses.dataclass(frozen=True)
class SignSGDMVServer(ServerAggregator):
    step: float = 0.01

    def from_counts(self, counts, m, b):
        return self.step * jnp.sign(2.0 * counts.astype(jnp.float32) - m)


@dataclasses.dataclass(frozen=True)
class RSAServer(ServerAggregator):
    step: float = 0.01

    def from_counts(self, counts, m, b):
        return self.step * (2.0 * counts.astype(jnp.float32) - m)


@dataclasses.dataclass(frozen=True)
class FedAvgServer(ServerAggregator):
    """Dense mean; streams as a weighted running sum (``stream_kind="sum"``)."""

    stream_kind = "sum"

    def from_dense(self, updates, weights=None):
        return fedavg_aggregate(updates, weights)


@dataclasses.dataclass(frozen=True)
class FedGMServer(ServerAggregator):
    """Weiszfeld geometric median — every iteration touches every row, so
    streaming buffers all rows (``stream_kind="buffer"``; parity fallback
    only, memory stays O(M * d))."""

    iters: int = 16
    stream_kind = "buffer"

    def from_dense(self, updates, weights=None):
        return geometric_median(updates, self.iters, weights=weights)


# ---------------------------------------------------------------------------
# Pipeline + registry
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class AggregatorPipeline:
    """One named aggregation scheme: compressor + server, jit-composable."""

    name: str
    compressor: ClientCompressor
    server: ServerAggregator

    @jax.named_scope("fl.compress")
    def compress_wire(
        self,
        key: jax.Array,
        deltas: jax.Array,
        b_scalar: jax.Array,
        residuals: jax.Array,
        *,
        flip_n: int = 0,
        flip_gate: jax.Array | None = None,
        row_offset: jax.Array | int = 0,
    ) -> tuple[Wire, jax.Array]:
        """Client half only: compress all clients onto the wire.

        ``flip_n > 0`` arms the ``bit_flip`` wire adversary: the first
        ``flip_n`` clients' codes are inverted *after* compression (see
        :func:`repro.core.attacks.flip_wire`). ``flip_gate`` optionally
        gates the flip with a traced boolean, so a vmapped campaign batch
        can mix bit_flip cells with delta-level-attack cells. Residuals are
        the honest compressor's (Byzantine rows lie about those too, which
        is exactly what an adversarial client would do under EF).

        ``row_offset`` identifies the rows as cohort positions
        ``[row_offset, row_offset + M)`` — the streaming round passes its
        chunk start so both the quantizer keys and the first-``flip_n``
        Byzantine membership resolve against global cohort position, not
        chunk-local row index.

        Exposed separately from :meth:`estimate` so the asynchronous round
        can interpose its staleness buffer between compression and the
        server estimate without reformatting the wire.
        """
        static_zero_offset = isinstance(row_offset, int) and row_offset == 0
        wire, residuals = self.compressor.compress(
            key, deltas, b_scalar, residuals, row_offset=row_offset
        )
        if flip_n:
            from .attacks import flip_wire, flip_wire_rows

            if static_zero_offset:
                flipped = flip_wire(wire, flip_n)
            else:
                rows = row_offset + jnp.arange(deltas.shape[0])
                flipped = flip_wire_rows(wire, rows < flip_n)
            if flip_gate is None:
                wire = flipped
            else:
                wire = jax.tree.map(
                    lambda f, w: jnp.where(flip_gate, f, w), flipped, wire
                )
        return wire, residuals

    def estimate(self, wire: Wire, weights: jax.Array | None = None) -> jax.Array:
        """Server half only: estimate theta_hat from a (buffered) wire.

        ``weights`` — one non-negative weight per wire row — selects the
        age-weighted count path (see :class:`ServerAggregator`).
        """
        return self.server.aggregate(wire, weights)

    def __call__(
        self,
        key: jax.Array,
        deltas: jax.Array,
        b_scalar: jax.Array,
        residuals: jax.Array,
        *,
        flip_n: int = 0,
        flip_gate: jax.Array | None = None,
    ) -> tuple[jax.Array, jax.Array]:
        """Full synchronous round: compress, aggregate, return (theta, res')."""
        wire, residuals = self.compress_wire(
            key, deltas, b_scalar, residuals, flip_n=flip_n, flip_gate=flip_gate
        )
        return self.estimate(wire), residuals


_PIPELINES: dict[str, Callable[..., AggregatorPipeline]] = {}


def _register(name: str):
    def deco(builder: Callable[..., AggregatorPipeline]):
        _PIPELINES[name] = builder
        return builder

    return deco


def available_aggregators() -> tuple[str, ...]:
    return tuple(sorted(_PIPELINES))


def build_pipeline(
    name: str,
    *,
    dp: DPConfig = DPConfig(0.0),
    b_mode: str = "dynamic",
    error_feedback: bool = False,
    topk_frac: float = 1.0,
    agg_step: float = 0.01,
    gm_iters: int = 16,
    use_kernels: bool = False,
    chunk: int = PACK_CHUNK,
    rand_bits: int = 32,
    wire_bits: int = 1,
    client_bits: tuple | None = None,
) -> AggregatorPipeline:
    """Resolve a registered aggregator name into a configured pipeline."""
    try:
        builder = _PIPELINES[name]
    except KeyError:
        raise ValueError(
            f"unknown aggregator {name!r}; available: {available_aggregators()}"
        ) from None
    if (wire_bits != 1 or client_bits is not None) and name != "probit_plus":
        raise ValueError(
            "wire_bits > 1 / per-client bit-widths are only supported by "
            f"the probit_plus wire, got {name!r}"
        )
    return builder(
        dp=dp,
        b_mode=b_mode,
        error_feedback=error_feedback,
        topk_frac=topk_frac,
        agg_step=agg_step,
        gm_iters=gm_iters,
        use_kernels=use_kernels,
        chunk=chunk,
        rand_bits=rand_bits,
        wire_bits=wire_bits,
        client_bits=client_bits,
    )


@_register("probit_plus")
def _build_probit_plus(
    *, dp, b_mode, error_feedback, topk_frac, agg_step, gm_iters, use_kernels,
    chunk, rand_bits, wire_bits=1, client_bits=None,
):
    kernel_wire = use_kernels
    return AggregatorPipeline(
        name="probit_plus",
        compressor=ClientCompressor(
            mode="pack_stochastic",
            error_feedback=error_feedback,
            topk_frac=topk_frac,
            dp=dp,
            b_mode=b_mode,
            use_kernels=kernel_wire,
            chunk=chunk,
            rand_bits=rand_bits,
            wire_bits=wire_bits,
            client_bits=client_bits,
        ),
        server=ProBitPlusServer(
            use_kernels=kernel_wire, chunk=chunk, wire_bits=wire_bits, dp=dp
        ),
    )


@_register("fedavg")
def _build_fedavg(*, gm_iters, chunk, **_):
    return AggregatorPipeline(
        name="fedavg",
        compressor=ClientCompressor(mode="dense", chunk=chunk),
        server=FedAvgServer(chunk=chunk),
    )


@_register("fed_gm")
def _build_fed_gm(*, gm_iters, chunk, **_):
    return AggregatorPipeline(
        name="fed_gm",
        compressor=ClientCompressor(mode="dense", chunk=chunk),
        server=FedGMServer(iters=gm_iters, chunk=chunk),
    )


@_register("signsgd_mv")
def _build_signsgd_mv(*, agg_step, chunk, **_):
    return AggregatorPipeline(
        name="signsgd_mv",
        compressor=ClientCompressor(mode="pack_sign", chunk=chunk),
        server=SignSGDMVServer(step=agg_step, chunk=chunk),
    )


@_register("rsa")
def _build_rsa(*, agg_step, chunk, **_):
    return AggregatorPipeline(
        name="rsa",
        compressor=ClientCompressor(mode="pack_sign", chunk=chunk),
        server=RSAServer(step=agg_step, chunk=chunk),
    )
