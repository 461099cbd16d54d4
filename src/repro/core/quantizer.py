"""Stochastic k-bit compressor (paper Eq. 5 and its k-bit extension).

The PRoBit+ client-side compressor maps a model difference ``delta`` and a
public quantization-range vector ``b`` (with ``b_i >= max_m |delta_i^m|``)
to one bit per component::

    c_i = +1  with probability (b_i + delta_i) / (2 b_i)
    c_i = -1  with probability (b_i - delta_i) / (2 b_i)

which is an unbiased one-bit estimate of ``delta_i / b_i``:
``E[c_i] * b_i = delta_i``.

k-bit generalization (``wire_bits`` in {1, 2, 4})
-------------------------------------------------
Eq. 5 is the L = 2 case of stochastic rounding onto the uniform
``L = 2**k``-level grid ``v_l = -b + l * 2b/(L-1)``: a clipped delta
between grid neighbours ``v_l <= delta <= v_{l+1}`` emits level ``l+1``
with probability ``(delta - v_l)/(v_{l+1} - v_l)`` and level ``l``
otherwise — adjacent-level probabilities, still unbiased
(``E[v_level] = delta``), with per-coordinate variance shrinking as
``(2b/(L-1))^2``. Levels travel as ``k`` one-bit *planes* (plane ``p``
carries bit ``p`` of each level index), each packed exactly like the
one-bit wire, concatenated plane-major along the byte axis — so the
packed-wire machinery below (chunked pack, popcount count reduction,
count streaming) consumes a k-bit wire unchanged: the flattened counts of
a ``(M, k * d_pad/8)`` wire *are* the per-plane vote counts, the
sufficient statistic of the (L, d) level histogram's mean. The k=1 wire
is produced by the original one-bit path (:func:`packed_binarize_batch`)
and stays bit-exact with it; k > 1 goes through
:func:`packed_quantize_batch` with the **same** counter-derived
``client_uniforms`` draw schedule. Pad coordinates carry deterministic 0
bits in every plane.

All functions are pure-JAX and shape-polymorphic; the Pallas-accelerated
versions live in :mod:`repro.kernels` and are validated against these.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = [
    "binarize_prob",
    "threshold_u16",
    "stochastic_binarize",
    "pack_bits",
    "unpack_bits",
    "codes_to_counts",
    "PACK_CHUNK",
    "WIRE_BITS",
    "wire_bytes",
    "padded_dim",
    "client_uniforms",
    "level_positions",
    "level_probs",
    "quantize_levels",
    "dequantize_levels",
    "pack_levels",
    "unpack_levels",
    "packed_binarize_batch",
    "packed_quantize_batch",
    "packed_sign_batch",
    "packed_counts",
    "packed_weighted_counts",
    "packed_residuals",
]

# Supported per-value wire widths. 8/k must divide evenly into bytes and
# the (L-1)-level grid must stay addressable in uint8 planes; {1, 2, 4}
# covers the Two-Bit Aggregation and HeteroSAg operating points.
WIRE_BITS = (1, 2, 4)


def wire_bytes(
    d: int, bits: int = 1, *, topk_frac: float = 1.0, d_pad: int | None = None
) -> int:
    """Uplink bytes of ONE client's packed wire row — the single place the
    coordinates x bits -> bytes arithmetic lives.

    Every byte-accounting call site (compressor row width, campaign
    ``peak_bytes_est``, pytree wire report, kernel microbenchmark uplink
    ratios) routes through here so the accounting can never drift from
    the actual wire layout.

    ``d_pad`` is the padded coordinate count the producing wire actually
    emits (``padded_dim(d, chunk)`` for the chunked packer,
    ``kernels.ops.padded_len(d)`` for the kernel wire); ``None`` gives the
    unpadded ``ceil(d/8)`` ideal floor. ``topk_frac < 1`` prices the
    sparse wire: int32 indices + packed codes for ``k = max(d*frac, 1)``
    coordinates.
    """
    if bits not in WIRE_BITS:
        raise ValueError(f"bits must be one of {WIRE_BITS}, got {bits}")
    if topk_frac < 1.0:
        k = max(int(d * topk_frac), 1)
        return 4 * k + bits * ((k + 7) // 8)
    n = d if d_pad is None else d_pad
    return bits * ((n + 7) // 8)


def binarize_prob(delta: jax.Array, b: jax.Array) -> jax.Array:
    """Probability that the compressor emits +1 (Eq. 5), with clipping.

    ``delta`` outside ``[-b, b]`` is clipped so the result is a valid
    probability even when a (Byzantine or mis-calibrated) update exceeds the
    public range — this is precisely the magnitude-immunity mechanism of
    Theorem 2.
    """
    b = jnp.broadcast_to(b, delta.shape).astype(jnp.float32)
    delta = jnp.clip(delta.astype(jnp.float32), -b, b)
    # Guard b == 0 (dead coordinate): probability 1/2 keeps E[c]*b = 0 = delta.
    safe_b = jnp.where(b > 0, b, 1.0)
    p = 0.5 + 0.5 * delta / safe_b
    return jnp.where(b > 0, p, 0.5)


def threshold_u16(p: jax.Array) -> jax.Array:
    """Eq.-5 probability -> 16-bit comparison threshold, in uint32.

    The ``rand_bits=16`` wire compares a uint16 draw against
    ``floor(p * 65536)``: probability granularity 2^-16 (relative bias
    < 1.6e-5) at half the random-draw memory of f32 uniforms. The
    comparison domain is uint32 **on purpose**: ``p = 1.0`` (a coordinate
    with ``|delta| >= b``, i.e. a *certain* +1 vote) maps to 65536, which
    a uint16 cast would wrap to 0 and transmit as a certain -1 — the
    fl_step sign-flip bug this function regression-guards. 65536 exceeds
    every uint16 draw, so saturated votes stay certain.
    """
    return (p.astype(jnp.float32) * 65536.0).astype(jnp.uint32)


def stochastic_binarize(key: jax.Array, delta: jax.Array, b: jax.Array) -> jax.Array:
    """Draw the one-bit codes ``c in {-1, +1}`` (int8) for one client."""
    p = binarize_prob(delta, b)
    u = jax.random.uniform(key, delta.shape, dtype=jnp.float32)
    return jnp.where(u < p, jnp.int8(1), jnp.int8(-1))


def pack_bits(codes: jax.Array) -> jax.Array:
    """Pack ±1 int8 codes into uint8 words, 8 codes/byte (LSB-first).

    The flat length is padded to a multiple of 8 with -1 codes (which unpack
    to 0-bits and are sliced away by :func:`unpack_bits`).
    """
    flat = codes.reshape(-1)
    pad = (-flat.shape[0]) % 8
    flat = jnp.pad(flat, (0, pad), constant_values=-1)
    bits = (flat > 0).astype(jnp.uint8).reshape(-1, 8)
    shifts = jnp.arange(8, dtype=jnp.uint8)
    return jnp.sum(bits << shifts, axis=-1).astype(jnp.uint8)


def unpack_bits(packed: jax.Array, n: int) -> jax.Array:
    """Inverse of :func:`pack_bits`; returns ±1 int8 codes of length ``n``."""
    shifts = jnp.arange(8, dtype=jnp.uint8)
    bits = (packed[:, None] >> shifts) & jnp.uint8(1)
    codes = jnp.where(bits > 0, jnp.int8(1), jnp.int8(-1)).reshape(-1)
    return codes[:n]


def codes_to_counts(codes: jax.Array) -> jax.Array:
    """``N_i`` of Eq. 12: number of +1 codes across the leading client axis."""
    return jnp.sum((codes > 0).astype(jnp.int32), axis=0)


# ---------------------------------------------------------------------------
# Packed wire format: chunked batch quantize / count
#
# The canonical on-the-wire representation of a round is the (M, d_pad/8)
# uint8 matrix of packed one-bit codes. The helpers below produce and
# consume it in d-chunks so the dense (M, d) codes tensor never
# materializes — peak extra memory is O(M * PACK_CHUNK) regardless of d.
# ---------------------------------------------------------------------------

PACK_CHUNK = 8192  # coordinates per chunked-reduction step (multiple of 8)


def padded_dim(d: int, chunk: int = PACK_CHUNK) -> int:
    """Wire dimension: ``d`` rounded up to a whole number of chunks."""
    return ((d + chunk - 1) // chunk) * chunk


def client_uniforms(
    client_key: jax.Array, n: int, chunk: int = PACK_CHUNK
) -> jax.Array:
    """The (n,) quantizer uniforms of one client, counter-derived per chunk.

    Chunk ``j`` draws ``uniform(fold_in(client_key, j), (chunk,))`` — exactly
    the schedule :func:`packed_binarize_batch` uses internally, so any
    compressor (dense, chunked, Pallas kernel) that consumes these uniforms
    with the same ``client_key = fold_in(key, row_offset + m)`` produces a
    bit-identical wire. Materializes the chunks at once (O(padded n)), which
    is fine per-client; the chunked batch path never calls this.
    """
    n_chunks = padded_dim(n, chunk) // chunk
    u = jax.vmap(
        lambda j: jax.random.uniform(
            jax.random.fold_in(client_key, j), (chunk,), dtype=jnp.float32
        )
    )(jnp.arange(n_chunks))
    return u.reshape(-1)[:n]


# ---------------------------------------------------------------------------
# k-bit grid primitives (Eq. 5 generalized to adjacent-level probabilities)
# ---------------------------------------------------------------------------

def level_positions(delta: jax.Array, b: jax.Array, bits: int) -> jax.Array:
    """Continuous grid position ``x in [0, L-1]`` of a clipped delta.

    ``x = (clip(delta, -b, b) + b) / step`` with ``step = 2b/(L-1)``; the
    emitted level is ``floor(x)`` or ``floor(x)+1`` with adjacent-level
    probabilities ``1-frac(x)`` / ``frac(x)``. Dead coordinates
    (``b == 0``) sit at the grid midpoint ``(L-1)/2`` so the dequantized
    mean stays 0 — the k-bit analogue of Eq. 5's ``p = 1/2`` guard.
    """
    levels = (1 << bits) - 1
    b = jnp.broadcast_to(b, delta.shape).astype(jnp.float32)
    delta = jnp.clip(delta.astype(jnp.float32), -b, b)
    safe_step = jnp.where(b > 0, 2.0 * b / levels, 1.0)
    x = (delta + b) / safe_step
    return jnp.where(b > 0, x, 0.5 * levels)


def level_probs(delta: jax.Array, b: jax.Array, bits: int) -> jax.Array:
    """Per-level emission probabilities ``(L,) + delta.shape``.

    The adjacent-level rule is the tent function
    ``q_l = max(0, 1 - |x - l|)`` of the grid position ``x`` — at most two
    nonzero entries per coordinate, summing to 1. Used by the privacy
    module to evaluate the L-level randomized-response likelihood ratio.
    """
    x = level_positions(delta, b, bits)
    lvls = jnp.arange(1 << bits, dtype=jnp.float32)
    lvls = lvls.reshape((-1,) + (1,) * x.ndim)
    return jnp.clip(1.0 - jnp.abs(x[None] - lvls), 0.0, 1.0)


def quantize_levels(
    u: jax.Array, delta: jax.Array, b: jax.Array, bits: int
) -> jax.Array:
    """Stochastic grid rounding: uniforms + deltas -> uint8 level indices.

    ``u`` follows the same counter-derived :func:`client_uniforms`
    schedule as the one-bit wire; level = ``low + 1[u < frac]`` where
    ``low/frac`` split the grid position. Unbiased:
    ``E[dequantize_levels(level)] = clip(delta, -b, b)``.
    """
    levels = (1 << bits) - 1
    x = level_positions(delta, b, bits)
    low = jnp.clip(jnp.floor(x), 0.0, float(levels - 1))
    frac = x - low
    return (low + (u < frac)).astype(jnp.uint8)


def dequantize_levels(levels: jax.Array, b: jax.Array, bits: int) -> jax.Array:
    """Grid value of a level index: ``v_l = -b + l * 2b/(L-1)``."""
    n_steps = (1 << bits) - 1
    b = b.astype(jnp.float32)
    return -b + levels.astype(jnp.float32) * (2.0 * b / n_steps)


def pack_levels(levels: jax.Array, bits: int) -> jax.Array:
    """(..., n) uint8 level indices -> (..., bits * ceil(n/8)) packed planes.

    Bit-plane order: plane ``p`` (bit ``p`` of each level index, LSB
    first) is packed exactly like the one-bit wire and the planes are
    concatenated along the byte axis — plane-major, each plane
    byte-major/LSB-first internally. ``n % 8 != 0`` tails pad each plane
    with 0 bits (level 0), which :func:`unpack_levels` slices away. At
    ``bits=1`` the layout *is* the one-bit wire's.
    """
    if bits not in WIRE_BITS:
        raise ValueError(f"bits must be one of {WIRE_BITS}, got {bits}")
    n = levels.shape[-1]
    pad = (-n) % 8
    levels = jnp.pad(
        levels.astype(jnp.uint8), [(0, 0)] * (levels.ndim - 1) + [(0, pad)]
    )
    planes = [
        _pack_bool_lastdim((levels >> p) & jnp.uint8(1)) for p in range(bits)
    ]
    return jnp.concatenate(planes, axis=-1)


def unpack_levels(packed: jax.Array, n: int, bits: int) -> jax.Array:
    """Inverse of :func:`pack_levels`: packed planes -> (..., n) uint8."""
    plane_bytes = packed.shape[-1] // bits
    shifts = jnp.arange(8, dtype=jnp.uint8)
    out = jnp.zeros(packed.shape[:-1] + (plane_bytes * 8,), jnp.uint8)
    for p in range(bits):
        plane = packed[..., p * plane_bytes : (p + 1) * plane_bytes]
        pbits = (plane[..., None] >> shifts) & jnp.uint8(1)
        out = out | (
            pbits.reshape(packed.shape[:-1] + (plane_bytes * 8,)) << p
        )
    return out[..., :n]


def _pack_bool_lastdim(bits: jax.Array) -> jax.Array:
    """(..., 8k) bool -> (..., k) uint8, LSB-first within each byte."""
    shape = bits.shape[:-1] + (bits.shape[-1] // 8, 8)
    b8 = bits.astype(jnp.uint8).reshape(shape)
    shifts = jnp.arange(8, dtype=jnp.uint8)
    return jnp.sum(b8 << shifts, axis=-1).astype(jnp.uint8)


def _pad_batch(deltas: jax.Array, b: jax.Array, chunk: int):
    """Pad (M, d) deltas / (d,) b to a whole number of chunks.

    Pad coordinates get delta = -1, b = 1 so their bit is deterministically
    0 (p = 0) — the wire is reproducible and pad bits carry no entropy.
    """
    m, d = deltas.shape
    d_pad = padded_dim(d, chunk)
    deltas = jnp.pad(
        deltas.astype(jnp.float32), ((0, 0), (0, d_pad - d)), constant_values=-1.0
    )
    b_full = jnp.pad(
        jnp.broadcast_to(b, (d,)).astype(jnp.float32),
        (0, d_pad - d),
        constant_values=1.0,
    )
    return deltas, b_full, d_pad


def packed_binarize_batch(
    key: jax.Array,
    deltas: jax.Array,
    b: jax.Array,
    *,
    chunk: int = PACK_CHUNK,
    want_residual: bool = False,
    row_offset: jax.Array | int = 0,
    rand_bits: int = 32,
) -> tuple[jax.Array, jax.Array | None]:
    """Chunked Eq. 5 binarize + pack: (M, d) f32 -> (M, d_pad/8) uint8.

    Randomness schedule: coordinate chunk ``j`` of client ``m`` draws its
    uniforms from ``fold_in(fold_in(key, row_offset + m), j)``, so the
    wire is exactly reproducible chunk-by-chunk without an (M, d) uniform
    or code tensor. ``row_offset`` (static or traced) rebases the client
    index: a streaming round that compresses the cohort in client-chunks
    passes the chunk's first cohort position, making the chunked wire
    bit-identical to the all-at-once one (the counter-derived draws of
    ``jax_threefry_partitionable`` depend only on the absolute row).

    With ``want_residual`` the error-feedback residual
    ``delta - c * b`` (codes in ±1) is emitted alongside, computed inside
    the same chunk loop.

    ``rand_bits=16`` swaps the f32 uniform for a uint16 draw compared
    against :func:`threshold_u16` in uint32 (same fold_in schedule, half
    the random-draw memory, probability granularity 2^-16; saturated
    ``|delta| >= b`` coordinates remain *certain* votes). The 16-bit wire
    is a distinct, reproducible bit stream — not bit-identical to the
    f32 one.
    """
    if rand_bits not in (16, 32):
        raise ValueError(f"rand_bits must be 16 or 32, got {rand_bits}")
    m, d = deltas.shape
    deltas_p, b_full, d_pad = _pad_batch(deltas, b, chunk)
    n_chunks = d_pad // chunk
    client_keys = jax.vmap(lambda i: jax.random.fold_in(key, i))(
        row_offset + jnp.arange(m)
    )

    def one_chunk(j):
        dch = jax.lax.dynamic_slice_in_dim(deltas_p, j * chunk, chunk, axis=1)
        bch = jax.lax.dynamic_slice_in_dim(b_full, j * chunk, chunk, axis=0)

        def per_client(ck, drow):
            kj = jax.random.fold_in(ck, j)
            if rand_bits == 16:
                u16 = jax.random.bits(kj, (chunk,), jnp.uint16)
                bits = u16.astype(jnp.uint32) < threshold_u16(
                    binarize_prob(drow, bch)
                )
            else:
                u = jax.random.uniform(kj, (chunk,), dtype=jnp.float32)
                bits = u < binarize_prob(drow, bch)
            packed = _pack_bool_lastdim(bits)
            if want_residual:
                return packed, drow - jnp.where(bits, bch, -bch)
            return packed, jnp.zeros((), jnp.float32)

        return jax.vmap(per_client)(client_keys, dch)

    packed_c, res_c = jax.lax.map(one_chunk, jnp.arange(n_chunks))
    packed = jnp.moveaxis(packed_c, 0, 1).reshape(m, d_pad // 8)
    if want_residual:
        res = jnp.moveaxis(res_c, 0, 1).reshape(m, d_pad)[:, :d]
        return packed, res
    return packed, None


def packed_quantize_batch(
    key: jax.Array,
    deltas: jax.Array,
    b: jax.Array,
    *,
    bits: int,
    chunk: int = PACK_CHUNK,
    want_residual: bool = False,
    row_offset: jax.Array | int = 0,
    gamma: jax.Array | None = None,
) -> tuple[jax.Array, jax.Array | None]:
    """Chunked k-bit quantize + plane-pack: (M, d) f32 -> (M, k*d_pad/8).

    The k > 1 counterpart of :func:`packed_binarize_batch` (which remains
    the one-bit wire, bit-exact with pre-k-bit history): same
    counter-derived schedule — the *rounding* uniform of coordinate chunk
    ``j`` of client ``m`` comes from ``fold_in(fold_in(key, row_offset +
    m), j)``, exactly the :func:`client_uniforms` draws — so dense,
    client-chunked, and kernel-dispatched compressions emit identical
    wires. Output layout: ``bits`` one-bit planes, plane-major over the
    full padded row (plane ``p`` occupies bytes ``[p*d_pad/8,
    (p+1)*d_pad/8)``), each plane internally in the one-bit wire's
    chunk/byte/LSB order.

    ``gamma`` (None, scalar, or per-coordinate ``(d,)``) arms the L-level
    randomized-response mixing that carries the (eps, 0)-DP guarantee at
    k > 1 (see :func:`repro.core.privacy.rr_gamma`): with probability
    ``gamma`` the emitted level is replaced by a uniform one. The RR gate
    and replacement level draw from ``fold_in(kj, 1)`` / ``fold_in(kj,
    2)`` of the chunk key — still counter-derived, so the DP wire too is
    reproducible across chunkings. Pad coordinates get ``gamma = 0`` and
    therefore keep their deterministic 0 bits in every plane.

    With ``want_residual`` the EF residual ``delta - v(level)`` (the
    *emitted* level, RR flips included) is returned alongside.
    """
    if bits not in WIRE_BITS:
        raise ValueError(f"bits must be one of {WIRE_BITS}, got {bits}")
    if bits == 1 and gamma is None:
        return packed_binarize_batch(
            key, deltas, b, chunk=chunk, want_residual=want_residual,
            row_offset=row_offset,
        )
    n_levels = 1 << bits
    m, d = deltas.shape
    deltas_p, b_full, d_pad = _pad_batch(deltas, b, chunk)
    gamma_full = None
    if gamma is not None:
        gamma_full = jnp.pad(
            jnp.broadcast_to(gamma, (d,)).astype(jnp.float32), (0, d_pad - d)
        )
    n_chunks = d_pad // chunk
    client_keys = jax.vmap(lambda i: jax.random.fold_in(key, i))(
        row_offset + jnp.arange(m)
    )

    def one_chunk(j):
        dch = jax.lax.dynamic_slice_in_dim(deltas_p, j * chunk, chunk, axis=1)
        bch = jax.lax.dynamic_slice_in_dim(b_full, j * chunk, chunk, axis=0)
        gch = (
            None
            if gamma_full is None
            else jax.lax.dynamic_slice_in_dim(gamma_full, j * chunk, chunk, 0)
        )

        def per_client(ck, drow):
            kj = jax.random.fold_in(ck, j)
            u = jax.random.uniform(kj, (chunk,), dtype=jnp.float32)
            lvl = quantize_levels(u, drow, bch, bits)
            if gch is not None:
                gate = jax.random.uniform(
                    jax.random.fold_in(kj, 1), (chunk,), dtype=jnp.float32
                )
                rand_lvl = jax.random.randint(
                    jax.random.fold_in(kj, 2), (chunk,), 0, n_levels, jnp.uint8
                )
                lvl = jnp.where(gate < gch, rand_lvl, lvl)
            packed = pack_levels(lvl, bits).reshape(bits, chunk // 8)
            if want_residual:
                return packed, drow - dequantize_levels(lvl, bch, bits)
            return packed, jnp.zeros((), jnp.float32)

        return jax.vmap(per_client)(client_keys, dch)

    packed_c, res_c = jax.lax.map(one_chunk, jnp.arange(n_chunks))
    # (n_chunks, M, bits, chunk/8) -> (M, bits, n_chunks, chunk/8)
    packed = jnp.moveaxis(packed_c, 0, 2).reshape(m, bits * d_pad // 8)
    if want_residual:
        res = jnp.moveaxis(res_c, 0, 1).reshape(m, d_pad)[:, :d]
        return packed, res
    return packed, None


def packed_sign_batch(deltas: jax.Array, *, chunk: int = PACK_CHUNK) -> jax.Array:
    """Deterministic sign codes (signSGD-MV / RSA wire): bit = delta >= 0."""
    deltas_p, _, _ = _pad_batch(deltas, jnp.ones((deltas.shape[1],)), chunk)
    return _pack_bool_lastdim(deltas_p >= 0)


def _popcount_colsums(pch: jax.Array) -> jax.Array:
    """Column bit-sums of a packed chunk via octet transpose + popcount.

    (M, cb) uint8 -> (cb * 8,) int32, column order byte-major / LSB-first
    (bit k of byte j is coordinate ``8 j + k``). Clients are grouped into
    octets of 8; the bit-k's of an octet's bytes are re-packed into one
    byte, whose ``jax.lax.population_count`` counts 8 clients' votes at once —
    the client reduction shortens 8x (M -> M/8 octets) and the widest
    intermediate stays uint8 instead of int32. Zero pad rows (M % 8)
    contribute zero bits, so the counts are exactly the unpack-and-sum
    ones.
    """
    m, cb = pch.shape
    pad = (-m) % 8
    x = jnp.pad(pch, ((0, pad), (0, 0))).reshape(-1, 8, cb)  # (G, 8, cb)
    shifts = jnp.arange(8, dtype=jnp.uint8)
    bit_k = (x[:, :, :, None] >> shifts) & jnp.uint8(1)  # (G, 8, cb, 8)
    octet = jnp.sum(
        bit_k << shifts[None, :, None, None], axis=1, dtype=jnp.uint8
    )  # (G, cb, 8) client-major bytes: bit g of octet[., j, k] = client bit
    counts = jnp.sum(jax.lax.population_count(octet).astype(jnp.int32), axis=0)
    return counts.reshape(cb * 8)


def _chunked_bit_counts(
    packed: jax.Array,
    chunk: int,
    weights: jax.Array | None,
    *,
    use_popcount: bool = True,
) -> jax.Array:
    """Shared chunk walk for the packed-wire count reductions.

    One chunk-layout / pad-handling implementation serves both the integer
    and the weighted count so the two can never diverge; only the
    per-chunk reduction differs. The integer count uses the popcount
    reduction (:func:`_popcount_colsums`) unless ``use_popcount=False``
    selects the unpack-and-sum reference (kept for the microbenchmark and
    as the semantics oracle); the weighted count must unpack (a per-client
    f32 multiply cannot ride a popcount).
    """
    m, pbytes = packed.shape
    cb = min(chunk // 8, pbytes)
    pb_pad = ((pbytes + cb - 1) // cb) * cb
    packed = jnp.pad(packed, ((0, 0), (0, pb_pad - pbytes)))
    shifts = jnp.arange(8, dtype=jnp.uint8)

    def one_chunk(j):
        pch = jax.lax.dynamic_slice_in_dim(packed, j * cb, cb, axis=1)
        if weights is None and use_popcount:
            return _popcount_colsums(pch)
        bits = (pch[..., None] >> shifts) & jnp.uint8(1)  # (M, cb, 8)
        if weights is None:
            acc = bits.astype(jnp.int32)
        else:
            acc = bits.astype(jnp.float32) * weights[:, None, None]
        return jnp.sum(acc, axis=0).reshape(cb * 8)

    counts = jax.lax.map(one_chunk, jnp.arange(pb_pad // cb)).reshape(-1)
    return counts[: 8 * pbytes]


def packed_counts(
    packed: jax.Array, *, chunk: int = PACK_CHUNK, use_popcount: bool = True
) -> jax.Array:
    """Vote counts ``N_i`` straight from the packed wire, chunked over d.

    packed: (M, P) uint8 -> counts (8 * P,) int32. Only O(M * chunk) bits
    are unpacked at a time; the int8 code matrix never materializes.
    ``use_popcount=False`` forces the unpack-and-sum reference reduction
    (identical integer counts; see ``benchmarks/kernels_micro.py`` for the
    measured difference).
    """
    return _chunked_bit_counts(packed, chunk, None, use_popcount=use_popcount)


def packed_weighted_counts(
    packed: jax.Array, weights: jax.Array, *, chunk: int = PACK_CHUNK
) -> jax.Array:
    """Age-weighted vote counts ``N_i^w = sum_m w_m 1[c_i^m = +1]``.

    The buffered-asynchronous server weights each buffered upload by its
    staleness weight *before* the Eq. 13 estimate; the packed uint8 wire is
    consumed unchanged — only the count reduction carries the weights.
    With unit weights the result equals :func:`packed_counts` exactly
    (a float sum of {0, 1} terms is exact below 2**24), which is what makes
    the zero-latency async round bit-exact with the synchronous one.

    packed: (M, P) uint8, weights: (M,) f32 -> counts (8 * P,) f32.
    """
    return _chunked_bit_counts(packed, chunk, weights.astype(jnp.float32))


def packed_residuals(
    packed: jax.Array, deltas: jax.Array, b: jax.Array, *, chunk: int = PACK_CHUNK
) -> jax.Array:
    """Error-feedback residual ``delta - c * b`` recovered from the wire.

    Used when the codes were produced by an external compressor (e.g. the
    Pallas kernel) that does not expose them unpacked; chunked like
    :func:`packed_counts`.
    """
    m, d = deltas.shape
    cb = chunk // 8
    shifts = jnp.arange(8, dtype=jnp.uint8)
    deltas_p, b_full, d_pad = _pad_batch(deltas, b, chunk)
    pbytes = packed.shape[1]
    packed = jnp.pad(packed, ((0, 0), (0, max(d_pad // 8 - pbytes, 0))))

    def one_chunk(j):
        pch = jax.lax.dynamic_slice_in_dim(packed, j * cb, cb, axis=1)
        dch = jax.lax.dynamic_slice_in_dim(deltas_p, j * chunk, chunk, axis=1)
        bch = jax.lax.dynamic_slice_in_dim(b_full, j * chunk, chunk, axis=0)
        bits = ((pch[..., None] >> shifts) & jnp.uint8(1)).reshape(m, cb * 8)
        return dch - jnp.where(bits > 0, bch, -bch)

    res = jax.lax.map(one_chunk, jnp.arange(d_pad // chunk))
    return jnp.moveaxis(res, 0, 1).reshape(m, d_pad)[:, :d]
