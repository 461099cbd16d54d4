"""Jit'd public wrappers around the Pallas kernels, with engine dispatch.

These accept flat (N,) vectors of arbitrary length, handle padding to the
(rows, 1024) tile layout, and dispatch to one of three engines:

  * ``"pallas"``    — the compiled Mosaic kernels. Requires a backend with
    a Pallas compiler (TPU); this is the deployment target.
  * ``"ref"``       — the pure-JAX reference wire (:mod:`repro.kernels.ref`
    + the :mod:`repro.core.quantizer` primitives), bit-identical to the
    kernels and compiled by stock XLA on any backend.
  * ``"interpret"`` — interpret-mode Pallas: the kernel emulated
    lane-by-lane in Python/XLA. Orders of magnitude slower than either of
    the above; it exists *only* so kernel-correctness tests can validate
    the Pallas lowering on CPU, and is never auto-selected.

:func:`resolve_engine` implements the policy: an explicit ``engine=`` wins;
otherwise TPU resolves to ``"pallas"`` and every other backend to
``"ref"``. (A previous revision auto-selected interpret mode on CPU, which
put the emulator in the hot path and made ``use_kernels=True`` ~115x
slower than the pure-JAX wire — see ``benchmarks/kernels_micro.py``, whose
smoke mode now guards this exact regression.)

Randomness: the quantizer uniforms are counter-derived per client via
:func:`repro.core.quantizer.client_uniforms` (chunk ``j`` of the client
draws from ``fold_in(client_key, j)``), the same schedule as
``packed_binarize_batch``. All three engines therefore produce
bit-identical packed wires — dense, chunked-streaming, and kernel paths
are interchangeable per wire, validated exactly in
``tests/test_pipeline.py``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..core.quantizer import (
    PACK_CHUNK,
    client_uniforms,
    packed_binarize_batch,
    packed_counts,
    packed_quantize_batch,
)
from .stoch_quant import LANES, stoch_quant_ef_2d, stoch_quant_pack_2d
from .bit_aggregate import bit_count_2d
from .prox_sgd import prox_sgd_2d
from . import ref

__all__ = [
    "ENGINES",
    "resolve_engine",
    "stoch_quant_pack",
    "stoch_quant_compress",
    "stoch_quant_compress_batch",
    "quant_pack_u",
    "bit_aggregate",
    "prox_sgd",
    "padded_len",
]

ENGINES = ("pallas", "ref", "interpret")


def resolve_engine(engine: str | None = None, backend: str | None = None) -> str:
    """Dispatch policy: explicit ``engine`` wins; else TPU->pallas, *->ref.

    ``interpret`` is only ever returned when explicitly requested — it is a
    test harness for the kernel lowering, not an execution engine.
    """
    if engine is not None:
        if engine not in ENGINES:
            raise ValueError(f"engine must be one of {ENGINES}, got {engine!r}")
        return engine
    backend = backend or jax.default_backend()
    return "pallas" if backend == "tpu" else "ref"


def _engine_arg(engine: str | None, interpret: bool | None) -> str:
    """Back-compat shim: ``interpret=True`` means engine="interpret"."""
    if interpret is not None:
        if engine is not None:
            raise ValueError("pass either engine= or interpret=, not both")
        engine = "interpret" if interpret else "pallas"
    return resolve_engine(engine)


def padded_len(n: int) -> int:
    return ((n + LANES - 1) // LANES) * LANES


def _pad_to_rows(x: jax.Array, fill: float) -> jax.Array:
    n = x.shape[0]
    p = padded_len(n)
    x = jnp.pad(x.astype(jnp.float32), (0, p - n), constant_values=fill)
    return x.reshape(-1, LANES)


@functools.partial(
    jax.jit, static_argnames=("chunk", "want_residual", "engine", "interpret")
)
def stoch_quant_compress(
    key: jax.Array,
    delta: jax.Array,
    b: jax.Array,
    residual: jax.Array | None = None,
    *,
    chunk: int = PACK_CHUNK,
    want_residual: bool = False,
    engine: str | None = None,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array | None]:
    """Fused EF-add + Eq.-5 binarize + bit-pack for one client.

    ``key`` is the *client* key (already ``fold_in(round_key, row)``-ed by
    the caller); uniforms follow the counter-derived ``client_uniforms``
    schedule at ``chunk``, so the emitted wire prefix is bit-identical to
    ``packed_binarize_batch(..., chunk=chunk)``'s for the same client.

    Args:
      delta: (N,) f32 model difference.
      b: scalar or (N,) public range.
      residual: optional (N,) EF carry added to delta before quantizing.
      want_residual: also return the next carry ``eff - c * b``.
    Returns:
      (packed (padded_len(N)/8,) uint8, residual (N,) f32 or None). Pad
      coordinates beyond N get delta=-1, b=1 (deterministic 0 bits), the
      same convention as the pure wire's ``_pad_batch``.
    """
    engine = _engine_arg(engine, interpret)
    n = delta.shape[0]
    b_full = jnp.broadcast_to(b, (n,)).astype(jnp.float32)
    u = client_uniforms(key, n, chunk)
    if engine == "ref":
        pad = padded_len(n) - n
        d_p = jnp.pad(delta.astype(jnp.float32), (0, pad), constant_values=-1.0)
        b_p = jnp.pad(b_full, (0, pad), constant_values=1.0)
        u_p = jnp.pad(u, (0, pad), constant_values=1.0)
        r_p = None
        if residual is not None:
            r_p = jnp.pad(residual.astype(jnp.float32), (0, pad))
        packed, res = ref.stoch_quant_compress_ref(
            d_p, b_p, u_p, r_p, want_residual=want_residual
        )
        return packed, None if res is None else res[:n]
    itp = engine == "interpret"
    d2 = _pad_to_rows(delta, -1.0)
    b2 = _pad_to_rows(b_full, 1.0)
    u2 = _pad_to_rows(u, 1.0)
    if residual is None and not want_residual:
        packed = stoch_quant_pack_2d(d2, b2, u2, interpret=itp)
        return packed.reshape(-1), None
    r2 = (
        _pad_to_rows(residual, 0.0)
        if residual is not None
        else jnp.zeros_like(d2)
    )
    packed, res = stoch_quant_ef_2d(d2, r2, b2, u2, interpret=itp)
    if not want_residual:
        return packed.reshape(-1), None
    return packed.reshape(-1), res.reshape(-1)[:n]


@functools.partial(jax.jit, static_argnames=("chunk", "engine", "interpret"))
def stoch_quant_pack(
    key: jax.Array,
    delta: jax.Array,
    b: jax.Array,
    *,
    chunk: int = PACK_CHUNK,
    engine: str | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """Flat (N,) delta/b -> packed (padded_len(N)/8,) uint8 codes."""
    packed, _ = stoch_quant_compress(
        key, delta, b, chunk=chunk, engine=_engine_arg(engine, interpret)
    )
    return packed


@functools.partial(
    jax.jit,
    static_argnames=("chunk", "want_residual", "engine", "interpret", "bits"),
)
def stoch_quant_compress_batch(
    key: jax.Array,
    deltas: jax.Array,
    b: jax.Array,
    *,
    row_offset: jax.Array | int = 0,
    chunk: int = PACK_CHUNK,
    want_residual: bool = False,
    engine: str | None = None,
    interpret: bool | None = None,
    bits: int = 1,
    gamma: jax.Array | None = None,
) -> tuple[jax.Array, jax.Array | None]:
    """Batch compress of an (M, d) cohort to the kernel-aligned wire.

    Client ``i`` draws from ``fold_in(key, row_offset + i)`` with the
    ``client_uniforms`` chunk schedule — the ``packed_binarize_batch``
    convention, so the wire is bit-identical across engines *and* across
    client-chunked streaming splits (``row_offset`` rebases the cohort
    position).

    The ref engine *is* ``packed_binarize_batch`` (the chunked pure-JAX
    packer — cache-blocked, the fast path on CPU), realigned losslessly to
    the kernel wire width ``padded_len(d)/8`` (both pads are deterministic
    0 bits); pallas/interpret vmap the fused kernel over clients.

    ``bits > 1`` emits the plane-major k-bit wire
    (:func:`repro.core.quantizer.packed_quantize_batch`, optionally
    randomized-response-mixed via ``gamma``), each plane realigned to the
    kernel width — (M, bits * padded_len(d)/8). There is no Mosaic k-bit
    kernel yet, so every backend routes k > 1 through the ref engine
    (interpret mode, being strictly a lowering test for the one-bit
    kernel, rejects it).

    Returns (packed (M, bits * padded_len(d)/8) uint8, residuals (M, d)
    or None).
    """
    engine = _engine_arg(engine, interpret)
    m, d = deltas.shape
    target = padded_len(d) // 8
    if bits > 1:
        if engine == "interpret":
            raise NotImplementedError(
                "bits > 1 has no Pallas lowering; interpret mode only "
                "emulates existing kernels (use engine='ref')"
            )
        packed, res = packed_quantize_batch(
            key, deltas, b, bits=bits, chunk=chunk,
            want_residual=want_residual, row_offset=row_offset, gamma=gamma,
        )
        src = packed.shape[1] // bits
        planes = packed.reshape(m, bits, src)
        if src > target:
            planes = planes[:, :, :target]
        elif src < target:
            planes = jnp.pad(planes, ((0, 0), (0, 0), (0, target - src)))
        return planes.reshape(m, bits * target), res
    if engine == "ref":
        packed, res = packed_binarize_batch(
            key, deltas, b, chunk=chunk, want_residual=want_residual,
            row_offset=row_offset,
        )
        if packed.shape[1] > target:
            packed = packed[:, :target]
        elif packed.shape[1] < target:
            packed = jnp.pad(packed, ((0, 0), (0, target - packed.shape[1])))
        return packed, res
    client_keys = jax.vmap(lambda i: jax.random.fold_in(key, i))(
        row_offset + jnp.arange(m)
    )
    return jax.vmap(
        lambda ck, row: stoch_quant_compress(
            ck, row, b, chunk=chunk, want_residual=want_residual, engine=engine
        )
    )(client_keys, deltas)


@functools.partial(jax.jit, static_argnames=("engine", "interpret"))
def quant_pack_u(
    delta: jax.Array,
    b: jax.Array,
    uniforms: jax.Array,
    *,
    engine: str | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """Explicit-uniforms Eq.-5 binarize + pack (the top-k gathered values).

    Unlike :func:`stoch_quant_compress` this draws nothing itself — the
    caller supplies the uniforms (e.g. ``uniform(client_key, (k,))``, the
    sparse path's schedule). (K,) float arrays -> (padded_len(K)/8,) uint8;
    pad coordinates get deterministic 0 bits, so slicing the first
    ``ceil(K/8)`` bytes reproduces ``pack_bits``'s output exactly.
    """
    engine = _engine_arg(engine, interpret)
    k = delta.shape[0]
    pad = padded_len(k) - k
    d_p = jnp.pad(delta.astype(jnp.float32), (0, pad), constant_values=-1.0)
    b_p = jnp.pad(
        jnp.broadcast_to(b, (k,)).astype(jnp.float32), (0, pad),
        constant_values=1.0,
    )
    u_p = jnp.pad(uniforms, (0, pad), constant_values=1.0)
    if engine == "ref":
        packed, _ = ref.stoch_quant_compress_ref(d_p, b_p, u_p)
        return packed
    packed = stoch_quant_pack_2d(
        d_p.reshape(-1, LANES),
        b_p.reshape(-1, LANES),
        u_p.reshape(-1, LANES),
        interpret=engine == "interpret",
    )
    return packed.reshape(-1)


@functools.partial(jax.jit, static_argnames=("n", "engine", "interpret"))
def bit_aggregate(
    packed: jax.Array,
    b: jax.Array,
    n: int,
    *,
    engine: str | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """packed (M, P) uint8 (P = padded_len(n)/8), b (n,) -> theta_hat (n,).

    Every engine counts the same integers as
    ``repro.core.quantizer.packed_counts`` (the ref engine is that
    popcount reduction; the kernel sums bit planes), slices the pad
    columns away so tail lanes can never leak, and applies the Eq.-13
    expression of ``ml_estimate_from_counts`` to the same (n,) operands.
    """
    engine = _engine_arg(engine, interpret)
    m = packed.shape[0]
    b_full = jnp.broadcast_to(b, (n,)).astype(jnp.float32)
    if engine == "ref":
        counts = packed_counts(packed)
    else:
        counts = bit_count_2d(packed, interpret=engine == "interpret")
    counts = counts.reshape(-1)[:n]
    return (2.0 * counts.astype(jnp.float32) - m) / m * b_full


@functools.partial(jax.jit, static_argnames=("engine", "interpret"))
def prox_sgd(
    w: jax.Array,
    w0: jax.Array,
    grad: jax.Array,
    momentum: jax.Array,
    eta: jax.Array,
    lam: jax.Array,
    mu: jax.Array,
    *,
    engine: str | None = None,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Flat (N,) fused prox-SGD step; returns (w_new, momentum_new)."""
    engine = _engine_arg(engine, interpret)
    if engine == "ref":
        return ref.prox_sgd_ref(w, w0, grad, momentum, eta, lam, mu)
    n = w.shape[0]
    args = [_pad_to_rows(x, 0.0) for x in (w, w0, grad, momentum)]
    elm = jnp.stack(
        [jnp.asarray(eta, jnp.float32), jnp.asarray(lam, jnp.float32),
         jnp.asarray(mu, jnp.float32)]
    )
    w2, m2 = prox_sgd_2d(*args, elm, interpret=engine == "interpret")
    return w2.reshape(-1)[:n], m2.reshape(-1)[:n]
