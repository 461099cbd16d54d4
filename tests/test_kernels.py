"""Per-kernel validation: Pallas (interpret=True) vs pure-jnp oracle,
swept over shapes and dtypes, plus the dispatch policy itself.

Interpret-mode Pallas appears here *only* — it validates the kernel
lowering on CPU and is never auto-selected (see
``test_dispatch_policy``)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.ops import _pad_to_rows
from repro.kernels.stoch_quant import stoch_quant_ef_2d, stoch_quant_pack_2d
from repro.kernels.bit_aggregate import bit_aggregate_2d

SHAPES = [1024, 2048, 8192, 1000, 4097, 65536]
DTYPES = [jnp.float32, jnp.bfloat16]


# ---------------------------------------------------------------------------
# Dispatch policy
# ---------------------------------------------------------------------------

def test_dispatch_policy():
    """CPU (and anything non-TPU) resolves to the ref engine; interpret is
    never auto-selected but stays reachable explicitly."""
    assert ops.resolve_engine(backend="cpu") == "ref"
    assert ops.resolve_engine(backend="gpu") == "ref"
    assert ops.resolve_engine(backend="tpu") == "pallas"
    assert ops.resolve_engine() in ("ref", "pallas")
    assert ops.resolve_engine() != "interpret"
    for explicit in ops.ENGINES:
        assert ops.resolve_engine(explicit, backend="cpu") == explicit
    with pytest.raises(ValueError):
        ops.resolve_engine("jitted")


def test_interpret_kwarg_is_explicit_interpret():
    """Back-compat: interpret=True selects the interpret engine; passing
    both engine= and interpret= is an error."""
    assert ops._engine_arg(None, True) == "interpret"
    assert ops._engine_arg(None, False) == "pallas"
    assert ops._engine_arg("ref", None) == "ref"
    with pytest.raises(ValueError):
        ops._engine_arg("ref", True)


# ---------------------------------------------------------------------------
# stoch_quant
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_stoch_quant_pack_matches_ref(n, dtype):
    key = jax.random.PRNGKey(n)
    delta = (0.01 * jax.random.normal(key, (n,))).astype(dtype)
    b = jnp.full((n,), 0.05, dtype)
    d2 = _pad_to_rows(delta, 0.0)
    b2 = _pad_to_rows(b, 0.0)
    u2 = jax.random.uniform(key, d2.shape, dtype=jnp.float32)
    got = stoch_quant_pack_2d(d2, b2, u2, interpret=True).reshape(-1)
    want = ref.stoch_quant_pack_ref(d2.reshape(-1), b2.reshape(-1), u2.reshape(-1))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("block_rows", [1, 4, 8])
def test_stoch_quant_block_shape_invariance(block_rows):
    """Output must not depend on the BlockSpec tiling."""
    key = jax.random.PRNGKey(0)
    d2 = _pad_to_rows(0.01 * jax.random.normal(key, (8192,)), 0.0)
    b2 = jnp.full_like(d2, 0.05)
    u2 = jax.random.uniform(key, d2.shape, dtype=jnp.float32)
    base = stoch_quant_pack_2d(d2, b2, u2, block_rows=8, interpret=True)
    other = stoch_quant_pack_2d(d2, b2, u2, block_rows=block_rows, interpret=True)
    np.testing.assert_array_equal(np.asarray(base), np.asarray(other))


@pytest.mark.parametrize("n", [1024, 8192, 4097])
def test_stoch_quant_ef_matches_ref(n):
    """Fused EF kernel (eff-add + binarize + pack + residual) vs oracle."""
    key = jax.random.PRNGKey(n + 1)
    delta = 0.01 * jax.random.normal(key, (n,))
    res = 0.001 * jax.random.normal(jax.random.fold_in(key, 1), (n,))
    b = jnp.full((n,), 0.05)
    d2 = _pad_to_rows(delta, 0.0)
    r2 = _pad_to_rows(res, 0.0)
    b2 = _pad_to_rows(b, 1.0)
    u2 = jax.random.uniform(key, d2.shape, dtype=jnp.float32)
    got_p, got_r = stoch_quant_ef_2d(d2, r2, b2, u2, interpret=True)
    want_p, want_r = ref.stoch_quant_compress_ref(
        d2.reshape(-1), b2.reshape(-1), u2.reshape(-1), r2.reshape(-1),
        want_residual=True,
    )
    np.testing.assert_array_equal(np.asarray(got_p.reshape(-1)), np.asarray(want_p))
    np.testing.assert_array_equal(np.asarray(got_r.reshape(-1)), np.asarray(want_r))


@pytest.mark.parametrize("n", [1000, 4097, 8192])
@pytest.mark.parametrize("want_residual", [False, True])
def test_stoch_quant_compress_engines_agree(n, want_residual):
    """Explicit interpret-mode Pallas == ref engine, bit for bit, for the
    counter-derived-uniforms compress (with and without EF)."""
    key = jax.random.fold_in(jax.random.PRNGKey(9), n)
    delta = 0.01 * jax.random.normal(key, (n,))
    res = 0.001 * jax.random.normal(jax.random.fold_in(key, 1), (n,))
    b = jnp.float32(0.05)
    p_ref, r_ref = ops.stoch_quant_compress(
        key, delta, b, res, want_residual=want_residual, engine="ref"
    )
    p_itp, r_itp = ops.stoch_quant_compress(
        key, delta, b, res, want_residual=want_residual, engine="interpret"
    )
    np.testing.assert_array_equal(np.asarray(p_ref), np.asarray(p_itp))
    if want_residual:
        np.testing.assert_array_equal(np.asarray(r_ref), np.asarray(r_itp))
    else:
        assert r_ref is None and r_itp is None


def test_quant_pack_u_matches_pack_bits():
    """Explicit-uniforms pack (the top-k path) reproduces the pure
    ``pack_bits``-of-codes bytes exactly on both engines."""
    from repro.core.quantizer import binarize_prob, pack_bits

    k = 123
    key = jax.random.PRNGKey(3)
    d_sel = 0.02 * jax.random.normal(key, (k,))
    b_sel = jnp.abs(0.05 * jax.random.normal(jax.random.fold_in(key, 1), (k,)))
    u = jax.random.uniform(jax.random.fold_in(key, 2), (k,), dtype=jnp.float32)
    codes = jnp.where(u < binarize_prob(d_sel, b_sel), jnp.int8(1), jnp.int8(-1))
    want = pack_bits(codes)
    nbytes = (k + 7) // 8
    for engine in ("ref", "interpret"):
        got = ops.quant_pack_u(d_sel, b_sel, u, engine=engine)
        np.testing.assert_array_equal(np.asarray(got[:nbytes]), np.asarray(want))
        assert not np.any(np.asarray(got[nbytes:]))


# ---------------------------------------------------------------------------
# bit_aggregate
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("m", [1, 3, 16, 64])
@pytest.mark.parametrize("n", [1024, 4096, 5000])
def test_bit_aggregate_matches_ref(m, n):
    key = jax.random.PRNGKey(m * 7 + n)
    delta = 0.01 * jax.random.normal(key, (n,))
    b = jnp.full((n,), 0.04)
    packed = jnp.stack(
        [ops.stoch_quant_pack(jax.random.fold_in(key, i), delta, b) for i in range(m)]
    )
    got = ops.bit_aggregate(packed, b, n, engine="interpret")
    b_pad = _pad_to_rows(b, 0.0).reshape(-1)
    want = ref.bit_aggregate_ref(packed, b_pad)[:n]
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("m_block", [8, 16, 256])
def test_bit_aggregate_m_block_invariance(m_block):
    """The client-axis grid accumulation must not depend on the tile size
    (zero-padded rows add zero votes; f32 partial sums are exact)."""
    m, c = 37, 256
    packed = jax.random.randint(
        jax.random.PRNGKey(1), (m, c), 0, 256, dtype=jnp.int32
    ).astype(jnp.uint8)
    b2d = jnp.abs(jax.random.normal(jax.random.PRNGKey(2), (c // 128, 1024)))
    base = bit_aggregate_2d(packed, b2d, m_block=256, interpret=True)
    other = bit_aggregate_2d(packed, b2d, m_block=m_block, interpret=True)
    np.testing.assert_array_equal(np.asarray(base), np.asarray(other))


@pytest.mark.parametrize("engine", ["ref", "interpret"])
def test_bit_aggregate_counts_match_packed_counts(engine):
    """The in-kernel popcount vote count is bit-exact with the production
    ``packed_counts`` reduction: feeding b=1 makes bit_aggregate return
    (2N - M)/M, from which N is recovered exactly."""
    from repro.core.quantizer import packed_counts

    m, n = 21, 2048
    packed = jax.random.randint(
        jax.random.PRNGKey(7), (m, n // 8), 0, 256, dtype=jnp.int32
    ).astype(jnp.uint8)
    ones = jnp.ones((n,), jnp.float32)
    theta = ops.bit_aggregate(packed, ones, n, engine=engine)
    counts = np.round((np.asarray(theta, np.float64) * m + m) / 2.0)
    want = np.asarray(packed_counts(packed)[:n])
    np.testing.assert_array_equal(counts, want.astype(np.float64))


def test_bit_aggregate_equals_core_ml_estimate():
    """Kernel wire + kernel aggregate == core chunked wire + Eq.-13
    estimate, exactly — the engines share the counter-derived uniform
    schedule and the popcount reduction end to end."""
    from repro.core import ml_estimate_from_counts
    from repro.core.quantizer import packed_binarize_batch, packed_counts

    key = jax.random.PRNGKey(5)
    n, m = 3000, 8
    deltas = 0.01 * jax.random.normal(key, (m, n))
    b = jnp.full((n,), 0.03)
    packed_core, _ = packed_binarize_batch(key, deltas, b)
    want = ml_estimate_from_counts(packed_counts(packed_core)[:n], m, b)
    client_keys = [jax.random.fold_in(key, i) for i in range(m)]
    packed_k = jnp.stack(
        [ops.stoch_quant_pack(ck, deltas[i], b) for i, ck in enumerate(client_keys)]
    )
    got = ops.bit_aggregate(packed_k, b, n)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("m", [10, 37])
def test_bit_aggregate_broadcast_b_matches_ref(m):
    """A round builds b as one broadcast value inside its jit, and XLA
    then reassociates Eq. 13 (b folds in before the division by M). The
    kernel engine must hand XLA the same expression as the ref engine, or
    the two round apart in the last bit."""
    n = 5000
    packed = jax.random.randint(
        jax.random.PRNGKey(m), (m, ops.padded_len(n) // 8), 0, 256,
        dtype=jnp.int32,
    ).astype(jnp.uint8)

    def theta(engine):
        return jax.jit(
            lambda p, s: ops.bit_aggregate(
                p, jnp.full((n,), s), n, engine=engine
            )
        )(packed, jnp.float32(0.0104))

    np.testing.assert_array_equal(
        np.asarray(theta("interpret")), np.asarray(theta("ref"))
    )


@pytest.mark.parametrize("engine", ["ref", "interpret"])
def test_bit_aggregate_padded_tail_never_leaks(engine):
    """n % 1024 != 0 and M % 8 != 0: adversarial all-ones pad lanes (both
    the in-byte tail bits and the whole pad bytes) must not perturb
    estimate[:n] on any engine."""
    n, m = 997, 5  # n % 8 != 0 -> the last in-range byte has 3 pad bits
    pbytes = ops.padded_len(n) // 8
    key = jax.random.PRNGKey(11)
    packed = jax.random.randint(
        key, (m, pbytes), 0, 256, dtype=jnp.int32
    ).astype(jnp.uint8)
    b = jnp.abs(jax.random.normal(jax.random.fold_in(key, 1), (n,)))
    base = ops.bit_aggregate(packed, b, n, engine=engine)

    # poison every pad position with 1-bits: whole bytes beyond ceil(n/8)
    # and the high bits of the straddling byte
    poisoned = np.asarray(packed).copy()
    full = n // 8  # bytes fully in range
    in_byte_pad = 8 * (full + 1) - n  # pad bits inside the straddling byte
    poisoned[:, full] |= (0xFF << (8 - in_byte_pad)) & 0xFF
    poisoned[:, full + 1:] = 0xFF
    got = ops.bit_aggregate(jnp.asarray(poisoned), b, n, engine=engine)
    np.testing.assert_array_equal(np.asarray(base), np.asarray(got))


# ---------------------------------------------------------------------------
# prox_sgd
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n", [1024, 4096, 3333])
@pytest.mark.parametrize("dtype", [jnp.float32])
def test_prox_sgd_matches_ref(n, dtype):
    key = jax.random.PRNGKey(n)
    ks = jax.random.split(key, 4)
    w = jax.random.normal(ks[0], (n,), dtype)
    w0 = w * 0.9
    g = jax.random.normal(ks[1], (n,), dtype)
    m = 0.1 * jax.random.normal(ks[2], (n,), dtype)
    got_w, got_m = ops.prox_sgd(w, w0, g, m, 0.01, 0.2, 0.5, engine="interpret")
    want_w, want_m = ref.prox_sgd_ref(w, w0, g, m, 0.01, 0.2, 0.5)
    np.testing.assert_allclose(np.asarray(got_w), np.asarray(want_w), rtol=2e-5, atol=1e-7)
    np.testing.assert_allclose(np.asarray(got_m), np.asarray(want_m), rtol=2e-5, atol=1e-7)
