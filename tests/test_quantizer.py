"""Unit + property tests for the stochastic one-bit compressor (Eq. 5)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.core import quantizer as qz
from repro.core import (
    binarize_prob,
    stochastic_binarize,
    pack_bits,
    unpack_bits,
    codes_to_counts,
)


def test_prob_formula_matches_eq5():
    delta = jnp.array([-0.05, 0.0, 0.05])
    b = jnp.array([0.05, 0.05, 0.05])
    p = binarize_prob(delta, b)
    np.testing.assert_allclose(p, [0.0, 0.5, 1.0], atol=1e-7)


def test_prob_clips_out_of_range():
    # Byzantine magnitudes cannot push the probability outside [0, 1]
    delta = jnp.array([-100.0, 100.0])
    b = jnp.array([0.01, 0.01])
    p = binarize_prob(delta, b)
    np.testing.assert_allclose(p, [0.0, 1.0], atol=1e-7)


def test_zero_b_is_fair_coin():
    p = binarize_prob(jnp.zeros(4), jnp.zeros(4))
    np.testing.assert_allclose(p, 0.5)


@settings(deadline=None, max_examples=25)
@given(
    st.integers(0, 2**31 - 1),
    st.floats(0.001, 0.2),
    st.integers(10, 200),
)
def test_unbiasedness_property(seed, scale, n):
    """E[c] * b == delta (Thm 1.2 at the compressor level)."""
    key = jax.random.PRNGKey(seed)
    delta = scale * jax.random.normal(key, (n,))
    b = jnp.abs(delta).max() + scale
    reps = 4000
    keys = jax.random.split(jax.random.fold_in(key, 1), reps)
    codes = jax.vmap(lambda k: stochastic_binarize(k, delta, jnp.full((n,), b)))(keys)
    est = jnp.mean(codes.astype(jnp.float32), axis=0) * b
    se = float(b) / np.sqrt(reps)
    assert float(jnp.max(jnp.abs(est - delta))) < 6 * se


@settings(deadline=None, max_examples=30)
@given(st.integers(0, 2**31 - 1), st.integers(1, 4000))
def test_pack_unpack_roundtrip(seed, n):
    key = jax.random.PRNGKey(seed)
    codes = jnp.where(
        jax.random.bernoulli(key, 0.5, (n,)), jnp.int8(1), jnp.int8(-1)
    )
    packed = pack_bits(codes)
    assert packed.dtype == jnp.uint8
    assert packed.shape[0] == (n + 7) // 8
    out = unpack_bits(packed, n)
    assert bool(jnp.all(out == codes))


def test_counts():
    codes = jnp.array([[1, -1, 1], [1, 1, -1], [-1, -1, -1]], jnp.int8)
    np.testing.assert_array_equal(codes_to_counts(codes), [2, 1, 1])


# ---------------------------------------------------------------------------
# Blocked chunk walk: many chunks per loop step, the same wire bit for bit
# ---------------------------------------------------------------------------


def _per_chunk_oracle(key, deltas, b, *, chunk, want_residual, row_offset, rand_bits):
    """The one-chunk-per-loop-step walk of ``packed_binarize_batch``."""
    m, d = deltas.shape
    deltas_p, b_full, d_pad = qz._pad_batch(deltas, b, chunk)
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
                bits = u16.astype(jnp.uint32) < qz.threshold_u16(
                    binarize_prob(drow, bch)
                )
            else:
                u = jax.random.uniform(kj, (chunk,), dtype=jnp.float32)
                bits = u < binarize_prob(drow, bch)
            packed = qz._pack_bool_lastdim(bits)
            if want_residual:
                return packed, drow - jnp.where(bits, bch, -bch)
            return packed, jnp.zeros((), jnp.float32)

        return jax.vmap(per_client)(client_keys, dch)

    packed_c, res_c = jax.lax.map(one_chunk, jnp.arange(d_pad // chunk))
    packed = jnp.moveaxis(packed_c, 0, 1).reshape(m, d_pad // 8)
    if want_residual:
        return packed, jnp.moveaxis(res_c, 0, 1).reshape(m, d_pad)[:, :d]
    return packed, None


def _walk_budget(monkeypatch, rows, chunk, k):
    """Set the walk budget so that ``rows`` clients walk ``k`` chunks a step."""
    monkeypatch.setattr(qz, "WALK_BUDGET", rows * chunk * k + chunk - 1)
    assert qz.chunks_per_step(rows, chunk) == k


@pytest.mark.parametrize(
    "m,chunk,n_chunks,k,rand_bits,want_residual",
    [
        (1, 64, 37, 4, 32, True),  # 37 chunks (prime): 9 whole steps + a tail
        (1, 64, 37, 4, 16, False),
        (3, 64, 37, 5, 32, False),
        (3, 64, 23, 5, 16, True),
        (13, 64, 29, 3, 32, True),
        (13, 64, 11, 16, 16, False),  # fewer chunks than one step holds
        (3, 64, 7, 1, 32, True),  # one chunk a step, as for a cohort of 512
        (13, 64, 5, 1, 16, False),
        (1, qz.PACK_CHUNK, 12, 5, 32, True),
        (1, qz.PACK_CHUNK, 12, 5, 16, False),
    ],
)
def test_blocked_walk_matches_per_chunk_oracle(
    monkeypatch, m, chunk, n_chunks, k, rand_bits, want_residual
):
    _walk_budget(monkeypatch, m, chunk, k)
    d = n_chunks * chunk - 5  # a ragged last chunk: pad bits inside the wire
    key = jax.random.PRNGKey(n_chunks * 31 + m)
    deltas = 0.05 * jax.random.normal(jax.random.fold_in(key, 1), (m, d))
    b = jnp.abs(deltas).max(axis=0) * 0.8 + 0.01  # some saturated votes

    def blocked(row_offset):
        return qz.packed_binarize_batch(
            key, deltas, b, chunk=chunk, want_residual=want_residual,
            row_offset=row_offset, rand_bits=rand_bits,
        )

    packed, res = jax.jit(blocked)(jnp.int32(7))  # a traced row offset
    want_p, want_r = _per_chunk_oracle(
        key, deltas, b, chunk=chunk, want_residual=want_residual,
        row_offset=7, rand_bits=rand_bits,
    )
    assert packed.shape == (m, qz.padded_dim(d, chunk) // 8)
    np.testing.assert_array_equal(np.asarray(packed), np.asarray(want_p))
    if want_residual:
        np.testing.assert_array_equal(np.asarray(res), np.asarray(want_r))
    else:
        assert res is None


@pytest.mark.parametrize("m", [1, 3])
def test_blocked_residuals_match_the_compressor(monkeypatch, m):
    chunk = 64
    _walk_budget(monkeypatch, m, chunk, 4)
    d = 23 * chunk - 9
    key = jax.random.PRNGKey(m)
    deltas = 0.05 * jax.random.normal(jax.random.fold_in(key, 1), (m, d))
    b = jnp.abs(deltas).max(axis=0) + 0.01
    packed, res = qz.packed_binarize_batch(key, deltas, b, chunk=chunk, want_residual=True)
    np.testing.assert_array_equal(
        np.asarray(qz.packed_residuals(packed, deltas, b, chunk=chunk)), np.asarray(res)
    )


@pytest.mark.parametrize("bits,gamma", [(2, None), (4, 0.3)])
def test_kbit_walk_does_not_depend_on_the_step(monkeypatch, bits, gamma):
    chunk, m = 64, 3
    d = 17 * chunk - 5
    key = jax.random.PRNGKey(bits)
    deltas = 0.05 * jax.random.normal(jax.random.fold_in(key, 1), (m, d))
    b = jnp.abs(deltas).max(axis=0) + 0.01
    out = {}
    for k in (1, 5):
        _walk_budget(monkeypatch, m, chunk, k)
        out[k] = qz.packed_quantize_batch(
            key, deltas, b, bits=bits, chunk=chunk, want_residual=True,
            row_offset=4, gamma=gamma,
        )
    for a, b_ in zip(out[1], out[5]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b_))


@pytest.mark.parametrize("m", [1, 8, 13])
def test_blocked_counts_match_unpack_and_sum(monkeypatch, m):
    chunk = 64
    # count rows are padded to client octets: round m up to 8
    _walk_budget(monkeypatch, -(-m // 8) * 8, chunk, 3)
    pbytes = 3 * (chunk // 8) * 4 + 5  # not a multiple of the 24-byte step
    rng = np.random.default_rng(m)
    packed = rng.integers(0, 256, (m, pbytes), dtype=np.uint8)
    weights = rng.integers(1, 9, m) / 8.0  # dyadic: every f32 sum is exact
    bits = np.unpackbits(packed, axis=1, bitorder="little")
    want = bits.sum(axis=0)
    for use_popcount in (True, False):
        got = qz.packed_counts(jnp.asarray(packed), chunk=chunk, use_popcount=use_popcount)
        assert got.dtype == jnp.int32
        np.testing.assert_array_equal(np.asarray(got), want)
    got_w = qz.packed_weighted_counts(
        jnp.asarray(packed), jnp.asarray(weights, jnp.float32), chunk=chunk
    )
    np.testing.assert_array_equal(np.asarray(got_w), (weights[:, None] * bits).sum(axis=0))


@pytest.mark.parametrize("rows", [1, 3, 8, 13, 255, 256, 511, 512, 513, 4096])
@pytest.mark.parametrize("chunk", [64, 1024, qz.PACK_CHUNK])
def test_chunks_per_step_rule(rows, chunk):
    budget = qz.WALK_BUDGET
    k = qz.chunks_per_step(rows, chunk)
    assert k >= 1
    if rows * chunk >= budget:
        assert k == 1  # cohort-scale rounds keep one chunk per step
    else:
        assert k * rows * chunk <= budget < (k + 1) * rows * chunk


@pytest.mark.parametrize("n_chunks", [1, 4, 7])
def test_wire_width_does_not_depend_on_the_step(monkeypatch, n_chunks):
    chunk = 64
    _walk_budget(monkeypatch, 2, chunk, 3)
    d = n_chunks * chunk - 3
    deltas = jnp.zeros((2, d))
    packed, res = qz.packed_binarize_batch(
        jax.random.PRNGKey(0), deltas, jnp.ones((d,)), chunk=chunk, want_residual=True
    )
    assert packed.shape == (2, qz.padded_dim(d, chunk) // 8)
    assert res.shape == (2, d)
    counts = qz.packed_counts(packed, chunk=chunk)
    assert counts.shape == (qz.padded_dim(d, chunk),)


def test_one_client_walks_512_chunks_per_step():
    assert qz.WALK_BUDGET == 1 << 22
    assert qz.chunks_per_step(1) == 512
    assert qz.chunks_per_step(8) == 64  # the count pads one client to an octet
    assert qz.chunks_per_step(512) == 1
