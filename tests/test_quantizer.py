"""Unit + property tests for the stochastic one-bit compressor (Eq. 5)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

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
