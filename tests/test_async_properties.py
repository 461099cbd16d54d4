"""Property-based tests for the staleness weighting and the age-weighted
MLE (core/aggregation.py, core/quantizer.py).

Randomized over shapes, ages, decays, and weights (hypothesis when
installed, the deterministic fallback shim otherwise):

* staleness weights are non-negative, bounded by 1, exactly uniform at
  decay 0, monotone non-increasing in age, and normalize to a probability
  vector over valid slots;
* the age-weighted Eq.-13 estimate keeps the amplitude-immunity bound
  |theta_hat_i| <= b_i for arbitrary non-negative weights, including
  packed inputs with d % 8 != 0 (pad-bit handling);
* unit weights reproduce the integer vote counts exactly — the algebraic
  half of the async zero-latency bit-exactness guarantee.
"""

import jax
import jax.numpy as jnp
import numpy as np

from hypothesis import given, settings, strategies as st

from repro.core import (
    build_pipeline,
    ml_estimate_from_counts,
    packed_counts,
    packed_weighted_counts,
    staleness_weights,
)


@settings(deadline=None, max_examples=20)
@given(
    st.integers(0, 2**31 - 1),
    st.integers(1, 64),
    st.floats(0.0, 4.0),
)
def test_staleness_weights_basic_properties(seed, n, decay):
    """Non-negative, <= 1, zero on invalid slots, normalizable."""
    key = jax.random.PRNGKey(seed)
    ages = jax.random.randint(key, (n,), 0, 100)
    valid = jax.random.bernoulli(jax.random.fold_in(key, 1), 0.7, (n,))
    w = np.asarray(staleness_weights(ages, jnp.float32(decay), valid))
    assert np.all(w >= 0.0) and np.all(w <= 1.0)
    assert np.all(w[~np.asarray(valid)] == 0.0)
    if w.sum() > 0:  # normalized weights form a probability vector
        p = w / w.sum()
        np.testing.assert_allclose(p.sum(), 1.0, rtol=1e-6)
        assert np.all(p >= 0.0) and np.all(p <= 1.0)


@settings(deadline=None, max_examples=20)
@given(st.integers(0, 2**31 - 1), st.integers(1, 64), st.floats(0.0, 4.0))
def test_staleness_weights_monotone_in_age(seed, n, decay):
    """Aging any upload by one round never raises its weight."""
    ages = jax.random.randint(jax.random.PRNGKey(seed), (n,), 0, 100)
    d = jnp.float32(decay)
    w_now = np.asarray(staleness_weights(ages, d))
    w_older = np.asarray(staleness_weights(ages + 1, d))
    assert np.all(w_older <= w_now + 1e-7)


@settings(deadline=None, max_examples=20)
@given(st.integers(0, 2**31 - 1), st.integers(1, 64))
def test_staleness_weights_uniform_at_zero_decay(seed, n):
    """decay = 0 reduces to exactly uniform (all-ones) weighting — the
    degenerate case the bit-exact sync parity rides on."""
    ages = jax.random.randint(jax.random.PRNGKey(seed), (n,), 0, 100)
    w = np.asarray(staleness_weights(ages, jnp.float32(0.0)))
    np.testing.assert_array_equal(w, np.ones(int(n), np.float32))


@settings(deadline=None, max_examples=10)
@given(
    st.integers(0, 2**31 - 1),
    st.integers(1, 12),
    st.sampled_from([1, 3, 8, 13, 64, 131, 256]),
)
def test_weighted_mle_bounded_by_b(seed, m, d):
    """|theta_hat_i| <= b_i for any non-negative staleness weights on any
    packed wire — d values deliberately include non-multiples of 8, so
    pad bits run through the weighted count path too."""
    key = jax.random.PRNGKey(seed)
    deltas = 0.05 * jax.random.normal(key, (m, d))
    b = jnp.float32(0.05)
    pipe = build_pipeline("probit_plus", chunk=64)
    wire, _ = pipe.compressor.compress(key, deltas, b, jnp.zeros((m, d)))
    ages = jax.random.randint(jax.random.fold_in(key, 1), (m,), 0, 20)
    decay = jax.random.uniform(jax.random.fold_in(key, 2), (), minval=0.0, maxval=3.0)
    valid = jax.random.bernoulli(jax.random.fold_in(key, 3), 0.8, (m,))
    w = staleness_weights(ages, decay, valid)
    theta = np.asarray(pipe.estimate(wire, weights=w))
    assert theta.shape == (d,)
    assert np.all(np.isfinite(theta))
    assert np.all(np.abs(theta) <= np.asarray(wire.b) * (1 + 1e-6))


@settings(deadline=None, max_examples=10)
@given(
    st.integers(0, 2**31 - 1),
    st.integers(1, 12),
    st.sampled_from([1, 3, 8, 13, 64, 131]),
)
def test_unit_weights_reproduce_integer_counts(seed, m, d):
    """sum_m(1.0 * bit) == popcount: the weighted count at unit weights is
    exactly the integer vote count, and the weighted estimate equals the
    unweighted pipeline estimate bit for bit."""
    key = jax.random.PRNGKey(seed)
    deltas = 0.02 * jax.random.normal(key, (m, d))
    b = jnp.float32(0.05)
    pipe = build_pipeline("probit_plus", chunk=64)
    wire, _ = pipe.compressor.compress(key, deltas, b, jnp.zeros((m, d)))
    wcounts = np.asarray(
        packed_weighted_counts(wire.packed, jnp.ones((m,)), chunk=64)
    )
    counts = np.asarray(packed_counts(wire.packed, chunk=64))
    np.testing.assert_array_equal(wcounts, counts.astype(np.float32))
    np.testing.assert_array_equal(
        np.asarray(pipe.estimate(wire, weights=jnp.ones((m,)))),
        np.asarray(pipe.estimate(wire)),
    )


@settings(deadline=None, max_examples=10)
@given(st.integers(0, 2**31 - 1), st.integers(2, 12), st.integers(1, 100))
def test_zero_weight_rows_drop_out(seed, m, d):
    """A zero-weighted (empty / fully stale) buffer slot contributes
    nothing: estimating with rows {0..m-1} and weight_j = 0 equals
    estimating the sub-wire without row j."""
    key = jax.random.PRNGKey(seed)
    deltas = 0.02 * jax.random.normal(key, (m, d))
    b = jnp.float32(0.05)
    pipe = build_pipeline("probit_plus", chunk=64)
    wire, _ = pipe.compressor.compress(key, deltas, b, jnp.zeros((m, d)))
    j = int(jax.random.randint(jax.random.fold_in(key, 1), (), 0, m))
    w = jnp.ones((m,)).at[j].set(0.0)
    import dataclasses

    sub = dataclasses.replace(
        wire, packed=jnp.delete(wire.packed, j, axis=0)
    )
    np.testing.assert_allclose(
        np.asarray(pipe.estimate(wire, weights=w)),
        np.asarray(pipe.estimate(sub, weights=jnp.ones((m - 1,)))),
        rtol=1e-5,
        atol=1e-7,
    )
