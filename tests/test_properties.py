"""Property-based tests for the ServerAggregator MLE (core/aggregation.py).

Randomized over shapes and values (hypothesis when installed, the
deterministic fallback shim otherwise):

* the Eq.-13 estimate is bounded by the public range: |theta_hat_i| <= b_i
  for any vote counts — the amplitude-immunity invariant;
* theta_hat is monotone in the vote count, coordinate-wise;
* packed-wire aggregation equals the dense-codes reference on random
  (M, d) shapes, including d not divisible by 8 (pad-bit handling).
"""

import jax
import jax.numpy as jnp
import numpy as np

from hypothesis import given, settings, strategies as st

from repro.core import (
    build_pipeline,
    codes_to_counts,
    ml_estimate_from_counts,
    packed_counts,
    probit_plus_aggregate,
)
from repro.core.aggregation import _unpack_rows


@settings(deadline=None, max_examples=20)
@given(
    st.integers(0, 2**31 - 1),
    st.integers(1, 64),
    st.integers(1, 257),
)
def test_estimate_bounded_by_b(seed, m, d):
    """|theta_hat_i| <= b_i for every possible count vector 0..M."""
    key = jax.random.PRNGKey(seed)
    counts = jax.random.randint(key, (d,), 0, m + 1)
    b = jnp.abs(jax.random.normal(jax.random.fold_in(key, 1), (d,))) + 1e-3
    theta = ml_estimate_from_counts(counts, m, b)
    assert bool(jnp.all(jnp.abs(theta) <= b * (1 + 1e-6)))
    # extremes reach exactly +/- b
    np.testing.assert_allclose(
        np.asarray(ml_estimate_from_counts(jnp.full((d,), m), m, b)),
        np.asarray(b),
        rtol=1e-6,
    )


@settings(deadline=None, max_examples=20)
@given(st.integers(0, 2**31 - 1), st.integers(2, 64), st.integers(1, 100))
def test_estimate_monotone_in_counts(seed, m, d):
    """Adding a +1 vote to one coordinate raises exactly that estimate."""
    key = jax.random.PRNGKey(seed)
    counts = jax.random.randint(key, (d,), 0, m)  # leave headroom for +1
    b = jnp.abs(jax.random.normal(jax.random.fold_in(key, 1), (d,))) + 1e-3
    i = int(jax.random.randint(jax.random.fold_in(key, 2), (), 0, d))
    theta = ml_estimate_from_counts(counts, m, b)
    theta_up = ml_estimate_from_counts(counts.at[i].add(1), m, b)
    assert float(theta_up[i]) > float(theta[i])
    mask = jnp.arange(d) != i
    np.testing.assert_array_equal(
        np.asarray(theta_up[mask]), np.asarray(theta[mask])
    )


@settings(deadline=None, max_examples=10)
@given(
    st.integers(0, 2**31 - 1),
    st.integers(1, 12),
    st.sampled_from([1, 3, 8, 13, 64, 131, 256]),
)
def test_packed_wire_matches_dense_reference(seed, m, d):
    """Pipeline on the packed wire == dense-codes math, any (M, d) —
    d values deliberately include non-multiples of 8."""
    key = jax.random.PRNGKey(seed)
    deltas = 0.02 * jax.random.normal(key, (m, d))
    b = jnp.float32(0.05)
    pipe = build_pipeline("probit_plus", chunk=64)
    wire, _ = pipe.compressor.compress(key, deltas, b, jnp.zeros((m, d)))
    codes = _unpack_rows(wire.packed, d)
    np.testing.assert_array_equal(
        np.asarray(packed_counts(wire.packed, chunk=64)[:d]),
        np.asarray(codes_to_counts(codes)),
    )
    theta, _ = pipe(key, deltas, b, jnp.zeros((m, d)))
    np.testing.assert_allclose(
        np.asarray(theta),
        np.asarray(probit_plus_aggregate(codes, wire.b)),
        rtol=1e-6,
        atol=1e-8,
    )
