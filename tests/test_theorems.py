"""Numerical validation of the paper's Theorems 1–3."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.core import (
    DPConfig,
    dp_b_floor,
    flip_codes,
    ml_estimate_from_counts,
    privacy_loss,
    probit_plus_aggregate,
    probit_plus_from_updates,
    stochastic_binarize,
)


def _updates(key, m, d, scale=0.01):
    # heterogeneous client means around a common theta (paper Fig. 1 model)
    theta = scale * jax.random.normal(key, (d,))
    noise = scale * 0.5 * jax.random.normal(jax.random.fold_in(key, 1), (m, d))
    return theta + noise


class TestTheorem1:
    def test_unbiased(self):
        """E[theta_hat] == theta over quantization randomness."""
        key = jax.random.PRNGKey(0)
        m, d = 32, 64
        upd = _updates(key, m, d)
        b = jnp.abs(upd).max() + 0.01
        bvec = jnp.full((d,), b)
        reps = 600
        keys = jax.random.split(jax.random.fold_in(key, 7), reps)
        ests = jax.vmap(lambda k: probit_plus_from_updates(k, upd, bvec))(keys)
        mean_est = jnp.mean(ests, axis=0)
        target = jnp.mean(upd, axis=0)  # FedAvg value = theta estimate target
        se = float(b) / np.sqrt(m * reps)
        assert float(jnp.max(jnp.abs(mean_est - target))) < 6 * se

    def test_error_formula(self):
        """E||theta - theta_hat||^2 == sum(b^2 - theta^2)/M for known theta."""
        key = jax.random.PRNGKey(1)
        d, m = 128, 16
        theta = 0.02 * jax.random.normal(key, (d,))
        b = 0.05
        bvec = jnp.full((d,), b)
        # all clients at exactly theta: the only error is quantization
        upd = jnp.tile(theta[None], (m, 1))
        reps = 800
        keys = jax.random.split(key, reps)
        errs = jax.vmap(
            lambda k: jnp.sum((probit_plus_from_updates(k, upd, bvec) - theta) ** 2)
        )(keys)
        expected = float(jnp.sum(b**2 - theta**2) / m)
        measured = float(jnp.mean(errs))
        assert abs(measured - expected) / expected < 0.1

    def test_error_rate_O_1_over_M(self):
        """Doubling M halves the squared error (Thm 1.3 rate)."""
        key = jax.random.PRNGKey(2)
        d = 256
        theta = 0.02 * jax.random.normal(key, (d,))
        b = jnp.full((d,), 0.06)
        errs = {}
        for m in (8, 32, 128):
            upd = jnp.tile(theta[None], (m, 1))
            keys = jax.random.split(jax.random.fold_in(key, m), 300)
            e = jax.vmap(
                lambda k: jnp.sum((probit_plus_from_updates(k, upd, b) - theta) ** 2)
            )(keys)
            errs[m] = float(jnp.mean(e))
        assert errs[32] < errs[8] / 2.5
        assert errs[128] < errs[32] / 2.5


class TestTheorem2:
    @settings(deadline=None, max_examples=10)
    @given(st.integers(0, 2**31 - 1), st.sampled_from([0.1, 0.2, 0.4]))
    def test_byzantine_deviation_bound(self, seed, beta):
        """||E[theta]_R - E[theta]_B|| <= 2 beta ||b|| under ANY bit attack."""
        key = jax.random.PRNGKey(seed)
        m, d = 40, 32
        n_byz = int(m * beta)
        upd = _updates(key, m, d)
        bvec = jnp.full((d,), float(jnp.abs(upd).max()) + 0.01)
        reps = 400
        keys = jax.random.split(jax.random.fold_in(key, 3), reps)

        def est(k, attack):
            ks = jax.random.split(k, m)
            codes = jax.vmap(stochastic_binarize, in_axes=(0, 0, None))(ks, upd, bvec)
            if attack:
                codes = flip_codes(codes, n_byz)  # worst-case bit adversary
            return probit_plus_aggregate(codes, bvec)

        clean = jnp.mean(jax.vmap(lambda k: est(k, False))(keys), axis=0)
        attacked = jnp.mean(jax.vmap(lambda k: est(k, True))(keys), axis=0)
        dev = float(jnp.linalg.norm(clean - attacked))
        bound = 2 * beta * float(jnp.linalg.norm(bvec))
        assert dev <= bound * 1.05  # 5% slack for Monte-Carlo noise

    def test_magnitude_immunity(self):
        """A single Byzantine with unbounded magnitude moves PRoBit+ by at
        most 2b/M per coordinate — while FedAvg diverges arbitrarily."""
        key = jax.random.PRNGKey(3)
        m, d = 20, 16
        upd = _updates(key, m, d)
        evil = upd.at[0].set(1e9)
        bvec = jnp.full((d,), float(jnp.abs(upd[1:]).max()) + 0.01)
        keys = jax.random.split(key, 500)
        clean = jnp.mean(
            jax.vmap(lambda k: probit_plus_from_updates(k, upd, bvec))(keys), axis=0
        )
        attacked = jnp.mean(
            jax.vmap(lambda k: probit_plus_from_updates(k, evil, bvec))(keys), axis=0
        )
        per_coord = jnp.abs(clean - attacked)
        assert float(per_coord.max()) <= 2 * float(bvec[0]) / m * 1.3
        fedavg_dev = jnp.abs(jnp.mean(evil, 0) - jnp.mean(upd, 0)).max()
        assert float(fedavg_dev) > 1e6  # FedAvg is destroyed


class TestTheorem3:
    @settings(deadline=None, max_examples=25)
    @given(
        st.integers(0, 2**31 - 1),
        st.sampled_from([0.05, 0.1, 0.5, 1.0]),
    )
    def test_privacy_loss_bounded_by_eps(self, seed, eps):
        """Worst-case log-likelihood ratio <= eps when b respects the floor."""
        key = jax.random.PRNGKey(seed)
        d = 64
        delta1 = 2e-4
        cfg = DPConfig(eps, delta1)
        delta_a = 0.01 * jax.random.normal(key, (d,))
        # adjacent update: l1 perturbation of size exactly Delta_1
        v = jax.random.normal(jax.random.fold_in(key, 1), (d,))
        v = v / jnp.sum(jnp.abs(v)) * delta1
        delta_b = delta_a + v
        floor = dp_b_floor(jnp.maximum(jnp.abs(delta_a), jnp.abs(delta_b)).max(), cfg)
        b = jnp.full((d,), floor)
        pl = float(privacy_loss(delta_a, delta_b, b))
        assert pl <= eps * 1.0001

    def test_privacy_loss_finite_at_range_boundary(self):
        """Regression: delta = +-b exactly drives binarize_prob to {0, 1};
        the empirical loss must clamp, not diverge to inf/NaN."""
        b = jnp.full((3,), 0.02)
        pl = privacy_loss(
            jnp.array([0.02, -0.02, 0.02]),
            jnp.array([-0.02, 0.02, 0.01]),
            b,
        )
        assert bool(jnp.isfinite(pl))
        # one-sided: only one update on the boundary
        pl1 = privacy_loss(jnp.array([0.02]), jnp.array([0.0]), b[:1])
        assert bool(jnp.isfinite(pl1))

    def test_smaller_eps_needs_larger_b(self):
        floors = [
            float(dp_b_floor(jnp.float32(0.01), DPConfig(e, 2e-4)))
            for e in (1.0, 0.1, 0.01)
        ]
        assert floors[0] < floors[1] < floors[2]
