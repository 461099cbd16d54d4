"""Substrate tests: data pipeline, partitioners, optimizer, checkpointing,
aggregation baselines."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.checkpoint import latest_step, load_checkpoint, save_checkpoint
from repro.core import (
    fedavg_aggregate,
    geometric_median,
    get_attack,
    rsa_aggregate,
    signsgd_mv_aggregate,
)
from repro.data import (
    make_classification,
    make_image_classification,
    make_lm_streams,
    partition_dirichlet,
    partition_label_skew,
)
from repro.models.vision import (
    accuracy,
    cnn_logits,
    init_cnn,
    init_resnet,
    resnet_logits,
    xent_loss,
)
from repro.optim import local_prox_train
from jax.flatten_util import ravel_pytree


class TestData:
    def test_label_skew_respects_class_budget(self):
        (_, y), _ = make_classification(0, n_train=2000)
        parts = partition_label_skew(y, 8, 2, 50)
        for idx in parts:
            assert len(idx) == 50
            assert len(np.unique(y[idx])) <= 2

    def test_dirichlet_partition(self):
        (_, y), _ = make_classification(0, n_train=2000)
        parts = partition_dirichlet(y, 8, 50, alpha=0.3)
        assert all(len(i) == 50 for i in parts)

    def test_lm_streams_skewed(self):
        streams = make_lm_streams(0, 4, 1000, 32, 10)
        assert len(streams) == 4
        assert streams[0].shape == (10, 32)
        assert streams[0].max() < 1000
        # different clients should have different unigram histograms
        h0 = np.bincount(streams[0].ravel(), minlength=1000)
        h1 = np.bincount(streams[1].ravel(), minlength=1000)
        assert not np.array_equal(h0, h1)


class TestVisionModels:
    def test_cnn_forward_backward(self):
        p = init_cnn(jax.random.PRNGKey(0), width=8)
        x = jnp.ones((2, 28, 28, 1))
        logits = cnn_logits(p, x)
        assert logits.shape == (2, 10)
        g = jax.grad(lambda q: xent_loss(cnn_logits, q, {"x": x, "y": jnp.zeros(2, jnp.int32)}))(p)
        assert all(bool(jnp.all(jnp.isfinite(v))) for v in jax.tree.leaves(g))

    def test_resnet_forward(self):
        p = init_resnet(jax.random.PRNGKey(0), width=8, blocks=(1, 1, 1, 1))
        x = jnp.ones((2, 32, 32, 3))
        logits = resnet_logits(p, x, blocks=(1, 1, 1, 1))
        assert logits.shape == (2, 10)
        assert bool(jnp.all(jnp.isfinite(logits)))


class TestOptim:
    def test_prox_pull_toward_global(self):
        """With zero data gradient, the prox term pulls w to w_global."""
        w0 = jnp.zeros(16)
        w_init = jnp.ones(16)
        batches = {"x": jnp.zeros((50, 1))}

        def loss_fn(params, batch):
            return 0.0 * jnp.sum(params)  # no data signal

        from jax.flatten_util import ravel_pytree

        flat, unravel = ravel_pytree(w_init)
        w, l0, l1 = local_prox_train(
            lambda p, b: loss_fn(p, b), w0, flat, unravel, batches,
            lr=0.1, mu=0.0, lam=1.0,
        )
        assert float(jnp.max(jnp.abs(w))) < float(jnp.max(jnp.abs(w_init)))


class TestAggregators:
    def test_fedavg_is_mean(self):
        u = jnp.arange(12.0).reshape(3, 4)
        np.testing.assert_allclose(fedavg_aggregate(u), u.mean(0))

    def test_geometric_median_resists_outlier(self):
        key = jax.random.PRNGKey(0)
        u = 0.01 * jax.random.normal(key, (20, 8))
        evil = u.at[0].set(1e6)
        gm = geometric_median(evil)
        assert float(jnp.linalg.norm(gm)) < 1.0
        assert float(jnp.linalg.norm(fedavg_aggregate(evil))) > 1e4

    def test_signsgd_mv_magnitude(self):
        codes = jnp.ones((5, 7), jnp.int8)
        out = signsgd_mv_aggregate(codes, step=0.01)
        np.testing.assert_allclose(out, 0.01)

    def test_rsa_accumulates(self):
        codes = jnp.ones((5, 7), jnp.int8)
        np.testing.assert_allclose(rsa_aggregate(codes, 0.01), 0.05)

    @settings(deadline=None, max_examples=10)
    @given(st.integers(0, 1000))
    def test_attacks_preserve_honest_rows(self, seed):
        key = jax.random.PRNGKey(seed)
        u = jax.random.normal(key, (10, 6))
        for name in ("gaussian", "sign_flip", "zero_gradient", "sample_duplicate"):
            out = get_attack(name)(key, u, 3)
            np.testing.assert_array_equal(np.asarray(out[3:]), np.asarray(u[3:]))

    def test_zero_gradient_sums_to_zero(self):
        key = jax.random.PRNGKey(1)
        u = jax.random.normal(key, (10, 6))
        out = get_attack("zero_gradient")(key, u, 4)
        np.testing.assert_allclose(np.asarray(out.sum(0)), 0.0, atol=1e-5)


class TestCheckpoint:
    def test_roundtrip(self, tmp_path):
        tree = {"a": jnp.arange(5.0), "b": {"c": jnp.ones((2, 3), jnp.bfloat16)}}
        save_checkpoint(str(tmp_path), 3, tree, {"note": "x"})
        assert latest_step(str(tmp_path)) == 3
        out = load_checkpoint(str(tmp_path), 3, tree)
        np.testing.assert_array_equal(np.asarray(out["a"]), np.arange(5.0))
        assert out["b"]["c"].dtype == jnp.bfloat16

    def test_shape_mismatch_raises(self, tmp_path):
        tree = {"a": jnp.arange(5.0)}
        save_checkpoint(str(tmp_path), 1, tree)
        bad = {"a": jnp.arange(6.0)}
        with pytest.raises(AssertionError):
            load_checkpoint(str(tmp_path), 1, bad)
