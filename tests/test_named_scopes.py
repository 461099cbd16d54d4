"""The round's phases carry ``fl.<phase>`` named scopes into the compiled
program's op metadata, where a profiler trace reads them (``tf_op``), and
the simulation's jitted round is named ``fl_round``. Scopes are metadata
only: the round computes exactly what it computes without them."""

import dataclasses
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.fl import FLConfig, FLSimulation
from repro.fl import rounds as R
from repro.launch.fl_step import DistFLConfig, make_fl_train_step
from repro.launch.mesh import make_host_mesh
from repro.models import build_specs, sample_batch
from repro.models.spec import init_params, param_pspecs
from repro.models.vision import accuracy, init_cnn, cnn_logits, xent_loss

ROUND_SCOPES = {"fl.gather", "fl.train", "fl.attack", "fl.compress",
                "fl.count", "fl.finalize", "fl.update", "fl.writeback"}
STEP_SCOPES = {"fl.train", "fl.compress", "fl.count", "fl.finalize"}


def _op_names(compiled_text: str) -> list:
    return re.findall(r'op_name="([^"]*)"', compiled_text)


def _scopes(compiled_text: str) -> set:
    return {f"fl.{s}" for name in _op_names(compiled_text)
            for s in re.findall(r"(?<![\w.])fl\.(\w+)", name)}


def _cnn_sim(**kw):
    rng = np.random.default_rng(0)
    cx = rng.normal(size=(8, 10, 28, 28, 1)).astype(np.float32)
    cy = rng.integers(0, 10, size=(8, 10)).astype(np.int32)
    cfg = FLConfig(n_clients=8, participation=0.5, rounds=1, local_epochs=1,
                   batch_size=5, byz_frac=0.25, attack="gaussian",
                   dp_epsilon=1.0, l1_sensitivity=2e-4, b_mode="dynamic", **kw)
    return FLSimulation(
        cfg, init_cnn(jax.random.PRNGKey(0), width=2),
        functools.partial(xent_loss, cnn_logits),
        functools.partial(accuracy, cnn_logits), cx, cy,
        {"x": cx[0], "y": cy[0]},
    )


@pytest.mark.parametrize("use_kernels", [False, True], ids=["xla", "kernels"])
def test_xdev_round_names_every_phase(use_kernels):
    sim = _cnn_sim(use_kernels=use_kernels)
    batches = sim._round_batches(jax.random.PRNGKey(2))
    text = sim._round.lower(
        jax.random.PRNGKey(1), sim.state, batches
    ).compile().as_text()
    assert _scopes(text) == ROUND_SCOPES
    # the round's program and its ops' name paths carry its name
    names = _op_names(text)
    assert names and all(n.startswith("jit(fl_round)/") for n in names
                         if n.startswith("jit("))
    assert not any("jit(<unknown>)" in n for n in names)


def test_named_round_computes_what_the_round_function_does():
    """``FLSimulation._round`` (the named, donating jit) against the round
    function jitted directly: the same state, bit for bit."""
    sim = _cnn_sim(use_kernels=True)
    plain = jax.jit(functools.partial(R.round_fn(sim.ctx), sim.ctx, sim._params))
    state = sim.state
    key = jax.random.PRNGKey(5)
    for _ in range(2):
        key, kb, kr = jax.random.split(key, 3)
        batches = sim._round_batches(kb)
        want, want_m = plain(kr, state, batches)
        want = jax.tree.map(np.asarray, want)
        state, got_m = sim._round(kr, state, batches)
        for g, w in zip(jax.tree.leaves(state), jax.tree.leaves(want)):
            np.testing.assert_array_equal(np.asarray(g), w)
        assert float(got_m["loss"]) == float(want_m["loss"])


def test_cross_silo_step_names_its_phases():
    cfg = dataclasses.replace(
        configs.get_config("qwen2-1.5b"), name="qwen2-micro",
        n_layers=1, d_model=32, n_heads=2, n_kv_heads=1, d_ff=64, vocab=64,
        d_head=16,
    )
    with jax.set_mesh(make_host_mesh()):
        specs = build_specs(cfg)
        params = init_params(specs, jax.random.PRNGKey(0))
        fl = DistFLConfig(clients_per_round=2, local_steps=1)
        step = jax.jit(make_fl_train_step(cfg, fl, param_pspecs(specs)))
        sb = sample_batch(cfg, 2, 16, "train")
        batch = jax.tree.map(
            lambda a: jnp.broadcast_to(a[None, None, None], (2, 1, 1) + a.shape), sb
        )
        text = step.lower(
            params, jnp.float32(0.01), batch, jax.random.PRNGKey(1)
        ).compile().as_text()
        metrics = jax.eval_shape(
            step, params, jnp.float32(0.01), batch, jax.random.PRNGKey(1)
        )[2]
    assert STEP_SCOPES <= _scopes(text)
    # the uplink baselines are static (pytree_wire_bytes), not step outputs
    assert set(metrics) == {"loss_first", "loss_last", "b", "wire_bytes"}
