"""Regression tests for the three hand-rolled-wire bugs fixed by routing
``launch/fl_step.py`` through the shared packed pipeline:

1. rand_bits=16 threshold wrap: ``(p * 65536).astype(uint16)`` is 0 at
   p = 1.0 — a *certain* +1 vote transmitted as a certain -1;
2. uint8 count accumulation wrapping mod 256 past 255 clients;
3. b-controller drift vs ``core.bcontrol.update_b_from_vote``.

Each test fails on the pre-rewrite implementation and passes now.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.core.aggregation import ClientCompressor, build_pipeline
from repro.core.bcontrol import (
    BControlConfig,
    BState,
    update_b,
    update_b_from_vote,
)
from repro.core.quantizer import threshold_u16, unpack_bits
from repro.launch import fl_step
from repro.launch.fl_step import DistFLConfig, make_fl_train_step, update_b_dist
from repro.launch.mesh import make_host_mesh
from repro.models import build_specs
from repro.models.spec import init_params, param_pspecs


# ---------------------------------------------------------------------------
# Bug 1: saturated-vote sign flip on the 16-bit wire
# ---------------------------------------------------------------------------

def test_threshold_u16_keeps_saturated_votes_certain():
    # p = 1.0 maps to 65536 — above every uint16 draw, so the vote stays
    # a certain +1. The buggy uint16 cast wraps it to 0 (a certain -1):
    assert int(threshold_u16(jnp.float32(1.0))) == 65536
    # The old uint16 threshold cannot represent certainty: whether the
    # out-of-range cast wraps (0, a certain -1) or saturates (65535),
    # some uint16 draw fails `u < thresh` — a saturated +1 vote can be
    # transmitted as -1. The uint32 threshold beats every draw.
    buggy = (jnp.float32(1.0) * 65536.0).astype(jnp.uint16)
    assert not bool(jnp.uint16(65535) < buggy)
    assert bool(jnp.uint32(65535) < threshold_u16(jnp.float32(1.0)))
    # interior probabilities are the plain floor
    assert int(threshold_u16(jnp.float32(0.5))) == 32768
    assert int(threshold_u16(jnp.float32(0.0))) == 0


@pytest.mark.parametrize("rand_bits", [32, 16])
def test_saturated_deltas_transmit_certain_votes(rand_bits):
    """|delta| >= b must produce deterministic codes for BOTH draw widths."""
    d = 12
    comp = ClientCompressor(rand_bits=rand_bits)
    b = jnp.float32(0.25)
    for sign in (1.0, -1.0):
        deltas = jnp.full((3, d), sign * 0.25, jnp.float32)
        wire, _ = comp.compress(
            jax.random.PRNGKey(0), deltas, b, jnp.zeros((3, d))
        )
        codes = np.asarray(
            jax.vmap(lambda p: unpack_bits(p, d))(wire.packed)
        )
        assert np.all(codes == sign), (rand_bits, sign, codes)


# ---------------------------------------------------------------------------
# Bug 2 (+1 end-to-end): exact counts past 255 clients through the real
# distributed train step
# ---------------------------------------------------------------------------

def tiny_cfg():
    return dataclasses.replace(
        configs.get_config("qwen2-1.5b"),
        name="qwen2-micro",
        n_layers=1, d_model=32, n_heads=2, n_kv_heads=1,
        d_ff=64, vocab=64, d_head=16,
    )


@pytest.mark.parametrize("rand_bits", [32, 16])
def test_fl_step_counts_exact_at_m300(monkeypatch, rand_bits):
    """Rigged cohort of M = 300 clients whose every delta saturates at
    +1.0 >> b: all votes are certain +1, so counts == 300 exactly and the
    Eq.-13 update is precisely +b on every parameter.

    The pre-rewrite step fails this twice over: uint8 count accumulation
    wraps 300 -> 44 (theta ~ -0.70 b), and at rand_bits=16 the threshold
    wrap turns every certain +1 into a certain -1 (theta == -b).
    """
    m = 300
    cfg = tiny_cfg()
    # A loss whose gradient is exactly -100 per coordinate: one prox-free
    # local step at lr = 0.01 moves every weight by +1.0.
    fake_loss = lambda p, sb, c: -100.0 * sum(
        jnp.sum(l.astype(jnp.float32)) for l in jax.tree.leaves(p)
    )
    monkeypatch.setattr(fl_step, "train_loss", fake_loss)
    with jax.set_mesh(make_host_mesh()):
        specs = build_specs(cfg)
        params = init_params(specs, jax.random.PRNGKey(0))
        fl = DistFLConfig(
            clients_per_round=m, local_steps=1, lr=0.01, rand_bits=rand_bits
        )
        step = jax.jit(make_fl_train_step(cfg, fl, param_pspecs(specs)))
        b = jnp.float32(0.5)
        batch = {"x": jnp.zeros((m, 1, 1, 1, 2), jnp.float32)}
        new_params, b_new, metrics = step(params, b, batch, jax.random.PRNGKey(1))
        expected = jax.tree.map(
            lambda w: (w.astype(jnp.float32) + 0.5).astype(w.dtype), params
        )
        # counts are exactly 300; under jit XLA folds the /M of Eq. 13 into
        # a reciprocal multiply (theta = 0.5 + O(1e-8)), so compare at float
        # tolerance — the bug signals are 0.85 b (uint8 wrap) and 2 b
        # (uint16 threshold), seven orders of magnitude above it.
        for got, want in zip(jax.tree.leaves(new_params), jax.tree.leaves(expected)):
            np.testing.assert_allclose(
                np.asarray(got, np.float32), np.asarray(want, np.float32),
                atol=1e-5, rtol=0,
            )
        # constant loss across the single local step -> no-progress vote,
        # tie/negative contracts b by b_down (shared controller semantics)
        assert np.isclose(float(b_new), 0.5 * fl.b_down)
        assert float(metrics["wire_bytes"]) > 0


# ---------------------------------------------------------------------------
# Bug 3: b-controller parity with the simulation path
# ---------------------------------------------------------------------------

def test_update_b_parity_with_simulation():
    fl = DistFLConfig(b_up=1.05, b_down=0.9)
    cfg = BControlConfig(mode="dynamic", up=fl.b_up, down=fl.b_down)
    b0 = jnp.float32(0.02)
    for vote in (-4.0, 0.0, 7.0):
        got = update_b_dist(b0, jnp.float32(vote), fl)
        ref = update_b_from_vote(
            BState(b=b0, prev_vote=jnp.float32(0.0)), jnp.float32(vote), cfg
        ).b
        assert float(got) == float(ref), vote
    # tie vote contracts — the case a hand-rolled `votes > 0` branch can
    # silently get wrong relative to fl/rounds.py
    assert np.isclose(float(update_b_dist(b0, jnp.float32(0.0), fl)), 0.02 * 0.9)
    # one-shot bit-stream composition used by fl/rounds agrees too
    bits = jnp.asarray([1, -1, -1, 1, 1], jnp.int8)
    ref_stream = update_b(
        BState(b=b0, prev_vote=jnp.float32(0.0)), bits, cfg
    ).b
    got_stream = update_b_dist(b0, jnp.sum(bits.astype(jnp.float32)), fl)
    assert float(got_stream) == float(ref_stream)


# ---------------------------------------------------------------------------
# Wire-schedule parity: the mesh step speaks the pytree-wire schedule
# ---------------------------------------------------------------------------

def test_fl_step_pipeline_uses_shared_registry():
    """The step's quantizer/estimator are the registry pipeline objects —
    no hand-rolled math left to drift."""
    pipe = build_pipeline("probit_plus", rand_bits=16)
    assert pipe.compressor.rand_bits == 16
    with pytest.raises(ValueError, match="rand_bits"):
        build_pipeline("probit_plus", rand_bits=8)
    with pytest.raises(ValueError, match="kernel"):
        ClientCompressor(rand_bits=16, use_kernels=True)
    with pytest.raises(ValueError, match="top-k"):
        ClientCompressor(rand_bits=16, topk_frac=0.5)
