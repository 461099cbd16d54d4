"""Streaming client-chunk aggregation: parity + memory-bound tests.

The streaming round (``FLConfig.client_chunk > 0``) must be a pure
execution-strategy change — same estimates, same trajectories, chunk size
invisible. Three layers are pinned here:

* **count protocol** — ``init_counts / accumulate_counts / finalize``
  over arbitrary client splits equals the one-shot ``aggregate`` for
  every registered aggregator (integer-exact for the count schemes,
  including 0/1 active-client masks and fractional staleness-style
  weights; FedAvg's running-sum protocol to f32 reassociation);
* **round parity** — dense vs chunked ``stream_fl_round`` at chunk
  sizes that do and do not divide M, for all five aggregators, under
  partial participation, error feedback, and the Byzantine attacks the
  streaming gate admits (bit-exact in eager, <= 1e-6 under jit; the
  model's d = 450 exercises d % 8 != 0 on the packed wire);
* **memory bound** — a subprocess under a hard ``RLIMIT_AS`` cap runs
  M = 60k clients x d = 4866 chunk-bounded where the dense round
  provably OOMs (the CI ``stream-smoke`` job runs exactly this:
  ``-k "smoke or rss"``).
"""

import functools
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import build_pipeline
from repro.core.quantizer import packed_counts
from repro.data import make_classification, partition_label_skew
from repro.fl import rounds as R
from repro.fl.runtime import FLConfig
from repro.models.vision import accuracy, init_mlp, mlp_logits, xent_loss

AGGREGATORS = ("probit_plus", "signsgd_mv", "rsa", "fedavg", "fed_gm")
COUNT_SCHEMES = ("probit_plus", "signsgd_mv", "rsa")
N = 10


# ---------------------------------------------------------------------------
# Count-protocol parity (aggregation layer)
# ---------------------------------------------------------------------------


def _wire(name, m=12, d=13, seed=0):
    """A packed (or dense) cohort wire at d % 8 != 0."""
    pipe = build_pipeline(name, chunk=16)
    key = jax.random.PRNGKey(seed)
    deltas = 0.05 * jax.random.normal(key, (m, d))
    b = jnp.float32(0.1)
    res = jnp.zeros((m, d))
    wire, _ = pipe.compress_wire(jax.random.fold_in(key, 1), deltas, b, res)
    return pipe, wire


@pytest.mark.parametrize("name", COUNT_SCHEMES)
def test_accumulate_finalize_matches_one_shot(name):
    """Chunked count accumulation == one-shot aggregate, any split."""
    pipe, wire = _wire(name)
    one_shot = pipe.server.aggregate(wire)
    for splits in ((4, 4, 4), (5, 4, 3), (12,), (1,) * 12):
        counts = pipe.server.init_counts(wire.packed.shape[1])
        row = 0
        for c in splits:
            counts = pipe.server.accumulate_counts(
                counts, wire.packed[row : row + c]
            )
            row += c
        est = pipe.server.finalize(counts, wire.n_clients, wire.b)
        np.testing.assert_array_equal(np.asarray(est), np.asarray(one_shot))


@pytest.mark.parametrize("name", COUNT_SCHEMES)
@pytest.mark.parametrize(
    "weights",
    [
        np.array([1, 0] * 6, np.float32),  # active-client mask
        (np.arange(12) % 4 + 1).astype(np.float32) / 4,  # staleness-style
    ],
    ids=["mask01", "staleness"],
)
def test_weighted_accumulate_matches_one_shot(name, weights):
    pipe, wire = _wire(name)
    w = jnp.asarray(weights)
    one_shot = pipe.server.aggregate(wire, w)
    counts = pipe.server.init_counts(wire.packed.shape[1], weighted=True)
    for row in range(0, 12, 5):  # 5 does not divide 12
        counts = pipe.server.accumulate_counts(
            counts, wire.packed[row : row + 5], w[row : row + 5]
        )
    wsum = jnp.maximum(jnp.sum(w), 1e-12)
    est = jnp.where(
        jnp.sum(w) > 0, pipe.server.finalize(counts, wsum, wire.b), 0.0
    )
    np.testing.assert_allclose(
        np.asarray(est), np.asarray(one_shot), rtol=1e-6, atol=1e-7
    )


def test_fedavg_stream_sum_matches_dense():
    pipe, wire = _wire("fedavg")
    w = jnp.asarray((np.arange(12) % 3).astype(np.float32))
    one_shot = pipe.server.aggregate(wire, w)
    carry = pipe.server.init_stream_sum(wire.updates.shape[1])
    for row in range(0, 12, 5):
        carry = pipe.server.accumulate_sum(
            carry, wire.updates[row : row + 5], w[row : row + 5]
        )
    np.testing.assert_allclose(
        np.asarray(pipe.server.finalize_sum(carry)),
        np.asarray(one_shot),
        rtol=1e-6,
        atol=1e-7,
    )


def test_popcount_matches_unpack_reduction():
    """population_count path == unpack-and-sum path, and both == numpy."""
    rng = np.random.default_rng(7)
    packed = jnp.asarray(rng.integers(0, 256, (37, 11), dtype=np.uint8))
    pop = packed_counts(packed, chunk=24, use_popcount=True)
    ref = packed_counts(packed, chunk=24, use_popcount=False)
    np.testing.assert_array_equal(np.asarray(pop), np.asarray(ref))
    bits = np.unpackbits(np.asarray(packed), axis=1, bitorder="little")
    np.testing.assert_array_equal(np.asarray(pop), bits.sum(0).astype(np.int32))
    bytes_ = jnp.arange(256, dtype=jnp.uint8)
    np.testing.assert_array_equal(
        np.asarray(jax.lax.population_count(bytes_)),
        np.array([bin(v).count("1") for v in range(256)], np.uint8),
    )


# ---------------------------------------------------------------------------
# Round parity (fl layer)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def round_env():
    (xtr, ytr), (xte, yte) = make_classification(0, n_train=1000, n_test=200)
    parts = partition_label_skew(ytr, N, 2, 60, seed=1)
    return dict(
        p0=init_mlp(jax.random.PRNGKey(0), hidden=8),
        loss=functools.partial(xent_loss, mlp_logits),
        acc=functools.partial(accuracy, mlp_logits),
        cx=np.stack([xtr[i] for i in parts]),
        cy=np.stack([ytr[i] for i in parts]),
        test={"x": xte, "y": yte},
    )


def _run(round_env, cfg, rounds=2, eager=True):
    ctx = R.make_context(
        cfg,
        round_env["p0"],
        round_env["loss"],
        round_env["acc"],
        round_env["cx"],
        round_env["cy"],
        round_env["test"],
    )
    params = R.cell_params(cfg)
    state = R.init_run_state(ctx)
    key = jax.random.PRNGKey(cfg.seed)
    fn = R.round_fn(ctx)
    with jax.disable_jit(eager):
        for _ in range(rounds):
            key, kb, kr = jax.random.split(key, 3)
            state, m = fn(ctx, params, kr, state, R.round_batches(ctx, kb))
    return state, m


@pytest.mark.parametrize("agg", AGGREGATORS)
def test_round_parity_all_aggregators(round_env, agg):
    """Chunked round == dense round; chunk 4 does not divide M = 10."""
    base = dict(n_clients=N, rounds=2, local_epochs=1, aggregator=agg)
    dense, _ = _run(round_env, FLConfig(**base))
    stream, _ = _run(round_env, FLConfig(**base, client_chunk=4))
    wd, ws = np.asarray(dense.w_global), np.asarray(stream.w_global)
    if agg in COUNT_SCHEMES:
        np.testing.assert_array_equal(wd, ws)
        np.testing.assert_array_equal(
            np.asarray(dense.b.b), np.asarray(stream.b.b)
        )
    else:
        np.testing.assert_allclose(wd, ws, atol=1e-6)


@pytest.mark.parametrize(
    "extra",
    [
        dict(participation=0.7),
        dict(error_feedback=True),
        dict(byz_frac=0.2, attack="sign_flip"),
        dict(byz_frac=0.2, attack="bit_flip"),
    ],
    ids=["participation", "error_feedback", "sign_flip", "bit_flip"],
)
def test_round_parity_masks_state_attacks(round_env, extra):
    """Parity extends to the full carried state (w_locals, residuals)."""
    base = dict(
        n_clients=N, rounds=2, local_epochs=1, aggregator="probit_plus", **extra
    )
    dense, _ = _run(round_env, FLConfig(**base))
    stream, _ = _run(round_env, FLConfig(**base, client_chunk=4))
    for field in ("w_global", "w_locals", "residuals"):
        np.testing.assert_array_equal(
            np.asarray(getattr(dense, field)), np.asarray(getattr(stream, field))
        )


def test_round_parity_kernel_wire(round_env):
    """use_kernels=True under client_chunk streaming: the kernel wire's
    counter-derived per-client PRNG (``row_offset`` rebasing) makes the
    chunked round bit-exact with the dense one — and, because the dispatch
    policy resolves to the ref engine off-TPU, bit-exact with the pure-JAX
    wire too."""
    from repro.kernels import resolve_engine

    base = dict(
        n_clients=N, rounds=2, local_epochs=1, aggregator="probit_plus",
        use_kernels=True,
    )
    dense, _ = _run(round_env, FLConfig(**base))
    stream, _ = _run(round_env, FLConfig(**base, client_chunk=4))
    for field in ("w_global", "w_locals", "residuals"):
        np.testing.assert_array_equal(
            np.asarray(getattr(dense, field)), np.asarray(getattr(stream, field))
        )
    if resolve_engine() == "ref":
        pure, _ = _run(
            round_env,
            FLConfig(n_clients=N, rounds=2, local_epochs=1,
                     aggregator="probit_plus"),
        )
        np.testing.assert_array_equal(
            np.asarray(dense.w_global), np.asarray(pure.w_global)
        )


def test_gaussian_attack_chunk_invariant(round_env):
    """The gaussian payload draws per cohort row, so the stream round is
    chunk-size invariant (dense parity is not required — the dense round
    draws its noise in one block)."""
    base = dict(
        n_clients=N,
        rounds=2,
        local_epochs=1,
        aggregator="probit_plus",
        byz_frac=0.2,
        attack="gaussian",
    )
    s4, _ = _run(round_env, FLConfig(**base, client_chunk=4))
    s7, _ = _run(round_env, FLConfig(**base, client_chunk=7))
    np.testing.assert_array_equal(
        np.asarray(s4.w_global), np.asarray(s7.w_global)
    )


def test_round_parity_under_jit(round_env):
    base = dict(n_clients=N, rounds=2, local_epochs=1, aggregator="probit_plus")
    dense, _ = _run(round_env, FLConfig(**base), eager=False)
    stream, _ = _run(round_env, FLConfig(**base, client_chunk=4), eager=False)
    np.testing.assert_allclose(
        np.asarray(dense.w_global), np.asarray(stream.w_global), atol=1e-6
    )


def test_stateless_clients_smoke(round_env):
    """Cross-device mode: no per-client state, single broadcast row."""
    cfg = FLConfig(
        n_clients=N,
        rounds=2,
        local_epochs=1,
        client_chunk=4,
        stateless_clients=True,
    )
    state, m = _run(round_env, cfg, eager=False)
    assert state.w_locals.shape[0] == 1
    assert np.isfinite(float(m["loss"]))


def test_kbit_stream_round_smoke(round_env):
    """k=2 wire (the CI smoke cell): chunked round == dense round exactly.

    The plane-major k-bit wire streams through the *unchanged* count
    protocol — the flat count carry of a ``bits * P``-byte row is the
    per-plane vote count — so chunk-vs-dense parity holds bit-for-bit
    just as at k=1.
    """
    base = dict(
        n_clients=N, rounds=2, local_epochs=1, aggregator="probit_plus",
        wire_bits=2,
    )
    dense, _ = _run(round_env, FLConfig(**base))
    stream, _ = _run(round_env, FLConfig(**base, client_chunk=4))
    for field in ("w_global", "w_locals", "residuals"):
        np.testing.assert_array_equal(
            np.asarray(getattr(dense, field)), np.asarray(getattr(stream, field))
        )


def test_campaign_planner_streams_fused_groups():
    """plan_campaign flips fusable groups past the threshold to streaming,
    with metric parity against the dense plan and peak-bytes stats."""
    from repro.sim import CampaignSpec, CellSpec, Task, run_campaign
    from repro.sim.plan import plan_campaign

    (xtr, ytr), (xte, yte) = make_classification(0, n_train=800, n_test=100)

    def task_fn(cfg, _cache={}):
        m = cfg.n_clients
        if m not in _cache:
            parts = partition_label_skew(ytr, m, 2, 30, seed=1)
            _cache[m] = Task(
                init_params=init_mlp(jax.random.PRNGKey(0), hidden=8),
                loss_fn=functools.partial(xent_loss, mlp_logits),
                acc_fn=functools.partial(accuracy, mlp_logits),
                client_x=np.stack([xtr[i] for i in parts]),
                client_y=np.stack([ytr[i] for i in parts]),
                test={"x": xte, "y": yte},
            )
        return _cache[m]

    spec = CampaignSpec(
        base=dict(rounds=2, local_epochs=1),
        cells=(CellSpec("M=8", dict(n_clients=8)),),
        seeds=(0, 1),
    )
    streamed = plan_campaign(spec, stream_threshold=4, stream_chunk=8)
    assert streamed.groups[0].client_chunk == 8
    assert "stream@8" in streamed.describe()
    dense = plan_campaign(spec, stream_threshold=10**9)
    assert dense.groups[0].client_chunk == 0

    rs = run_campaign(spec, task_fn, plan=streamed)
    rd = run_campaign(spec, task_fn, plan=dense)
    np.testing.assert_allclose(
        rs.cells[0].metrics["theta_mse"],
        rd.cells[0].metrics["theta_mse"],
        atol=1e-9,
    )
    g = rs.groups[0]
    assert g["client_chunk"] == 8
    assert g["peak_bytes_est"] > 0
    # the dense plan's resident estimate must dominate the streamed one
    assert rd.groups[0]["peak_bytes_est"] >= g["peak_bytes_est"]


# ---------------------------------------------------------------------------
# Memory bound (CI stream-smoke target)
# ---------------------------------------------------------------------------

_RSS_CHILD = textwrap.dedent(
    """
    import resource, sys
    # Hard address-space cap, far below the dense working set (the dense
    # leg OOMs even at 4.5 GB) with headroom over the streaming round's
    # ~0.6 GB resident set for XLA thread stacks / allocator arenas.
    cap = 4 << 30
    resource.setrlimit(resource.RLIMIT_AS, (cap, cap))
    import functools
    import jax, numpy as np
    from repro.fl import rounds as R
    from repro.fl.runtime import FLConfig
    from repro.models.vision import accuracy, init_mlp, mlp_logits, xent_loss

    M, DIM, PER, HID = 60_000, 8, 2, 64
    rng = np.random.default_rng(0)
    w = rng.standard_normal(DIM).astype(np.float32)
    cx = rng.standard_normal((M, PER, DIM), dtype=np.float32)
    cy = (cx @ w > 0).astype(np.int32)
    stream = sys.argv[1] == "stream"
    cfg = FLConfig(
        n_clients=M, rounds=1, local_epochs=1, batch_size=PER, lr=0.01,
        b_mode="fixed", b_init=0.1, pack_chunk=512,
        client_chunk=2048 if stream else 0, stateless_clients=stream,
    )
    ctx = R.make_context(
        cfg, init_mlp(jax.random.PRNGKey(0), in_dim=DIM, hidden=HID, classes=2),
        functools.partial(xent_loss, mlp_logits),
        functools.partial(accuracy, mlp_logits), cx, cy,
        {"x": cx[0], "y": cy[0]},
    )
    _, traj = R.run_rounds(
        ctx, R.cell_params(cfg), jax.random.PRNGKey(0),
        R.init_run_state(ctx), with_acc=False,
    )
    jax.block_until_ready(traj)
    rss = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss // 1024
    print(f"STREAM_OK maxrss_mb={rss} loss={float(traj['loss'][-1]):.4f}")
    """
)


def _rss_child(mode: str) -> subprocess.CompletedProcess:
    env = dict(os.environ)
    env["PYTHONPATH"] = (
        os.path.join(os.path.dirname(__file__), "..", "src")
        + os.pathsep
        + env.get("PYTHONPATH", "")
    )
    # Drop any inherited device-count flag (repro.launch.dryrun writes 512
    # into os.environ when another test imports it): 512 virtual devices'
    # thread stacks alone would exhaust the child's address-space cap.
    env["XLA_FLAGS"] = " ".join(
        f
        for f in env.get("XLA_FLAGS", "").split()
        if "xla_force_host_platform_device_count" not in f
    )
    return subprocess.run(
        [sys.executable, "-c", _RSS_CHILD, mode],
        capture_output=True,
        text=True,
        env=env,
        timeout=600,
    )


def test_stream_smoke_rss_capped():
    """M = 60k x d = 4866 under a 4 GB RLIMIT_AS: the chunked round must
    complete (resident set ~ chunk * d, ~0.6 GB measured) while the dense
    round — whose (M, d) f32 state alone is ~1.2 GB before training
    intermediates — dies OOM under the same cap. This is the acceptance
    subprocess the CI ``stream-smoke`` job runs."""
    res = _rss_child("stream")
    assert res.returncode == 0, res.stderr[-2000:]
    assert "STREAM_OK" in res.stdout, res.stdout

    dense = _rss_child("dense")
    assert dense.returncode != 0, (
        "dense round unexpectedly fit under the cap:\n" + dense.stdout
    )
