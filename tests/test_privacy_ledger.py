"""Privacy-ledger subsystem + DP-accounting boundary regressions.

Covers the ISSUE-4 acceptance criteria:

* ``privacy_loss`` is finite for all ``delta in [-b, b]`` including the
  endpoints (where Eq. 5's probability is exactly 0/1);
* ``rounds_for_budget`` returns 0 when one round already busts the
  budget, and T at a budget exactly equal to the T-round cost;
* degenerate-input identities: ``rounds = 0`` reports eps = 0 under
  every accountant, and ``q = 1`` amplification is bit-identical to the
  unamplified per-round eps (no log/exp float drift);
* ledger invariants (monotone in rounds, monotone-decreasing in q,
  amplified <= unamplified per accountant) and the closed-form match
  after real runs through both ``FLSimulation`` and a ``run_campaign``
  grid over (participation, eps);
* the tier-1 smoke path of ``benchmarks/fig_privacy_amplification.py``
  (tiny grid, 2 rounds).
"""

import functools
import json
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.core import (
    ACCOUNTANTS,
    PrivacyLedger,
    advanced_composition,
    amplified_epsilon,
    basic_composition,
    privacy_loss,
    rounds_for_budget,
    subsampled_composition,
)
from repro.data import make_classification, partition_label_skew
from repro.fl import FLConfig, FLSimulation
from repro.models.vision import accuracy, init_mlp, mlp_logits, xent_loss
from repro.sim import CampaignSpec, Task, group_signature, run_campaign

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from benchmarks.fig_privacy_amplification import fig_privacy_spec  # noqa: E402


# ---------------------------------------------------------------------------
# Satellite 1: privacy_loss boundary regression
# ---------------------------------------------------------------------------


class TestPrivacyLossBoundary:
    def test_finite_at_exact_boundary(self):
        """delta = +-b exactly (binarize prob 0/1) must not produce inf/NaN."""
        b = jnp.full((4,), 0.05)
        delta_a = jnp.array([0.05, -0.05, 0.05, -0.05])
        delta_b = jnp.array([-0.05, 0.05, 0.0, -0.05])
        pl = privacy_loss(delta_a, delta_b, b)
        assert bool(jnp.isfinite(pl))

    def test_finite_on_full_range_grid(self):
        """Finite for every delta in [-b, b] including both endpoints."""
        b = jnp.float32(0.03)
        grid = jnp.linspace(-0.03, 0.03, 61)  # includes +-b exactly
        da, db = jnp.meshgrid(grid, grid)
        pl = jax.vmap(
            lambda a, c: privacy_loss(a[None], c[None], b[None])
        )(da.ravel(), db.ravel())
        assert bool(jnp.all(jnp.isfinite(pl)))

    def test_finite_beyond_range(self):
        """Out-of-range updates clip to the boundary and stay finite."""
        pl = privacy_loss(jnp.array([5.0]), jnp.array([-5.0]), jnp.array([0.01]))
        assert bool(jnp.isfinite(pl))

    def test_near_boundary_interior_loss_not_shrunk(self):
        """The clamp sits on the float32 probability-grid edges, so a
        representable interior probability — even one ulp from 0 — must
        pass through unclamped (no silent under-reporting)."""
        b = jnp.float32(1.0)
        # delta/b = -1 + 2^-24 is representable; Eq. 5 gives p = 2^-25,
        # the smallest realizable nonzero probability.
        da = jnp.float32(-1.0 + 2.0**-24)
        db = jnp.float32(0.0)
        pa = float(jnp.log(jnp.float32(2.0**-25)))
        expected = abs(pa - math.log(0.5))  # loss on the +1 outcome
        pl = float(privacy_loss(da[None], db[None], b[None]))
        assert pl == pytest.approx(expected, rel=1e-6)

    def test_interior_losses_unchanged_by_clamp(self):
        """The clamp only bites at the boundary: a Theorem-3-respecting b
        keeps probabilities far inside [1e-6, 1-1e-6], so the loss is
        still bounded by eps (the original Theorem-3 test contract)."""
        from repro.core import DPConfig, dp_b_floor

        key = jax.random.PRNGKey(0)
        eps, delta1 = 0.1, 2e-4
        delta_a = 0.01 * jax.random.normal(key, (32,))
        v = jax.random.normal(jax.random.fold_in(key, 1), (32,))
        delta_b = delta_a + v / jnp.sum(jnp.abs(v)) * delta1
        floor = dp_b_floor(
            jnp.maximum(jnp.abs(delta_a), jnp.abs(delta_b)).max(),
            DPConfig(eps, delta1),
        )
        pl = float(privacy_loss(delta_a, delta_b, jnp.full((32,), floor)))
        assert 0.0 < pl <= eps * 1.0001


# ---------------------------------------------------------------------------
# Satellite 2: rounds_for_budget boundaries
# ---------------------------------------------------------------------------


class TestRoundsForBudget:
    def test_zero_when_budget_below_one_round(self):
        eps = 0.1
        one_round = advanced_composition(eps, 1)[0]
        assert rounds_for_budget(one_round * 0.99, eps) == 0
        assert rounds_for_budget(0.0, eps) == 0
        assert rounds_for_budget(-1.0, eps) == 0

    def test_exactly_one_round(self):
        eps = 0.1
        one_round = advanced_composition(eps, 1)[0]
        assert rounds_for_budget(one_round, eps) == 1

    def test_budget_exactly_at_T_rounds(self):
        eps = 0.05
        for T in (2, 7, 31):
            budget = advanced_composition(eps, T)[0]
            assert rounds_for_budget(budget, eps) == T

    def test_returned_T_affordable_and_maximal(self):
        eps, budget = 0.1, 3.0
        t = rounds_for_budget(budget, eps)
        assert advanced_composition(eps, t)[0] <= budget
        assert advanced_composition(eps, t + 1)[0] > budget

    def test_disabled_dp_rejected(self):
        """eps_per_round <= 0 would make every horizon affordable — the
        old code spun the search loop to its 10M cap; now it raises."""
        with pytest.raises(ValueError, match="eps_per_round"):
            rounds_for_budget(1.0, 0.0)
        with pytest.raises(ValueError, match="eps_per_round"):
            rounds_for_budget(1.0, -0.1)


# ---------------------------------------------------------------------------
# Satellite 3: degenerate-input identities (property-tested)
# ---------------------------------------------------------------------------


class TestDegenerateIdentities:
    @settings(deadline=None, max_examples=30)
    @given(st.floats(1e-4, 2.0))
    def test_zero_rounds_is_zero_eps_every_accountant(self, eps):
        # zero mechanisms spend neither eps nor delta — matches the
        # ledger's empty event log exactly
        assert advanced_composition(eps, 0) == (0.0, 0.0)
        assert basic_composition(eps, 0) == 0.0
        assert subsampled_composition(eps, 0, 0.5) == 0.0
        led = PrivacyLedger(eps, 0.5)
        for acc in ACCOUNTANTS:
            assert led.compose(acc) == (0.0, 0.0)
            assert led.eps_at(0, acc) == 0.0
            assert led.trajectory(0, acc).shape == (0,)

    @settings(deadline=None, max_examples=30)
    @given(st.floats(1e-4, 2.0))
    def test_q1_amplification_bit_identical(self, eps):
        """q = 1 must short-circuit: no ln(1 + (e^eps - 1)) round-trip."""
        assert amplified_epsilon(eps, 1.0) == eps
        led = PrivacyLedger(eps, 1.0, "subsampled")
        led_basic = PrivacyLedger(eps, 1.0, "basic")
        led.record_round(5)
        led_basic.record_round(5)
        assert led.per_round_epsilon == eps
        assert led.eps_spent == led_basic.eps_spent
        assert np.array_equal(led.trajectory(9), led_basic.trajectory(9))

    @settings(deadline=None, max_examples=30)
    @given(st.floats(1e-4, 2.0), st.floats(0.01, 0.99))
    def test_amplification_strictly_tightens(self, eps, q):
        amp = amplified_epsilon(eps, q)
        assert 0.0 < amp < eps

    def test_edge_rates(self):
        assert amplified_epsilon(0.5, 0.0) == 0.0
        assert amplified_epsilon(0.0, 0.5) == 0.0
        assert amplified_epsilon(-1.0, 0.5) == 0.0


# ---------------------------------------------------------------------------
# Satellite 4a: ledger invariants
# ---------------------------------------------------------------------------


class TestLedgerInvariants:
    @settings(deadline=None, max_examples=20)
    @given(st.floats(1e-3, 1.0), st.floats(0.05, 1.0))
    def test_monotone_in_rounds(self, eps, q):
        for acc in ACCOUNTANTS:
            traj = PrivacyLedger(eps, q, acc).trajectory(12)
            assert np.all(np.diff(traj) > 0.0), acc

    @settings(deadline=None, max_examples=20)
    @given(st.floats(1e-3, 1.0))
    def test_monotone_decreasing_in_q(self, eps):
        qs = (0.1, 0.3, 0.6, 1.0)
        spent = []
        for q in qs:
            led = PrivacyLedger(eps, q, "subsampled")
            led.record_round(10)
            spent.append(led.eps_spent)
        assert all(a < b for a, b in zip(spent, spent[1:]))

    @settings(deadline=None, max_examples=20)
    @given(st.floats(1e-3, 1.0), st.floats(0.05, 0.95))
    def test_amplified_le_unamplified_every_accountant(self, eps, q):
        for acc in ACCOUNTANTS:
            sub, full = PrivacyLedger(eps, q, acc), PrivacyLedger(eps, 1.0, acc)
            sub.record_round(8)
            full.record_round(8)
            assert sub.eps_spent <= full.eps_spent, acc
        # and the subsampled accountant beats basic strictly at q < 1
        led = PrivacyLedger(eps, q)
        led.record_round(8)
        assert led.compose("subsampled")[0] < led.compose("basic")[0]

    def test_compose_matches_closed_form_trajectory(self):
        """Recording T homogeneous events == the closed-form curve, bit
        for bit (fsum of T copies is the correctly-rounded product)."""
        for acc in ACCOUNTANTS:
            led = PrivacyLedger(0.1, 0.5, acc)
            for t in range(1, 25):
                led.record_round()
                assert led.eps_spent == led.trajectory(t)[-1], (acc, t)

    def test_heterogeneous_events(self):
        led = PrivacyLedger(0.1, 0.5)
        led.record(0.1, 0.5)
        led.record(0.2, 1.0)
        assert led.compose("basic")[0] == pytest.approx(0.3)
        assert led.compose("subsampled")[0] == pytest.approx(
            amplified_epsilon(0.1, 0.5) + 0.2
        )
        # trajectory() follows the heterogeneous log, not the configured
        # homogeneous closed form — its last point IS eps_spent
        for acc in ACCOUNTANTS:
            traj = led.trajectory(accountant=acc)
            assert traj.shape == (2,)
            assert traj[-1] == led.compose(acc)[0], acc
            assert traj[0] == PrivacyLedger(0.1, 0.5, acc).eps_at(1), acc
        # record() validates like the constructor
        with pytest.raises(ValueError, match="q must be"):
            led.record(0.1, 1.5)
        led.record(-1.0)  # negative eps clamps to 0, like the constructor
        assert led.events[-1].epsilon == 0.0

    def test_validation(self):
        with pytest.raises(ValueError, match="accountant"):
            PrivacyLedger(0.1, accountant="zcdp")
        with pytest.raises(ValueError, match="q must be"):
            PrivacyLedger(0.1, q=1.5)
        with pytest.raises(ValueError, match="delta_slack"):
            PrivacyLedger(0.1, delta_slack=0.0)
        with pytest.raises(ValueError, match="accountant"):
            PrivacyLedger(0.1).compose("zcdp")


# ---------------------------------------------------------------------------
# Acceptance: the exact subsampled per-round numbers
# ---------------------------------------------------------------------------


class TestAcceptanceNumbers:
    def test_half_participation_eps_point_one(self):
        """participation=0.5, eps=0.1: per-round eps = ln(1+0.5(e^0.1-1))
        to 1e-12 and strictly below 0.1."""
        led = FLConfig(n_clients=20, participation=0.5, dp_epsilon=0.1).ledger()
        expect = math.log(1.0 + 0.5 * (math.exp(0.1) - 1.0))
        assert abs(led.per_round_epsilon - expect) < 1e-12
        assert led.per_round_epsilon < 0.1

    def test_full_participation_reproduces_conservative(self):
        """participation=1.0 reproduces the pre-ledger numbers exactly."""
        cfg = FLConfig(n_clients=20, participation=1.0, dp_epsilon=0.1, rounds=30)
        led = cfg.ledger()
        led.record_round(cfg.rounds)
        assert led.eps_spent == basic_composition(0.1, 30)

    def test_sampling_rate_uses_realized_cohort(self):
        """q comes from n_active/M (the floor the runtime actually takes),
        not the raw participation fraction."""
        cfg = FLConfig(n_clients=21, participation=0.5, dp_epsilon=0.1)
        assert cfg.n_active == 10
        assert cfg.sampling_rate == pytest.approx(10 / 21)
        assert FLConfig(n_clients=21, participation=1.0).sampling_rate == 1.0

    def test_config_validation(self):
        with pytest.raises(ValueError, match="dp_accountant"):
            FLConfig(dp_accountant="zcdp")
        FLConfig(dp_accountant="renyi")  # first-class since ISSUE 5
        with pytest.raises(ValueError, match="participation"):
            FLConfig(participation=0.0)
        with pytest.raises(ValueError, match="participation"):
            FLConfig(participation=1.5)

    def test_accountant_does_not_split_campaign_groups(self):
        """dp_accountant is host-side bookkeeping — cells differing only
        there must share one compiled program."""
        base = dict(n_clients=6, dp_epsilon=0.1, participation=0.5)
        assert group_signature(FLConfig(**base)) == group_signature(
            FLConfig(**base, dp_accountant="basic")
        )
        assert group_signature(FLConfig(**base)) != group_signature(
            FLConfig(**{**base, "dp_epsilon": 0.2})
        )


# ---------------------------------------------------------------------------
# ISSUE-5 satellite: the Rényi (moments) accountant
# ---------------------------------------------------------------------------


class TestRenyiAccountant:
    """RDP of randomized response, composed in the Rényi domain.

    The load-bearing property: the reported eps DOMINATES (is <=) the
    ``advanced`` DRV eps on every multi-round trajectory — renyi is a
    strict upgrade, never a looser bound — and is also <= ``basic``
    (the alpha -> inf endpoint of the RR curve is pure composition).
    """

    @settings(deadline=None, max_examples=40)
    @given(st.floats(1e-4, 4.0), st.integers(1, 400))
    def test_dominates_advanced_on_every_trajectory(self, eps, rounds):
        led = PrivacyLedger(eps, accountant="renyi")
        renyi = led.trajectory(rounds, "renyi")
        advanced = led.trajectory(rounds, "advanced")
        basic = led.trajectory(rounds, "basic")
        assert np.all(renyi <= advanced + 1e-12)
        assert np.all(renyi <= basic + 1e-12)
        assert np.all(renyi >= 0.0)

    def test_tightens_the_small_eps_multiround_regime(self):
        """The ROADMAP motivation: at eps ~ 0.1 over many rounds, renyi
        beats DRV strictly (and DRV already beats basic there)."""
        led = PrivacyLedger(0.1, accountant="renyi")
        renyi = led.eps_at(100, "renyi")
        advanced = led.eps_at(100, "advanced")
        basic = led.eps_at(100, "basic")
        assert renyi < advanced < basic

    @settings(deadline=None, max_examples=20)
    @given(st.floats(1e-3, 1.0))
    def test_rr_rdp_curve_shape(self, eps):
        from repro.core.ledger import _ALPHA_GRID, rr_renyi_divergence

        rdp = rr_renyi_divergence(eps, _ALPHA_GRID)
        assert np.all(rdp > 0.0) and np.all(np.isfinite(rdp))
        # bounded by the pure-DP level, approached as alpha -> inf
        assert np.all(rdp <= eps + 1e-12)
        assert rdp[-1] == pytest.approx(eps, rel=1e-3)
        assert np.all(np.diff(rdp) >= -1e-15)  # non-decreasing in alpha

    def test_compose_matches_trajectory_and_converts_at_delta_slack(self):
        led = PrivacyLedger(0.1, accountant="renyi")
        led.record_round(50)
        assert led.eps_spent == led.trajectory(50)[-1]
        assert led.delta_spent == led.delta_slack
        assert "renyi" in led.report()
        assert led.report()["renyi"]["eps"] == led.eps_spent

    def test_zero_eps_reports_zero(self):
        led = PrivacyLedger(0.0, accountant="renyi")
        led.record_round(10)
        assert led.eps_spent == 0.0 and led.delta_spent == 0.0
        assert np.all(led.trajectory(10) == 0.0)

    def test_heterogeneous_composition(self):
        """Per-event RDP curves sum; a (0.1, 0.3) log lands between its
        homogeneous brackets and below their basic sum."""
        led = PrivacyLedger(0.1, accountant="renyi")
        led.record(0.1)
        led.record(0.3)
        lo = PrivacyLedger(0.1, accountant="renyi").eps_at(2)
        hi = PrivacyLedger(0.3, accountant="renyi").eps_at(2)
        assert lo <= led.eps_spent <= hi
        assert led.eps_spent <= 0.4 + 1e-12

    def test_config_wires_renyi_through_ledger(self):
        cfg = FLConfig(dp_epsilon=0.1, dp_accountant="renyi", rounds=40)
        traj = cfg.ledger().trajectory(cfg.rounds)
        drv = FLConfig(
            dp_epsilon=0.1, dp_accountant="advanced", rounds=40
        ).ledger().trajectory(40)
        assert traj.shape == (40,)
        assert np.all(traj <= drv + 1e-12)


# ---------------------------------------------------------------------------
# Satellite 4b: end-to-end through FLSimulation and run_campaign
# ---------------------------------------------------------------------------


N, ROUNDS = 4, 3


@pytest.fixture(scope="module")
def tiny_task():
    (xtr, ytr), (xte, yte) = make_classification(0, n_train=400, n_test=100)
    parts = partition_label_skew(ytr, N, 2, 40, seed=1)
    return Task(
        init_params=init_mlp(jax.random.PRNGKey(0), hidden=8),
        loss_fn=functools.partial(xent_loss, mlp_logits),
        acc_fn=functools.partial(accuracy, mlp_logits),
        client_x=np.stack([xtr[i] for i in parts]),
        client_y=np.stack([ytr[i] for i in parts]),
        test={"x": xte, "y": yte},
    )


class TestLedgerEndToEnd:
    def test_flsimulation_records_and_reports(self, tiny_task):
        cfg = FLConfig(
            n_clients=N, rounds=ROUNDS, local_epochs=1,
            dp_epsilon=0.1, participation=0.5, b_mode="fixed",
        )
        sim = FLSimulation(
            cfg, tiny_task.init_params, tiny_task.loss_fn, tiny_task.acc_fn,
            tiny_task.client_x, tiny_task.client_y, tiny_task.test,
        )
        sim.run(eval_every=1)
        assert sim.ledger.rounds == ROUNDS
        expect = cfg.ledger().eps_at(ROUNDS)  # closed form
        assert sim.ledger.eps_spent == pytest.approx(expect, rel=1e-12)
        eps_hist = [h["eps_spent"] for h in sim.history]
        assert eps_hist == pytest.approx(
            list(cfg.ledger().trajectory(ROUNDS)), rel=1e-12
        )
        # a second run() keeps accumulating (one event per executed round)
        sim.run(rounds=2, eval_every=2)
        assert sim.ledger.rounds == ROUNDS + 2

    def test_campaign_grid_over_participation_and_eps(self, tiny_task):
        """The (participation x eps) grid carries eps_spent as a first-
        class metric matching the closed-form composition, and the
        cumulative trajectory lands in the campaign JSON."""
        spec = CampaignSpec.from_grid(
            base=dict(n_clients=N, rounds=ROUNDS, local_epochs=1, b_mode="fixed"),
            axes={"participation": (0.5, 1.0), "dp_epsilon": (0.1, 0.5)},
            seeds=(0, 1),
        )
        result = run_campaign(spec, lambda cfg: tiny_task)
        for cell_spec in spec.cells:
            cfg = spec.config(cell_spec)
            cell = result.cell(cell_spec.name)
            eps = cell.metrics["eps_spent"]
            assert eps.shape == (2, ROUNDS)
            assert np.array_equal(eps[0], eps[1])  # seed-independent
            assert np.all(np.diff(eps[0]) > 0)  # monotone in rounds
            np.testing.assert_allclose(
                eps[0], cfg.ledger().trajectory(ROUNDS), rtol=1e-12
            )
            assert cell.eps_spent() == pytest.approx(
                cfg.ledger().eps_at(ROUNDS), rel=1e-12
            )
        # participation=1.0 cells report today's conservative numbers...
        full = result.cell("participation=1.0|dp_epsilon=0.1")
        np.testing.assert_array_equal(
            full.metrics["eps_spent"][0], 0.1 * np.arange(1, ROUNDS + 1)
        )
        # ...and subsampling strictly tightens them at equal eps
        half = result.cell("participation=0.5|dp_epsilon=0.1")
        assert np.all(
            half.metrics["eps_spent"][0] < full.metrics["eps_spent"][0]
        )
        # the trajectory appears in the JSON artifact
        js = result.to_json()
        traj = js["cells"]["participation=0.5|dp_epsilon=0.1"][
            "trajectory_mean"]["eps_spent"]
        np.testing.assert_allclose(
            traj, spec.config(spec.cells[0]).ledger().trajectory(ROUNDS),
            rtol=1e-12,
        )

    def test_non_dp_cells_report_zero(self, tiny_task):
        spec = CampaignSpec.from_grid(
            base=dict(n_clients=N, rounds=2, local_epochs=1, b_mode="fixed"),
            axes={"participation": (0.5,)},
            seeds=(0,),
        )
        result = run_campaign(spec, lambda cfg: tiny_task)
        assert np.all(result.cells[0].metrics["eps_spent"] == 0.0)
        assert result.cells[0].eps_spent() == 0.0


# ---------------------------------------------------------------------------
# Satellite 6: benchmark smoke path (tiny grid, 2 rounds)
# ---------------------------------------------------------------------------


class TestAmplificationFigureSmoke:
    def test_tiny_grid_two_rounds(self, tiny_task, tmp_path):
        spec = fig_privacy_spec(
            rounds=2,
            participations=(0.5, 1.0),
            epsilons=(0.1,),
            aggregators=("probit_plus",),
            n_clients=N,
            seeds=(0,),
        )
        result = run_campaign(spec, lambda cfg: tiny_task)
        assert len(result.cells) == 2
        path = result.save(str(tmp_path / "fig_priv_smoke.json"))
        with open(path) as f:
            js = json.load(f)
        for cell_spec in spec.cells:
            cfg = spec.config(cell_spec)
            traj = js["cells"][cell_spec.name]["trajectory_mean"]["eps_spent"]
            np.testing.assert_allclose(
                traj, cfg.ledger().trajectory(2), rtol=1e-12
            )
        sub = js["cells"]["participation=0.5|dp_epsilon=0.1|aggregator=probit_plus"]
        full = js["cells"]["participation=1.0|dp_epsilon=0.1|aggregator=probit_plus"]
        assert sub["trajectory_mean"]["eps_spent"][-1] < \
            full["trajectory_mean"]["eps_spent"][-1]
