"""Cross-device rounds of the paper's CNN through the program's
``FLSimulation`` (``repro.fl.runtime`` -> ``repro.fl.rounds.fl_round``).

The loop is ``FLSimulation.run``'s: per round split the key, sample the
round's batches, call the jitted round, record the round in the privacy
ledger, and evaluate on the test set every ``eval_every`` rounds. The
harness times each round to ``block_until_ready`` of the round state.

Set-up makes the weights, the label-skewed client data and the test set
on the device from the seed, builds the simulation, drives the first
``CHECKED_ROUNDS`` rounds (compiling the round once) and warms the
evaluation. Those rounds' losses, b, global-model updates and vote counts
are what the check compares with the plain reference
(``bench.reference.cnn``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.flatten_util import ravel_pytree

from bench import flops
from bench.drivers.xsilo import norm_gap
from bench.harness import Check, limit_of
from bench.reference import cnn as ref
from bench.traffic import generators as gen

CHECKED_ROUNDS = 3
# faults a broken round can have; none crosses chips in a one-chip cell
FAULTS = ("state_unchanged", "half_batch", "altered_token", "altered_answer")
KERNELS = {
    "prox_sgd": "prox_sgd_2d",
    "stoch_quant": "stoch_quant_pack_2d",
    "bit_count": "bit_count_2d",
}


def make(ctx):
    return XDev(ctx)


def participation(population: int, cohort: int) -> float:
    """The participation fraction whose cohort, int(population * p), is
    exactly ``cohort``."""
    p = (cohort + 0.5) / population
    assert int(population * p) == cohort
    return p


class XDev:
    def __init__(self, ctx):
        self.ctx = ctx
        self.cfg = ctx.cell.config
        self.tr = ctx.cell.traffic
        self.hp = dict(self.cfg["protocol"])
        self.hp.update(
            cohort=self.tr["cohort"], local_epochs=self.tr["local_epochs"],
        )
        self.readings = {}

    def _data(self):
        cfg, tr = self.cfg, self.tr
        k = gen.seed_key(self.ctx.seed)
        kw, kp, kc, kt, self.run_key = jax.random.split(k, 5)

        @jax.jit
        def make_all(kw, kp, kc, kt):
            protos = gen.image_prototypes(
                kp, cfg["classes"], cfg["image"], cfg["in_channels"]
            )
            cx, cy = gen.label_skew_clients(
                kc, protos, tr["population"], tr["samples_per_client"],
                tr["classes_per_client"], tr["noise"],
            )
            test = gen.test_set(kt, protos, tr["test_samples"], tr["noise"])
            return ref.init_weights(kw, cfg), cx, cy, test

        return make_all(kw, kp, kc, kt)

    def setup(self):
        from repro.fl import FLConfig, FLSimulation
        from repro.models.vision import accuracy, cnn_logits, xent_loss

        span = self.ctx.span
        cfg, tr, hp = self.cfg, self.tr, self.hp
        with span("setup.data"):
            w0, cx, cy, test = self._data()
            jax.block_until_ready(cx)
        self.w0_flat = np.asarray(ravel_pytree(w0)[0])
        with span("setup.build"):
            fl = FLConfig(
                n_clients=tr["population"],
                participation=participation(tr["population"], tr["cohort"]),
                local_epochs=tr["local_epochs"],
                batch_size=hp["batch_size"],
                lr=hp["lr"], momentum=hp["momentum"], lam=hp["lam"],
                byz_frac=hp["byz_frac"], attack=hp["attack"],
                dp_epsilon=hp["dp_epsilon"], l1_sensitivity=hp["l1_sensitivity"],
                b_mode="dynamic", b_init=hp["b_init"],
                aggregator=hp["aggregator"], use_kernels=hp["use_kernels"],
                rounds=CHECKED_ROUNDS,
            )
            if fl.n_active != tr["cohort"] or fl.bctrl.up != hp["b_up"] or (
                    fl.bctrl.down != hp["b_down"]):
                raise ValueError("the program's FLConfig departs from the configuration")
            self.sim = FLSimulation(
                fl, w0, functools.partial(xent_loss, cnn_logits),
                functools.partial(accuracy, cnn_logits), cx, cy, test,
            )
        self.key = self.run_key
        self.t = 0
        theta1 = None
        losses, bs = [], []
        for r in range(CHECKED_ROUNDS):
            w_prev = np.asarray(self.sim.w_global)
            with span(f"setup.round{r}"):
                out = self.round()
            losses.append(self._loss)
            bs.append(float(self.sim.b_state.b))
            if r == 0:
                theta1 = np.asarray(self.sim.w_global) - w_prev
            if not out["ok"]:
                break
        with span("setup.eval"):
            self.sim.evaluate()
        self.readings = {
            "loss": losses, "b": bs, "theta1": theta1,
            "change3": np.asarray(self.sim.w_global) - self.w0_flat,
        }

    # -- the timed path ------------------------------------------------------

    def round(self) -> dict:
        span = self.ctx.span
        sim = self.sim
        with span("batch"):
            self.key, kb, kr = jax.random.split(self.key, 3)
            batches = sim._round_batches(kb)
        with span("round"):
            sim.state, metrics = sim._round(kr, sim.state, batches)
        with span("sync"):
            jax.block_until_ready(sim.state)
        with span("ledger"):
            sim.ledger.record_round()
        self.t += 1
        if self.t % self.cfg["eval_every"] == 0:
            with span("eval"):
                sim.evaluate()
        self._loss = float(metrics["loss"])
        steps = self.steps()
        return {
            "clients": self.tr["cohort"],
            "samples": self.tr["cohort"] * steps * self.hp["batch_size"],
            "ok": bool(np.isfinite(self._loss)),
        }

    def steps(self) -> int:
        return max(self.tr["local_epochs"] * self.tr["samples_per_client"]
                   // self.hp["batch_size"], 1)

    def facts(self) -> dict:
        d = flops.cnn_param_count(self.cfg)
        m, steps, bsz = self.tr["cohort"], self.steps(), self.hp["batch_size"]
        fwd = flops.cnn_forward_flops_per_sample(self.cfg)
        return {
            "params": d,
            "clients_per_round": m,
            # training (forward + backward) plus the two forward passes of
            # each client's loss vote
            "model_flops_per_round": m * (steps * bsz * 3 * fwd + 2 * bsz * fwd),
            "kernel_bytes_per_round": {
                "prox_sgd": m * steps * flops.prox_sgd_bytes(d),
                "stoch_quant": m * flops.stoch_quant_pack_bytes(d),
                "bit_count": flops.bit_count_bytes(m, d),
            },
            "kernels": dict(KERNELS) if self.hp["use_kernels"] else {},
        }

    def release(self):
        if hasattr(self, "sim"):
            del self.sim

    # -- the check -----------------------------------------------------------

    def reference_readings(self, precision: str = "f32", fault: str | None = None) -> dict:
        """The reference's readings over the checked rounds, from the seed;
        ``fault`` plants one of ``FAULTS`` in it."""
        cfg, tr = self.cfg, self.tr
        w0, cx, cy, _ = self._data()
        w0 = ravel_pytree(w0)[0]
        state = (w0, jnp.tile(w0[None], (tr["population"], 1)),
                 jnp.float32(self.hp["b_init"]))
        key = self.run_key
        losses, bs, counts1, bwire1 = [], [], None, None
        cfg_items = tuple(sorted((k, v) for k, v in cfg.items()
                                 if isinstance(v, (int, float, str))))
        hp_items = tuple(sorted((k, v) for k, v in self.hp.items()
                                if isinstance(v, (int, float))))
        for r in range(CHECKED_ROUNDS):
            key, kb, kr = jax.random.split(key, 3)
            state, loss, counts, b_wire = ref.fl_round(
                state, kb, kr, cx, cy, cfg_items, hp_items, precision, fault
            )
            losses.append(float(loss))
            bs.append(float(state[2]))
            if r == 0:
                counts1 = np.asarray(counts)
                bwire1 = float(b_wire)
                theta1 = np.asarray(state[0]) - np.asarray(w0)
        return {
            "loss": losses, "b": bs, "theta1": theta1, "counts1": counts1,
            "b_wire1": bwire1, "change3": np.asarray(state[0]) - np.asarray(w0),
        }

    def check(self) -> list:
        values = self.values(self.readings, self.reference_readings())
        return [Check(k, v, limit_of(self.ctx.cell.limits, k)) for k, v in values.items()]

    # the control: the reference one precision below the configuration's
    LOWER_PRECISION = "bf16"

    def prepare(self):
        """Nothing to prepare: the reference makes its own data."""

    def values(self, got: dict, want: dict) -> dict:
        return compare(got, want, self.cfg, self.hp)


def leaf_norms(flat: np.ndarray, cfg: dict) -> np.ndarray:
    return np.array([
        np.linalg.norm(flat[a:b].astype(np.float64))
        for _, a, b in ref.leaf_slices(cfg)
    ])


def compare(got: dict, want: dict, cfg: dict, hp: dict) -> dict:
    """The numbers the check compares, by name."""
    n = len(got["loss"])
    loss_gap = (
        max(abs(a - b) for a, b in zip(got["loss"], want["loss"]))
        if n == CHECKED_ROUNDS else float("inf")
    )
    m = hp["cohort"]
    # The program's round-1 update is Eq. 13 of its counts; its counts
    # follow from it exactly (one count moves theta by 2 b' / M, far above
    # the rounding of w + theta).
    counts_got = np.rint(
        (got["theta1"] / want["b_wire1"] * m + m) / 2.0
    ).astype(np.int64)
    values = {
        "loss_gap": loss_gap,
        "update1_gap": norm_gap(leaf_norms(got["theta1"], cfg),
                                leaf_norms(want["theta1"], cfg)),
        "change3_gap": norm_gap(leaf_norms(got["change3"], cfg),
                                leaf_norms(want["change3"], cfg)),
        "counts1_mismatch": float(np.mean(counts_got != want["counts1"])),
    }
    return values
