"""The check fails a run whose timed path is broken underneath, and its
control (the reference one precision lower in the program's place)
fails it too. Each run drives the whole harness on the CPU at a tiny
size, with the cells' own limits; only the look for a chip is skipped."""

from __future__ import annotations

import dataclasses

import jax
import pytest

from bench.drivers.xsilo import flip_largest_update

from conftest import run_tiny


def _step_fault(kind):
    """A make_fl_train_step whose step is broken in one way."""
    import repro.launch.fl_step as fs

    real_make = fs.make_fl_train_step

    def make(cfg, fl, specs):
        real = real_make(cfg, fl, specs)

        def step(params, b, batch, key):
            if kind == "state_unchanged":
                _, _, met = real(params, b, batch, key)
                return params, b, met
            if kind == "half_batch":
                # half of the cohort's silos left out, the mean over the rest
                batch = jax.tree.map(lambda a: a[: a.shape[0] // 2], batch)
                return real(params, b, batch, key)
            if kind == "altered_answer":
                new, b_new, met = real(params, b, batch, key)
                return flip_largest_update(params, new), b_new, met
            # one token of one silo's batch altered where the feed makes it
            t = batch["tokens"]
            t = t.at[0, 0, 0, 0, 3].set((t[0, 0, 0, 0, 3] + 1) % cfg.vocab)
            return real(params, b, {"tokens": t, "labels": t}, key)

        return step

    return fs, "make_fl_train_step", make


def _round_fault(kind):
    """The cross-device round broken in one way."""
    import repro.fl.rounds as rounds

    if kind in ("state_unchanged", "altered_answer"):
        real_fn = rounds.round_fn

        def round_fn(ctx):
            real = real_fn(ctx)

            def broken(ctx_, params, key, state, batches):
                new, met = real(ctx_, params, key, state, batches)
                if kind == "state_unchanged":
                    return state, met
                # the largest weight's global update negated
                sizes = [x.size for x in jax.tree.leaves(ctx_.unravel(state.w_global))]
                i = sizes.index(max(sizes))
                a, z = sum(sizes[:i]), sum(sizes[: i + 1])
                theta = new.w_global - state.w_global
                w = state.w_global + theta.at[a:z].multiply(-1.0)
                return dataclasses.replace(new, w_global=w), met

            return broken

        return rounds, "round_fn", round_fn
    real_batches = rounds.round_batches

    def round_batches(ctx, key):
        out = real_batches(ctx, key)
        if kind == "half_batch":
            # half of every client's batch left out, the mean over the rest
            return {k: v[:, :, : v.shape[2] // 2] for k, v in out.items()}
        y = out["y"]  # one label of every client's first batch altered
        return {"x": out["x"], "y": y.at[:, 0, 0].set((y[:, 0, 0] + 1) % 10)}

    return rounds, "round_batches", round_batches


# Each cell's faults that its check must catch. One token altered in one
# silo's batch is not among the cross-silo cell's: the one-bit wire hides
# a single sample by design (PERF.md gives its reading).
CELL_FAULTS = [
    ("xsilo-tiny", f) for f in ("state_unchanged", "half_batch", "altered_answer")
] + [
    ("xdev-tiny", f)
    for f in ("state_unchanged", "half_batch", "altered_token", "altered_answer")
]


@pytest.mark.parametrize("cell,fault", CELL_FAULTS)
def test_a_broken_timed_path_is_not_correct(tiny_checkout, monkeypatch, cell, fault):
    root, bench = tiny_checkout
    module, name, broken = (
        _step_fault(fault) if cell.startswith("xsilo") else _round_fault(fault)
    )
    monkeypatch.setattr(module, name, broken)
    rc, res = run_tiny(root, bench, cell, seed=17, seconds=0.1)
    assert rc == 0
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("cell", ["xsilo-tiny", "xdev-tiny"])
def test_the_control_is_not_correct(tiny_checkout, cell):
    """The reference computed one precision lower than the configuration
    (fp8 matmul operands for the bf16 decoder, bf16 for the f32 CNN) put
    in the program's place fails one of the cell's limits."""
    from bench import controls, harness

    root, bench = tiny_checkout
    limits = harness.find_cell(root, cell, bench).limits
    recs = list(controls.readings(cell, [], [23], root=root, bench_dir=bench,
                                  require_tpu=False))
    assert [r["kind"] for r in recs] == ["control"]
    values = recs[0]["values"]
    assert any(values[k] > limits[k] for k in values), values
