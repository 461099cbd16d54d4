"""Each driver runs its cell end to end at a tiny size on the CPU: set-up
with the checked rounds, a window of rounds, the reference, the check."""

from __future__ import annotations

import pytest

from conftest import run_tiny


@pytest.mark.parametrize("cell", ["xsilo-tiny", "xdev-tiny"])
def test_driver_runs_rounds_and_is_correct(tiny_checkout, cell):
    root, bench = tiny_checkout
    rc, res = run_tiny(root, bench, cell, seed=2**31 + 11, seconds=0.3)
    assert rc == 0
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert res["metrics"]["setup_s"]["value"] > 0
    assert res["checks"]["window_compiles"]["value"] == 0
    key = "tokens_per_s" if cell.startswith("xsilo") else "clients_per_s"
    assert res["metrics"][key]["value"] > 0
    assert list(res)[-1] == "checks"


def test_same_seed_same_readings(tiny_checkout):
    """The seed fixes the inputs and weights: two runs of one seed check
    the same numbers."""
    root, bench = tiny_checkout
    _, a = run_tiny(root, bench, "xdev-tiny", seed=5, seconds=0.1)
    _, b = run_tiny(root, bench, "xdev-tiny", seed=5, seconds=0.1)
    assert a["checks"] == b["checks"]
