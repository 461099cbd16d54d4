"""PRoBit+ reproduction package.

Sets ``jax_threefry_partitionable`` once, at import, for every consumer:
partitionable threefry makes each random draw a pure function of
``(key, element index)`` — independent of the array's total shape — which
two subsystems rely on:

* the campaign planner's **fused heterogeneous-M groups**
  (:mod:`repro.sim.plan`): the client axis is padded to the group max, and
  a cell's real clients must draw exactly the batches/quantizer bits they
  would in an unpadded program (prefix-stable ``split`` / ``randint`` /
  ``uniform``), so fused and per-group execution agree;
* **device sharding** of campaign batches: random ops lower to
  per-element counter hashes with no cross-device layout dependence.

This is also the installed JAX's default stream; pinning it keeps seeds
stable should that default change. (Trajectories differ from the legacy
stream; every seed-calibrated test threshold was re-verified green on the new stream
when this landed — the PR-3 20-seed calibrations held without retuning.)
"""

import jax as _jax

_jax.config.update("jax_threefry_partitionable", True)
