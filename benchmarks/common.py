"""Shared benchmark harness utilities.

Two ways to run FL scenarios from a benchmark module:

* :func:`run_fl` — one sequential :class:`repro.fl.FLSimulation` (kept as
  the reference driver and for parity/timing comparisons);
* :func:`campaign_task` — the task provider that plugs the same
  classification task into the vectorized campaign engine
  (:func:`repro.sim.run_campaign`), which is how the figure/table grids
  run by default.
"""

from __future__ import annotations

import functools
import os
import sys
import time

import jax
import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.data import make_classification, partition_label_skew  # noqa: E402
from repro.fl import FLConfig, FLSimulation  # noqa: E402
from repro.models.vision import accuracy, init_mlp, mlp_logits, xent_loss  # noqa: E402
from repro.sim import Task  # noqa: E402

enable_compile_cache()

# Benchmark scale (CPU container): paper protocol at reduced scale.
ROUNDS = int(os.environ.get("PROBIT_BENCH_ROUNDS", "60"))
N_TRAIN = 3000
PER_CLIENT = 100


@functools.lru_cache(maxsize=None)
def task(n_clients: int, classes_per_client: int = 2, seed: int = 0):
    (xtr, ytr), (xte, yte) = make_classification(seed, n_train=N_TRAIN, n_test=600)
    parts = partition_label_skew(ytr, n_clients, classes_per_client, PER_CLIENT, seed)
    cx = np.stack([xtr[i] for i in parts])
    cy = np.stack([ytr[i] for i in parts])
    return cx, cy, {"x": xte, "y": yte}


@functools.lru_cache(maxsize=None)
def _mlp_p0(hidden: int = 48):
    return init_mlp(jax.random.PRNGKey(0), hidden=hidden)


def campaign_task(cfg: FLConfig) -> Task:
    """Campaign-engine task provider for the benchmark classification task.

    Same data, partition, and initial model as :func:`run_fl`, keyed on
    the cell's ``n_clients`` (cached), so a campaign cell at a fixed seed
    reproduces the sequential driver bit for bit.
    """
    cx, cy, test = task(cfg.n_clients, 2)
    return Task(
        init_params=_mlp_p0(),
        loss_fn=functools.partial(xent_loss, mlp_logits),
        acc_fn=functools.partial(accuracy, mlp_logits),
        client_x=cx,
        client_y=cy,
        test=test,
    )


def run_fl(n_clients: int, rounds: int = None, classes_per_client: int = 2, **kw) -> FLSimulation:
    cx, cy, test = task(n_clients, classes_per_client)
    cfg = FLConfig(n_clients=n_clients, rounds=rounds or ROUNDS, local_epochs=2, **kw)
    p0 = _mlp_p0()
    sim = FLSimulation(
        cfg,
        p0,
        functools.partial(xent_loss, mlp_logits),
        functools.partial(accuracy, mlp_logits),
        cx,
        cy,
        test,
    )
    sim.run(eval_every=cfg.rounds)
    return sim


def timed(fn, *args, reps: int = 20, warmup: int = 3) -> float:
    """Median microseconds per call (jax-blocking)."""
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append((time.perf_counter() - t0) * 1e6)
    return float(np.median(ts))


def require_cpu_sweep(what: str) -> None:
    """Refuse a CPU virtual-device sweep anywhere but the CPU backend.

    Such a sweep spawns one child per device count, each forcing a CPU
    device count through ``XLA_FLAGS``. On an accelerator the flag means
    nothing, and the parent, having touched JAX, already holds the chip
    that every child would need (one process per chip)."""
    backend = jax.default_backend()
    if backend != "cpu":
        raise RuntimeError(
            f"{what} sweeps CPU virtual devices in child processes; it runs "
            f"on the CPU backend only, not on {backend} (this process holds "
            f"the chip)"
        )


def emit(name: str, us_per_call: float, derived: str):
    print(f"{name},{us_per_call:.1f},{derived}")
