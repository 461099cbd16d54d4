"""Campaign throughput vs device count: cells/sec with the batch axis
sharded over N virtual CPU devices.

The ROADMAP's "device-sharded campaigns at scale" item, measured: one
fused campaign grid (every cell in a single compiled program — the
planner's fused heterogeneous-M path) is executed with
``run_campaign(..., shard=True)`` under ``XLA_FLAGS=
--xla_force_host_platform_device_count=N`` for a sweep of N. Each device
count runs in a **subprocess** because the flag must be set before jax
initializes its platform; the child re-enters this module with
``--inner`` and prints one JSON line the parent collects.

Per device count the child warms the AOT compile cache, then times
``REPS`` executions and reports the best cells/sec (steady-state
throughput; compile excluded by the warm-up). The parent emits one row
per device count, writes ``reports/fig_campaign_throughput.json``, and
reports ``monotone_1_to_max`` — throughput at the max device count must
be >= throughput at 1 device (the 1 -> 4 endpoint comparison; interior
counts are reported but not gated, since on an N-core host the
intermediate points can jitter within noise). This is the acceptance
line for the sharded execution path, asserted by the nightly test in
``tests/test_plan.py``.

  PYTHONPATH=src python -m benchmarks.fig_campaign_throughput
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

DEVICE_COUNTS = (1, 2, 4)
REPS = 3
# Smaller default than the figure benchmarks: the sweep runs the same
# grid once per device count (plus warm-up).
ROUNDS = int(os.environ.get("PROBIT_BENCH_ROUNDS", "60")) // 3 or 1
SEEDS = (0, 1, 2, 3)


def throughput_spec(rounds: int | None = None):
    """A fused grid: (M x lr) cells, all in ONE compiled program.

    n_clients spans 8..16 so the planner's heterogeneous-M fusion is on
    the measured path; 8 cells x 4 seeds = 32 batch elements shard evenly
    over 1/2/4 devices.
    """
    from repro.sim import CampaignSpec

    return CampaignSpec.from_grid(
        base=dict(rounds=rounds or ROUNDS, local_epochs=2, b_mode="fixed"),
        axes={"n_clients": (8, 12, 16, 10), "lr": (0.01, 0.02)},
        seeds=SEEDS,
    )


def run_inner(rounds: int | None = None, reps: int = REPS) -> dict:
    """Measure this process's device configuration (child entry point)."""
    import jax

    from .common import campaign_task
    from repro.sim import plan_campaign, run_campaign

    spec = throughput_spec(rounds)
    plan = plan_campaign(spec, shard=True)
    run_campaign(spec, campaign_task, shard=True, with_acc=False)  # warm-up
    best = None
    for _ in range(reps):
        t0 = time.perf_counter()
        result = run_campaign(spec, campaign_task, shard=True, with_acc=False)
        wall = time.perf_counter() - t0
        cps = len(spec.cells) * len(spec.seeds) / wall
        if best is None or cps > best["cells_per_sec"]:
            best = {
                "cells_per_sec": cps,
                "wall_s": wall,
                "n_devices": jax.device_count(),
                "n_programs": plan.n_programs,
                "n_fused": plan.n_fused,
                "groups": result.groups,
            }
    return best


def main(rounds: int | None = None, device_counts=DEVICE_COUNTS) -> dict:
    from .common import emit, require_cpu_sweep

    require_cpu_sweep("fig_campaign_throughput")

    out: dict = {"rounds": rounds or ROUNDS, "sweep": {}}
    for n_dev in device_counts:
        env = dict(os.environ)
        # Drop any inherited device-count flag (repro.launch.dryrun sets
        # 512 into os.environ when imported) — ours must win.
        inherited = [
            f
            for f in env.get("XLA_FLAGS", "").split()
            if "xla_force_host_platform_device_count" not in f
        ]
        env["XLA_FLAGS"] = " ".join(
            [f"--xla_force_host_platform_device_count={n_dev}", *inherited]
        )
        env["PYTHONPATH"] = (
            os.path.join(os.path.dirname(__file__), "..", "src")
            + os.pathsep
            + env.get("PYTHONPATH", "")
        )
        cmd = [
            sys.executable, "-m", "benchmarks.fig_campaign_throughput",
            "--inner", "--rounds", str(rounds or ROUNDS),
        ]
        res = subprocess.run(
            cmd, capture_output=True, text=True, env=env,
            cwd=os.path.join(os.path.dirname(__file__), ".."),
        )
        if res.returncode != 0:
            raise RuntimeError(
                f"device-count={n_dev} child failed:\n{res.stderr[-3000:]}"
            )
        payload = json.loads(res.stdout.strip().splitlines()[-1])
        assert payload["n_devices"] == n_dev, payload
        out["sweep"][n_dev] = payload
        emit(
            f"campaign_throughput_dev{n_dev}",
            1e6 / payload["cells_per_sec"],
            f"cells_per_sec={payload['cells_per_sec']:.2f};"
            f"programs={payload['n_programs']};fused={payload['n_fused']}",
        )

    counts = sorted(out["sweep"])
    thr = [out["sweep"][k]["cells_per_sec"] for k in counts]
    out["monotone_1_to_max"] = bool(thr[-1] >= thr[0])
    emit(
        "campaign_throughput_scaling",
        1e6 / thr[-1],
        f"speedup_{counts[0]}to{counts[-1]}={thr[-1] / thr[0]:.2f}x;"
        f"monotone={out['monotone_1_to_max']}",
    )

    report = os.path.join(
        os.path.dirname(__file__), "..", "reports",
        "fig_campaign_throughput.json",
    )
    os.makedirs(os.path.dirname(report), exist_ok=True)
    with open(report, "w") as f:
        json.dump(out, f, indent=1, default=str)
    return out


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--inner", action="store_true")
    ap.add_argument("--rounds", type=int, default=None)
    ap.add_argument("--reps", type=int, default=REPS)
    args = ap.parse_args()
    if args.inner:
        payload = run_inner(args.rounds, args.reps)
        print(json.dumps(payload, default=str))
    else:
        main(args.rounds)
