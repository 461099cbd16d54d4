"""Streaming client-chunk scaling: million-client PRoBit+ rounds on CPU.

The ROADMAP's "streaming client aggregation" item, measured: an M-sweep
up to 1e6 clients where each cell runs the chunked round
(``client_chunk > 0`` + ``stateless_clients``) through the campaign
engine, so resident memory stays O(chunk * d/8) instead of O(M * d/8).
Per M the figure reports

* ``clients_per_sec`` for the **dense** round (only up to ``DENSE_MAX`` —
  beyond that the (M, d) update matrix stops fitting comfortably),
  the **streaming** round, and (at the largest M) the **sharded
  streaming** round, where the chunk's client axis is split over
  virtual CPU devices and vote counts are psum-reduced;
* ``peak_bytes_est`` — the executor's per-device resident-wire estimate
  (``sim.campaign`` group stats) for the streaming vs dense path;
* ``theta_mse`` averaged over rounds.

With b fixed above the update range the PRoBit+ estimate is unbiased and
Theorem 1 gives per-coordinate variance ~ b^2 / M, so the log-log
theta_mse slope across the sweep must sit in ``SLOPE_WINDOW`` (~ -1);
``main`` asserts this — it is the acceptance line for the streaming
execution path at scales the dense round cannot reach.

The sharded point runs in a **subprocess** (the
``--xla_force_host_platform_device_count`` flag must be set before jax
initializes); the child re-enters this module with ``--inner`` and
prints one JSON line, mirroring ``fig_campaign_throughput``.

  PYTHONPATH=src python -m benchmarks.fig_streaming_clients
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import resource
import subprocess
import sys
import time

import numpy as np

M_GRID = tuple(
    int(m)
    for m in os.environ.get(
        "PROBIT_STREAM_M_GRID", "1000,10000,100000,1000000"
    ).split(",")
)
DENSE_MAX = 10_000  # largest M the dense (M, d) round is run at
CHUNK = 4096  # streaming client-chunk size (cohort rows resident at once)
PACK = 512  # pack_chunk: d padded to 512 -> 64-byte wire rows
ROUNDS = int(os.environ.get("PROBIT_STREAM_ROUNDS", "2"))
SHARD_DEVICES = int(os.environ.get("PROBIT_STREAM_DEVICES", "4"))
SLOPE_WINDOW = (-1.35, -0.65)

DIM = 8
PER_CLIENT = 2
HIDDEN = 16


@functools.lru_cache(maxsize=None)
def stream_task(m: int, seed: int = 0):
    """Synthetic per-client data at cross-device scale.

    Hyperplane labels over Gaussian features with a per-client mean
    shift (mild heterogeneity); at M=1e6 the arrays are ~72 MB — the
    data fits, it is the dense update matrix that does not.
    """
    rng = np.random.default_rng(seed)
    w = rng.standard_normal(DIM).astype(np.float32)

    def draw(rows, per, shift):
        x = rng.standard_normal((rows, per, DIM), dtype=np.float32)
        if shift:
            x += 0.3 * rng.standard_normal((rows, 1, 1)).astype(np.float32)
        y = (x @ w > 0).astype(np.int32)
        return x, y

    cx, cy = draw(m, PER_CLIENT, shift=True)
    tx, ty = draw(1, 512, shift=False)
    return cx, cy, {"x": tx[0], "y": ty[0]}


def _overrides(m: int, stream: bool) -> dict:
    ov = dict(n_clients=m)
    if stream:
        ov.update(client_chunk=min(CHUNK, m), stateless_clients=True)
    return ov


def _base(rounds: int) -> dict:
    # Fixed b above the update range -> unbiased compressor (Theorem 1),
    # so theta_mse is pure O(1/M) aggregation error.
    return dict(
        rounds=rounds,
        local_epochs=1,
        batch_size=PER_CLIENT,
        lr=0.01,
        b_mode="fixed",
        b_init=0.1,
        pack_chunk=PACK,
    )


def _init_params():
    import jax

    from repro.models.vision import init_mlp

    return init_mlp(jax.random.PRNGKey(0), in_dim=DIM, hidden=HIDDEN, classes=2)


def _task_fn(cfg):
    from repro.models.vision import accuracy, mlp_logits, xent_loss
    from repro.sim import Task

    cx, cy, test = stream_task(cfg.n_clients)
    return Task(
        init_params=_init_params(),
        loss_fn=functools.partial(xent_loss, mlp_logits),
        acc_fn=functools.partial(accuracy, mlp_logits),
        client_x=cx,
        client_y=cy,
        test=test,
    )


def run_cell(m: int, rounds: int, stream: bool) -> dict:
    """One single-cell campaign at M clients; timed on the warm rerun."""
    from repro.sim import CampaignSpec, CellSpec, run_campaign
    from repro.sim.plan import CompileCache, plan_campaign

    spec = CampaignSpec(
        base=_base(rounds),
        cells=(CellSpec(f"M={m}", _overrides(m, stream)),),
        seeds=(0,),
    )
    # The dense baseline must stay dense: past STREAM_M_THRESHOLD the
    # default planner would silently stream the cell.
    plan = None if stream else plan_campaign(spec, stream_threshold=1 << 62)
    cache = CompileCache()
    run_campaign(spec, _task_fn, plan=plan, with_acc=False, compile_cache=cache)
    t0 = time.perf_counter()
    result = run_campaign(
        spec, _task_fn, plan=plan, with_acc=False, compile_cache=cache
    )
    wall = time.perf_counter() - t0
    g = result.groups[0]
    return {
        "m": m,
        "mode": "stream" if stream else "dense",
        "clients_per_sec": m * rounds / wall,
        "wall_s": wall,
        "theta_mse": float(np.mean(result.cells[0].metrics["theta_mse"])),
        "client_chunk": g["client_chunk"],
        "peak_bytes_est": g["peak_bytes_est"],
    }


def run_inner(m: int, rounds: int) -> dict:
    """Sharded streaming round (child entry point): the chunk's client
    axis is split over this process's devices, counts psum-reduced."""
    import jax

    from repro.fl import FLConfig
    from repro.fl import rounds as R
    from repro.models.vision import accuracy, mlp_logits, xent_loss

    cx, cy, test = stream_task(m)
    cfg = FLConfig(
        **_base(rounds),
        **_overrides(m, stream=True),
        stream_shard=True,
    )
    ctx = R.make_context(
        cfg,
        _init_params(),
        functools.partial(xent_loss, mlp_logits),
        functools.partial(accuracy, mlp_logits),
        cx,
        cy,
        test,
    )
    params = R.cell_params(cfg)
    key = jax.random.PRNGKey(0)
    state = R.init_run_state(ctx)
    jax.block_until_ready(
        R.run_rounds(ctx, params, key, state, with_acc=False)
    )
    t0 = time.perf_counter()
    _, traj = R.run_rounds(ctx, params, key, state, with_acc=False)
    jax.block_until_ready(traj)
    wall = time.perf_counter() - t0
    return {
        "m": m,
        "mode": "stream_sharded",
        "n_devices": jax.device_count(),
        "clients_per_sec": m * rounds / wall,
        "wall_s": wall,
        "theta_mse": float(np.mean(traj["theta_mse"])),
        "maxrss_mb": resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024,
    }


def run_sharded(m: int, rounds: int, n_dev: int) -> dict:
    env = dict(os.environ)
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
        sys.executable, "-m", "benchmarks.fig_streaming_clients",
        "--inner", "--m", str(m), "--rounds", str(rounds),
    ]
    res = subprocess.run(
        cmd, capture_output=True, text=True, env=env,
        cwd=os.path.join(os.path.dirname(__file__), ".."),
    )
    if res.returncode != 0:
        raise RuntimeError(f"sharded child failed:\n{res.stderr[-3000:]}")
    payload = json.loads(res.stdout.strip().splitlines()[-1])
    assert payload["n_devices"] == n_dev, payload
    return payload


def main(rounds: int | None = None, m_grid=None) -> dict:
    from .common import emit, require_cpu_sweep

    require_cpu_sweep("fig_streaming_clients (its sharded point)")
    rounds = ROUNDS if rounds is None else min(rounds, ROUNDS)
    m_grid = tuple(m_grid or M_GRID)
    out: dict = {"rounds": rounds, "chunk": CHUNK, "sweep": {}}

    for m in m_grid:
        row: dict = {"stream": run_cell(m, rounds, stream=True)}
        if m <= DENSE_MAX:
            row["dense"] = run_cell(m, rounds, stream=False)
        out["sweep"][m] = row
        s = row["stream"]
        mem = (
            f";peak_stream={s['peak_bytes_est']};"
            f"peak_dense={row['dense']['peak_bytes_est']}"
            if "dense" in row
            else f";peak_stream={s['peak_bytes_est']}"
        )
        emit(
            f"streaming_clients_M{m}",
            1e6 / s["clients_per_sec"],
            f"clients_per_sec={s['clients_per_sec']:.0f};"
            + (
                f"dense_cps={row['dense']['clients_per_sec']:.0f}"
                if "dense" in row
                else "dense_cps=skipped"
            )
            + f";theta_mse={s['theta_mse']:.3e}" + mem,
        )

    out["sharded"] = run_sharded(max(m_grid), rounds, SHARD_DEVICES)
    emit(
        f"streaming_clients_sharded_M{max(m_grid)}",
        1e6 / out["sharded"]["clients_per_sec"],
        f"clients_per_sec={out['sharded']['clients_per_sec']:.0f};"
        f"devices={out['sharded']['n_devices']};"
        f"maxrss_mb={out['sharded']['maxrss_mb']:.0f}",
    )
    out["maxrss_mb"] = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024

    ms = sorted(out["sweep"])
    mses = [out["sweep"][m]["stream"]["theta_mse"] for m in ms]
    if len(ms) >= 2:
        slope = float(np.polyfit(np.log(ms), np.log(mses), 1)[0])
        lo, hi = SLOPE_WINDOW
        out["slope"] = slope
        out["slope_ok"] = bool(lo <= slope <= hi)
        emit(
            "streaming_clients_slope",
            0.0,
            f"slope={slope:.3f};window=[{lo},{hi}];ok={out['slope_ok']}",
        )

    report = os.path.join(
        os.path.dirname(__file__), "..", "reports", "fig_streaming_clients.json"
    )
    os.makedirs(os.path.dirname(report), exist_ok=True)
    with open(report, "w") as f:
        json.dump(out, f, indent=1, default=str)

    if len(ms) >= 2:
        assert out["slope_ok"], (
            f"theta_mse log-log slope {out['slope']:.3f} outside "
            f"{SLOPE_WINDOW} — O(1/M) decay broken: {dict(zip(ms, mses))}"
        )
    return out


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--inner", action="store_true")
    ap.add_argument("--m", type=int, default=None)
    ap.add_argument("--rounds", type=int, default=None)
    args = ap.parse_args()
    if args.inner:
        print(json.dumps(run_inner(args.m, args.rounds or ROUNDS), default=str))
    else:
        main(args.rounds)
