"""Hierarchical tree aggregation throughput: clients/sec vs edge count.

The ROADMAP's "hierarchical aggregation" item, measured: the M-client
synthetic round (the ``fig_streaming_clients`` task, so the flat
streaming baseline is pinned to the same data and model) is executed as
a clients -> edges -> root count tree for a sweep of edge counts, each
edge mapped onto its own virtual CPU device (``tree_shard``; psum-free
root merge). Three acceptance lines ride the figure:

* **parity gate** — before any timing, a small eager run asserts the
  tree root estimate is **bit-exact** with the flat streaming round at
  zero staleness (the additive count merge is associative);
* **edge-count sweep** — clients/sec at edges in {1, 2, 4} (each edge
  count in a subprocess with ``--xla_force_host_platform_device_count``
  = edges, since the flag must precede jax platform init), plus the
  flat streaming round as the no-tree baseline. ``monotone_1_to_max``
  records whether the max-edge throughput beats the 1-edge tree — a
  *recorded* property, asserted only by the nightly slow test, because
  on a single-core host every virtual device shares one core;
* **Byzantine-edge sweep** — an (attacked-edges x merge-rule) campaign
  at E = 8: the naive additive merge's ``theta_mse`` degrades with the
  number of inflating edges while the rate-median merge holds. The
  campaign JSON (with CI bands) is written next to the figure and the
  trajectory PNG is rendered *from the JSON on disk* via
  ``benchmarks.plots`` — the artifact -> plot path CI exercises.

Writes ``reports/fig_tree_throughput.json``, the stable
``reports/BENCH_tree_throughput.json`` (clients/sec at edges {1, 4},
M = 1e5, CPU — the tracked regression number), and
``reports/fig_tree_throughput_campaign.json`` (+ PNG when matplotlib is
available). ``--smoke`` shrinks every axis for the per-push CI gate.

  PYTHONPATH=src python -m benchmarks.fig_tree_throughput
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

from .fig_streaming_clients import CHUNK, _base, _init_params, _task_fn, stream_task

EDGE_COUNTS = (1, 2, 4)
M_SWEEP = int(os.environ.get("PROBIT_TREE_M", "100000"))
M_BYZ = 512
BYZ_EDGES = 8
ROUNDS = int(os.environ.get("PROBIT_STREAM_ROUNDS", "2"))
REPORTS = os.path.join(os.path.dirname(__file__), "..", "reports")


def _tree_cfg(m: int, edges: int, rounds: int, **extra):
    from repro.fl import FLConfig

    return FLConfig(
        **_base(rounds),
        n_clients=m,
        client_chunk=min(CHUNK, m),
        stateless_clients=True,
        tree_edges=edges,
        **extra,
    )


def _make_ctx(cfg):
    from repro.fl import rounds as R
    from repro.models.vision import accuracy, mlp_logits, xent_loss

    cx, cy, test = stream_task(cfg.n_clients)
    return R.make_context(
        cfg,
        _init_params(),
        functools.partial(xent_loss, mlp_logits),
        functools.partial(accuracy, mlp_logits),
        cx,
        cy,
        test,
    )


def parity_gate(m: int = 64, rounds: int = 2) -> float:
    """Bit-exact tree == flat at zero staleness (eager, small M).

    Returns the max |difference| (must be exactly 0.0) — the correctness
    gate that must pass before any throughput number is reported.
    """
    import jax

    from repro.fl import FLConfig, rounds as R

    def run(cfg):
        ctx = _make_ctx(cfg)
        params = R.cell_params(cfg)
        state = R.init_run_state(ctx)
        key = jax.random.PRNGKey(0)
        fn = R.round_fn(ctx)
        with jax.disable_jit():
            for _ in range(rounds):
                key, kb, kr = jax.random.split(key, 3)
                state, _ = fn(ctx, params, kr, state, R.round_batches(ctx, kb))
        return np.asarray(state.w_global)

    flat = run(
        FLConfig(
            **_base(rounds), n_clients=m, client_chunk=16,
            stateless_clients=True,
        )
    )
    tree = run(
        FLConfig(
            **_base(rounds), n_clients=m, client_chunk=16,
            stateless_clients=True, tree_edges=4,
        )
    )
    diff = float(np.abs(flat - tree).max())
    if diff != 0.0:
        raise AssertionError(
            f"tree root estimate not bit-exact with flat round: max diff {diff}"
        )
    return diff


def run_inner(m: int, edges: int, rounds: int) -> dict:
    """One timed cell in this process's device configuration (child).

    ``edges == 0`` is the flat streaming baseline; ``edges >= 1`` runs
    the tree, sharded one edge per device when the parent gave us
    ``device_count == edges``.
    """
    import jax

    from repro.fl import rounds as R

    if edges:
        cfg = _tree_cfg(m, edges, rounds, tree_shard=edges > 1)
    else:
        from repro.fl import FLConfig

        cfg = FLConfig(
            **_base(rounds), n_clients=m, client_chunk=min(CHUNK, m),
            stateless_clients=True,
        )
    ctx = _make_ctx(cfg)
    params = R.cell_params(cfg)
    key = jax.random.PRNGKey(0)
    state = R.init_run_state(ctx)
    jax.block_until_ready(R.run_rounds(ctx, params, key, state, with_acc=False))
    t0 = time.perf_counter()
    _, traj = R.run_rounds(ctx, params, key, state, with_acc=False)
    jax.block_until_ready(traj)
    wall = time.perf_counter() - t0
    return {
        "m": m,
        "edges": edges,
        "n_devices": jax.device_count(),
        "clients_per_sec": m * rounds / wall,
        "wall_s": wall,
        "theta_mse": float(np.mean(traj["theta_mse"])),
        "maxrss_mb": resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024,
    }


def _spawn(m: int, edges: int, rounds: int) -> dict:
    n_dev = max(edges, 1)
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
        sys.executable, "-m", "benchmarks.fig_tree_throughput",
        "--inner", "--m", str(m), "--edges", str(edges),
        "--rounds", str(rounds),
    ]
    res = subprocess.run(
        cmd, capture_output=True, text=True, env=env,
        cwd=os.path.join(os.path.dirname(__file__), ".."),
    )
    if res.returncode != 0:
        raise RuntimeError(
            f"edges={edges} child failed:\n{res.stderr[-3000:]}"
        )
    payload = json.loads(res.stdout.strip().splitlines()[-1])
    assert payload["n_devices"] == n_dev, payload
    return payload


def byz_campaign(rounds: int, seeds=(0, 1, 2)) -> dict:
    """(byz_edges x merge) grid at E = 8 through the campaign engine.

    Returns the summary dict; writes the campaign JSON artifact and
    renders its trajectory PNG *from the file on disk* (the
    ``benchmarks.plots`` CLI path).
    """
    from repro.sim import CampaignSpec, CellSpec, run_campaign
    from .plots import plot_trajectories

    base = dict(
        **_base(rounds),
        n_clients=M_BYZ,
        client_chunk=min(CHUNK, M_BYZ),
        stateless_clients=True,
        tree_edges=BYZ_EDGES,
        edge_attack="edge_inflate",
    )
    cells = tuple(
        CellSpec(f"byz{b}_{merge}", dict(byz_edges=b, edge_merge=merge))
        for b in (0, 1, 3)
        for merge in ("sum", "median")
    )
    spec = CampaignSpec(base=base, cells=cells, seeds=seeds)
    result = run_campaign(spec, _task_fn, with_acc=False)

    camp_path = os.path.join(REPORTS, "fig_tree_throughput_campaign.json")
    result.save(camp_path)
    png = plot_trajectories(
        camp_path, "theta_mse",
        out_path=camp_path.replace(".json", "_theta_mse.png"),
        title=f"Byzantine edges at E={BYZ_EDGES} (edge_inflate)",
        logy=True,
    )

    mse = {
        c.name: float(np.mean(c.metrics["theta_mse"])) for c in result.cells
    }
    # the robustness headline: at 3/8 inflating edges the median merge
    # must beat the naive sum (recorded; the unit breakdown test asserts
    # the sharper merge-layer version)
    return {
        "theta_mse": mse,
        "median_beats_sum_at_3": bool(mse["byz3_median"] < mse["byz3_sum"]),
        "campaign_json": os.path.relpath(camp_path, REPORTS + "/.."),
        "png": png and os.path.relpath(png, REPORTS + "/.."),
    }


def main(rounds: int | None = None, smoke: bool = False) -> dict:
    import jax

    from .common import emit, require_cpu_sweep

    require_cpu_sweep("fig_tree_throughput")
    rounds = ROUNDS if rounds is None else min(rounds, ROUNDS)
    m = 10_000 if smoke else M_SWEEP
    edge_counts = (1, 2) if smoke else EDGE_COUNTS

    out: dict = {"m": m, "rounds": rounds, "smoke": smoke, "sweep": {}}
    out["parity_max_diff"] = parity_gate()
    emit("tree_parity_gate", 0.0, "bit_exact=True")

    out["flat_baseline"] = _spawn(m, 0, rounds)
    for e in edge_counts:
        out["sweep"][e] = _spawn(m, e, rounds)
        r = out["sweep"][e]
        emit(
            f"tree_throughput_E{e}",
            1e6 / r["clients_per_sec"],
            f"clients_per_sec={r['clients_per_sec']:.0f};"
            f"devices={r['n_devices']};maxrss_mb={r['maxrss_mb']:.0f}",
        )
    thr = [out["sweep"][e]["clients_per_sec"] for e in edge_counts]
    out["monotone_1_to_max"] = bool(thr[-1] >= thr[0])
    emit(
        "tree_throughput_scaling",
        1e6 / thr[-1],
        f"speedup_1to{edge_counts[-1]}={thr[-1] / thr[0]:.2f}x;"
        f"monotone={out['monotone_1_to_max']};"
        f"flat_cps={out['flat_baseline']['clients_per_sec']:.0f}",
    )

    out["byzantine"] = byz_campaign(
        min(rounds * 5, 10), seeds=(0,) if smoke else (0, 1, 2)
    )
    emit(
        "tree_byzantine_edges",
        0.0,
        f"median_beats_sum_at_3={out['byzantine']['median_beats_sum_at_3']};"
        + ";".join(
            f"{k}={v:.2e}" for k, v in out["byzantine"]["theta_mse"].items()
        ),
    )

    os.makedirs(REPORTS, exist_ok=True)
    with open(os.path.join(REPORTS, "fig_tree_throughput.json"), "w") as f:
        json.dump(out, f, indent=1, default=str)
    if not smoke:
        # the stable tracked number: clients/sec at edges {1, max}, M,
        # stamped with the backend that produced it
        bench = {
            "m": m,
            "rounds": rounds,
            "platform": jax.default_backend(),
            "clients_per_sec": {
                "flat": round(out["flat_baseline"]["clients_per_sec"]),
                **{
                    f"edges_{e}": round(out["sweep"][e]["clients_per_sec"])
                    for e in edge_counts
                },
            },
            "monotone_1_to_max": out["monotone_1_to_max"],
        }
        with open(os.path.join(REPORTS, "BENCH_tree_throughput.json"), "w") as f:
            json.dump(bench, f, indent=1)
    return out


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--inner", action="store_true")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--m", type=int, default=None)
    ap.add_argument("--edges", type=int, default=0)
    ap.add_argument("--rounds", type=int, default=None)
    args = ap.parse_args()
    if args.inner:
        print(
            json.dumps(
                run_inner(args.m, args.edges, args.rounds or ROUNDS),
                default=str,
            )
        )
    else:
        main(args.rounds, smoke=args.smoke)
