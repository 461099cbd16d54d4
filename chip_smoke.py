"""Prove that the system's main paths run on a TPU chip.

    python chip_smoke.py                # one chip: phases a-d below
    python chip_smoke.py --four-chips   # four chips: the cross-silo step only

One chip:
  a. the device is a TPU and the kernel dispatch resolves to ``pallas``;
  b. the Pallas wire at the width of one qwen2-1.5b MLP matrix
     (1536 x 8960): compress bytes (plain and error-feedback kernels) for
     256 clients, the ``bit_aggregate`` estimate at M=256 and at a ragged
     M, and ``prox_sgd`` -- each against the ``ref`` engine on the chip,
     which must agree bit for bit;
  c. PRoBit+ ``FLSimulation`` rounds of the paper's CNN with 10%
     Byzantine clients and DP, once with ``use_kernels=True`` (the Pallas
     wire) and once without; both trajectories must be identical;
  d. rounds of the ``repro.launch.train`` path at qwen2-1.5b's published
     widths, cut in depth only, to the deepest whole-layer cut whose
     compiled step fits the device's memory; losses must be finite and
     the wire non-empty.

Four chips (``--four-chips``): ``launch/fl_step.py`` rounds on a
``pod`` = 4 mesh (one silo per chip), each against the same cohort, data
and key scanned on one chip. With ``--lr 0`` every client's delta is
exactly zero, so each bit depends only on its cohort position's quantizer
key: the key schedule, the int32 vote counts summed across pods and the
Eq.-13 step must give the one-chip parameters bit for bit. A trained
round (default lr) is reported beside it and held to finite losses and
equal wire bytes only: the pod program and the one-chip program round
the bf16 forward pass differently, so a few stochastic votes flip.

Each phase prints a JSON line. The last line of standard output is
``{"ok": true, "device": {...}}``, printed only when every phase passed.
The script exits non-zero, without that line, when JAX finds no TPU, when
the repository's ``src/`` is not next to it, or when any phase fails.
Everything runs in this one process, which holds the chip.
"""

from __future__ import annotations

import argparse
import functools
import json
import sys
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"

D_MLP = 1536 * 8960  # one qwen2-1.5b MLP matrix, as a flat wire
M_FULL, M_RAGGED = 256, 37  # one whole bit_aggregate M_BLOCK tile; ragged
CLIENT_CHUNK = 16  # clients compressed per call in phase b
QWEN_ARCH = "qwen2-1.5b"
# Deepest whole-layer cut of qwen2-1.5b whose round step (2 clients,
# 2 local steps of 2 x 128 tokens) fits one v5e chip; phase d checks it
# against the chip's memory and that one layer more does not fit.
QWEN_LAYERS = 17
QWEN_ARGS = [
    "--arch", QWEN_ARCH, "--rounds", "3", "--clients", "2",
    "--local-steps", "2", "--per-batch", "2", "--seq", "128",
]
# --four-chips: a shallower cut keeps the cross-silo comparison quick.
POD_LAYERS = 2
POD_ARGS = [
    "--arch", QWEN_ARCH, "--layers", str(POD_LAYERS), "--rounds", "1",
    "--clients", "4", "--local-steps", "2", "--per-batch", "2",
    "--seq", "128",
]


class PhaseFailed(RuntimeError):
    pass


def report(phase: str, ok: bool, **fields) -> None:
    print(json.dumps({"phase": phase, "ok": ok, **fields}), flush=True)
    if not ok:
        raise PhaseFailed(f"phase {phase} failed: {fields}")


def device_info() -> dict:
    import jax

    dev = jax.devices()[0]
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}


def phase_device(n_chips: int) -> dict:
    """a. A TPU with the expected chip count, and the Pallas engine."""
    from repro.kernels import resolve_engine

    info = device_info()
    engine = resolve_engine()
    ok = (
        info["platform"] == "tpu"
        and engine == "pallas"
        and info["count"] >= n_chips
    )
    report("device", ok, engine=engine, **info)
    return info


def _n_diff(a, b) -> int:
    import jax.numpy as jnp

    return int(jnp.sum(a != b))


def _n_bit_diff(a, b) -> int:
    import jax
    import jax.numpy as jnp

    return int(jnp.sum(jax.lax.population_count(a ^ b).astype(jnp.int32)))


def phase_kernels(d: int = D_MLP, m: int = M_FULL, ragged: int = M_RAGGED,
                  chunk: int = CLIENT_CHUNK, seed: int = 0,
                  engine: str = "pallas") -> None:
    """b. ``engine`` vs ``ref``, bit for bit, at width ``d``."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops

    kd, kb, kq, kp = jax.random.split(jax.random.PRNGKey(seed), 4)
    # per-coordinate range; |delta| ~ b, so clipped and interior
    # coordinates both occur
    b = 0.01 * (1.0 + jax.random.uniform(kb, (d,)))
    diffs = {"pack_bits": 0, "ef_bits": 0, "ef_residual": 0}
    wire = []
    for c in range(m // chunk):
        deltas = 0.01 * jax.random.normal(jax.random.fold_in(kd, c), (chunk, d))
        for ef in (False, True):
            got = ops.stoch_quant_compress_batch(
                kq, deltas, b, row_offset=c * chunk, want_residual=ef,
                engine=engine,
            )
            want = ops.stoch_quant_compress_batch(
                kq, deltas, b, row_offset=c * chunk, want_residual=ef,
                engine="ref",
            )
            if ef:
                diffs["ef_bits"] += _n_bit_diff(got[0], want[0])
                diffs["ef_residual"] += _n_diff(got[1], want[1])
            else:
                diffs["pack_bits"] += _n_bit_diff(got[0], want[0])
                wire.append(got[0])
    wire = jnp.concatenate(wire)

    def uniform_b(eng):
        # b built inside the jit as one broadcast value, as a round builds
        # it: XLA then reassociates Eq. 13, on both engines alike
        return jax.jit(lambda p, s: ops.bit_aggregate(
            p, jnp.full((d,), s), d, engine=eng))

    for n_clients in (m, ragged):
        got = ops.bit_aggregate(wire[:n_clients], b, d, engine=engine)
        want = ops.bit_aggregate(wire[:n_clients], b, d, engine="ref")
        diffs[f"theta_m{n_clients}"] = _n_diff(got, want)
        s = jnp.float32(0.0104)
        diffs[f"theta_m{n_clients}_uniform_b"] = _n_diff(
            uniform_b(engine)(wire[:n_clients], s),
            uniform_b("ref")(wire[:n_clients], s),
        )
    ks = jax.random.split(kp, 4)
    w, g, mom = (jax.random.normal(k, (d,)) for k in ks[:3])
    w0 = w + 0.1 * jax.random.normal(ks[3], (d,))
    got = ops.prox_sgd(w, w0, g, mom, 0.01, 0.2, 0.5, engine=engine)
    want = ops.prox_sgd(w, w0, g, mom, 0.01, 0.2, 0.5, engine="ref")
    diffs["prox_w"] = _n_diff(got[0], want[0])
    diffs["prox_momentum"] = _n_diff(got[1], want[1])
    report("kernels", not any(diffs.values()), engine=engine, d=d,
           clients=m, ragged_clients=ragged, differing=diffs)


def phase_rounds(rounds: int = 3, n_clients: int = 10, seed: int = 0) -> None:
    """c. PRoBit+ CNN rounds, Pallas wire vs plain JAX, identical."""
    import jax
    import numpy as np

    from repro.data import make_image_classification, partition_label_skew
    from repro.fl import FLConfig, FLSimulation
    from repro.models.vision import accuracy, cnn_logits, init_cnn, xent_loss

    (xtr, ytr), (xte, yte) = make_image_classification(
        seed, n_train=1000, n_test=200
    )
    parts = partition_label_skew(ytr, n_clients, 2, 50, seed=seed)
    cx = np.stack([xtr[i] for i in parts])
    cy = np.stack([ytr[i] for i in parts])
    p0 = init_cnn(jax.random.PRNGKey(seed))
    runs = {}
    for use_kernels in (True, False):
        cfg = FLConfig(
            n_clients=n_clients, rounds=rounds, local_epochs=1,
            byz_frac=0.1, attack="gaussian", dp_epsilon=1.0,
            use_kernels=use_kernels, seed=seed,
        )
        sim = FLSimulation(
            cfg, p0, functools.partial(xent_loss, cnn_logits),
            functools.partial(accuracy, cnn_logits), cx, cy,
            {"x": xte, "y": yte},
        )
        history = sim.run(eval_every=1)
        runs[use_kernels] = (np.asarray(sim.w_global), history)
    (w_k, h_k), (w_p, h_p) = runs[True], runs[False]
    finite = all(np.isfinite(h["loss"]) for h in h_k)
    report(
        "rounds", finite and h_k == h_p and _n_diff(w_k, w_p) == 0,
        model="cnn", d=int(w_k.size), rounds=rounds, clients=n_clients,
        byz_frac=0.1, dp_epsilon=1.0, differing_w_global=_n_diff(w_k, w_p),
        history_kernels=h_k, history_plain=h_p,
    )


def phase_qwen(layers: int = QWEN_LAYERS) -> None:
    """d. train.py rounds at published widths, deepest fitting depth."""
    import jax
    import numpy as np

    from repro.launch import train

    limit = jax.devices()[0].memory_stats()["bytes_limit"]
    deeper = train.step_memory(QWEN_ARGS + ["--layers", str(layers + 1)])
    res = train.main(QWEN_ARGS + ["--layers", str(layers)])
    losses = [h[k] for h in res["rounds"] for k in ("loss_first", "loss_last")]
    ok = (
        res["memory"]["total"] <= limit < deeper["total"]
        and all(np.isfinite(losses))
        and all(h["wire_bytes"] > 0 for h in res["rounds"])
        and res["wire"]["wire_bytes"] > 0
    )
    report(
        "qwen", ok, arch=res["arch"], layers=res["layers"],
        bytes_limit=limit, step_bytes=res["memory"]["total"],
        step_bytes_one_more_layer=deeper["total"],
        peak_bytes_in_use=res["peak_bytes_in_use"],
        wire_bytes_per_round=res["wire"]["wire_bytes"],
        losses=losses,
    )


def _param_diff(a, b) -> tuple[int, float]:
    """(differing elements, max |a - b|) over two param pytrees."""
    import jax
    import numpy as np

    n_diff, max_abs = 0, 0.0
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        x = np.asarray(x, np.float32)
        y = np.asarray(y, np.float32)
        n_diff += int(np.sum(x != y))
        max_abs = max(max_abs, float(np.max(np.abs(x - y))))
    return n_diff, max_abs


def phase_four_chips() -> None:
    """Pod=4 cross-silo rounds against the same rounds on one chip."""
    import jax
    import numpy as np

    from repro.launch import train

    runs, ok = {}, True
    for name, extra in (("lr0", ["--lr", "0"]), ("trained", [])):
        pod = train.main(POD_ARGS + extra + ["--pod-mesh"])
        one = train.main(POD_ARGS + extra)
        n_diff, max_abs = _param_diff(pod["params"], one["params"])
        losses = [
            h[k] for r in (pod, one) for h in r["rounds"]
            for k in ("loss_first", "loss_last")
        ]
        ok = ok and (
            pod["pods"] == 4 and one["pods"] == 1
            and all(np.isfinite(losses))
            and pod["rounds"][-1]["wire_bytes"] == one["rounds"][-1]["wire_bytes"]
        )
        runs[name] = {
            "params": sum(x.size for x in jax.tree.leaves(one["params"])),
            "differing_params": n_diff, "max_abs_diff": max_abs,
            "rounds_pod": pod["rounds"], "rounds_one_chip": one["rounds"],
        }
    report(
        "four_chips", ok and runs["lr0"]["differing_params"] == 0,
        arch=pod["arch"], layers=pod["layers"], clients=pod["clients"],
        **runs,
    )


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument(
        "--four-chips", action="store_true",
        help="run only the pod=4 cross-silo step and its one-chip twin",
    )
    args = ap.parse_args(argv)
    if not (SRC / "repro").is_dir():
        print(f"chip_smoke: repository sources not found at {SRC}",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))
    import jax

    if jax.devices()[0].platform != "tpu":
        print(
            f"chip_smoke: no TPU found (JAX platform is "
            f"{jax.devices()[0].platform!r})", file=sys.stderr,
        )
        return 1
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    try:
        if args.four_chips:
            info = phase_device(4)
            phase_four_chips()
        else:
            info = phase_device(1)
            phase_kernels()
            phase_rounds()
            phase_qwen()
    except PhaseFailed as e:
        print(f"chip_smoke: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": info}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
