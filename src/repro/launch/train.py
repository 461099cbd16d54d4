"""End-to-end distributed FL training driver.

Runs REAL training (not a dry-run) of any ``--arch`` on synthetic LM data
using the distributed PRoBit+ round from fl_step.py.

  PYTHONPATH=src python -m repro.launch.train --arch qwen2-1.5b --reduced \
      --rounds 5 --clients 4 --seq 128 --per-batch 2

Model size:
  --reduced             family-preserving smoke variant (every width 256)
  --layers N            depth-only cut: the first N whole layers at the
      published widths (what a one-chip run of a real config uses)

Mesh:
  default               one device, ("data", "model") = (1, 1)
  --pod-mesh            one silo per device: ("pod", "data", "model") =
      (n_devices, 1, 1); --clients must be a multiple of the device count
  --production-mesh     the 16x16 production layout

Other flags:
  --aggregator {probit_plus,fedavg_fp32}  packed one-bit wire (default)
      vs the full-precision FedAvg baseline the 32x claim compares to
  --rand-bits {32,16}   quantizer draw width (16 halves RNG memory)
  --json-out PATH       write per-round metrics + wire-byte report JSON
  --smoke               exit nonzero unless every round's losses are
      finite and the wire-byte report is nonzero (CI gate)

The client at cohort position ``g`` trains on stream ``g`` at scan step
``g // n_pods`` on pod ``g % n_pods``, so a run on the pod mesh and a run
on one device with the same ``--clients`` and seed see the same cohort.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from .. import configs
from ..checkpoint import save_checkpoint
from ..core import build_pipeline
from ..data import make_lm_streams
from ..fl.pytree_wire import pytree_wire_bytes
from ..models import build_specs
from ..models.spec import abstract_params, count_params, init_params, param_pspecs
from .compile_cache import enable_compile_cache
from .fl_step import DistFLConfig, make_fl_train_step
from .mesh import make_host_mesh, make_production_mesh, make_silo_mesh


def parse_args(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--layers", type=int, default=0)
    ap.add_argument("--rounds", type=int, default=5)
    ap.add_argument("--clients", type=int, default=4)
    ap.add_argument("--local-steps", type=int, default=2)
    ap.add_argument("--per-batch", type=int, default=2)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=0.01)
    ap.add_argument("--lam", type=float, default=0.2)
    ap.add_argument("--b-init", type=float, default=0.01)
    ap.add_argument(
        "--aggregator", default="probit_plus",
        choices=["probit_plus", "fedavg_fp32"],
    )
    ap.add_argument("--rand-bits", type=int, default=32, choices=[16, 32])
    ap.add_argument("--json-out", default=None)
    ap.add_argument("--smoke", action="store_true")
    mesh = ap.add_mutually_exclusive_group()
    mesh.add_argument("--pod-mesh", action="store_true")
    mesh.add_argument("--production-mesh", action="store_true")
    ap.add_argument("--ckpt-dir", default=None)
    return ap.parse_args(argv)


def model_config(args):
    cfg = configs.get_config(args.arch)
    if args.reduced:
        cfg = configs.reduced(cfg)
    if args.layers:
        cfg = configs.with_depth(cfg, args.layers)
    return cfg


def build_mesh(args):
    if args.production_mesh:
        return make_production_mesh()
    if args.pod_mesh:
        return make_silo_mesh()
    return make_host_mesh()


def memory_report(compiled) -> dict:
    """Device bytes of one compiled program (``memory_analysis``); the
    total is what the program holds at once: arguments + outputs +
    temporaries, less the outputs aliased onto arguments."""
    ma = compiled.memory_analysis()
    if ma is None:
        return {}
    rep = {
        k: int(getattr(ma, f"{k}_size_in_bytes"))
        for k in ("argument", "output", "temp", "alias", "generated_code")
    }
    rep["total"] = (
        rep["argument"] + rep["output"] + rep["temp"] - rep["alias"]
    )
    return rep


def _setup(args):
    """(cfg, mesh, n_pods, batch sharding, jitted step) for parsed args."""
    cfg = model_config(args)
    mesh = build_mesh(args)
    n_pods = dict(zip(mesh.axis_names, mesh.axis_sizes)).get("pod", 1)
    if args.clients % n_pods:
        raise SystemExit(
            f"--clients {args.clients} is not a multiple of the {n_pods} pods"
        )
    # batch leaves (m_seq, n_pods, local_steps, per_batch, ...): pod-sharded
    batch_sharding = NamedSharding(
        mesh, P(None, "pod") if "pod" in mesh.axis_names else P()
    )
    fl = DistFLConfig(
        clients_per_round=args.clients,
        local_steps=args.local_steps,
        lr=args.lr,
        lam=args.lam,
        aggregator=args.aggregator,
        rand_bits=args.rand_bits,
    )
    with jax.set_mesh(mesh):
        specs = build_specs(cfg)
        step = jax.jit(
            make_fl_train_step(cfg, fl, param_pspecs(specs, fsdp_axis="data"))
        )
    return cfg, mesh, n_pods, batch_sharding, specs, step


def step_memory(argv=None) -> dict:
    """:func:`memory_report` of the step ``main(argv)`` would run, compiled
    from shapes alone: nothing is placed on the device. Token-input
    architectures only."""
    args = parse_args(argv)
    cfg, mesh, n_pods, batch_sharding, specs, step = _setup(args)
    rep = NamedSharding(mesh, P())
    params = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=rep),
        abstract_params(specs),
    )
    toks = jax.ShapeDtypeStruct(
        (args.clients // n_pods, n_pods, args.local_steps, args.per_batch, args.seq),
        jnp.int32, sharding=batch_sharding,
    )
    with jax.set_mesh(mesh):
        compiled = step.lower(
            params,
            jax.ShapeDtypeStruct((), jnp.float32, sharding=rep),
            {"tokens": toks, "labels": toks},
            jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=rep),
        ).compile()
    return memory_report(compiled)


def main(argv=None) -> dict:
    """Run ``--rounds`` rounds; returns the per-round history, the wire
    report, the final params and the step's memory (``memory_analysis``
    and, where the backend reports it, ``peak_bytes_in_use``)."""
    args = parse_args(argv)
    enable_compile_cache()
    cfg, mesh, n_pods, batch_sharding, specs, step = _setup(args)
    m_seq = args.clients // n_pods
    if cfg.encoder_only or cfg.frontend != "none":
        print(f"note: {args.arch} uses the {cfg.frontend or 'encoder'} input path")
    with jax.set_mesh(mesh):
        params = init_params(specs, jax.random.PRNGKey(0))
        print(
            f"{cfg.name}: {count_params(specs)/1e6:.1f}M params, "
            f"{cfg.n_layers} layers, mesh={dict(mesh.shape)}"
        )
        b = jnp.float32(args.b_init)

        # Exact static per-round uplink accounting (the jitted metric is
        # the same number in f32): packed wire vs int8 vs f32 baselines.
        wire_pipeline = build_pipeline(
            "probit_plus" if args.aggregator == "probit_plus" else "fedavg",
            rand_bits=args.rand_bits,
        )
        wire = pytree_wire_bytes(wire_pipeline, params, args.clients)
        print(
            f"uplink/round: {wire['wire_bytes']/1e6:.3f} MB packed "
            f"(ideal {wire['wire_bytes_ideal']/1e6:.3f}) vs "
            f"{wire['wire_bytes_int8']/1e6:.3f} MB int8 ({wire['wire_bytes_int8']/max(wire['wire_bytes_ideal'],1):.1f}x) / "
            f"{wire['wire_bytes_f32']/1e6:.3f} MB f32 ({wire['wire_bytes_f32']/max(wire['wire_bytes_ideal'],1):.1f}x)"
        )

        streams = make_lm_streams(
            0, args.clients, cfg.vocab, args.seq + 1,
            args.local_steps * args.per_batch * args.rounds,
        )
        key = jax.random.PRNGKey(1)
        history = []
        memory = {}
        for r in range(args.rounds):
            t0 = time.time()
            per_round = args.local_steps * args.per_batch
            toks = np.stack(
                [
                    s[r * per_round : (r + 1) * per_round]
                    .reshape(args.local_steps, args.per_batch, args.seq + 1)
                    for s in streams
                ]
            ).reshape(m_seq, n_pods, args.local_steps, args.per_batch, args.seq + 1)
            batch = {
                "tokens": toks[..., :-1],
                "labels": toks[..., 1:],
            }
            if cfg.frontend == "vision":
                b_shape = toks.shape[:4]
                batch["patches"] = 0.02 * np.ones(
                    b_shape + (cfg.frontend_tokens, cfg.d_model), jnp.bfloat16
                )
            elif cfg.frontend == "audio":
                b_shape = toks.shape[:4]
                batch = {
                    "feats": 0.02 * np.ones(b_shape + (args.seq, cfg.d_model), jnp.bfloat16),
                    "labels": toks[..., :-1] % cfg.vocab,
                    "mask": np.ones(b_shape + (args.seq,), bool),
                }
            batch = jax.device_put(batch, batch_sharding)
            key, kr = jax.random.split(key)
            if r == 0:
                # the jitted call below reuses this compilation
                memory = memory_report(step.lower(params, b, batch, kr).compile())
                if memory:
                    print(
                        "step memory (memory_analysis): "
                        + " ".join(f"{k}={v}" for k, v in memory.items())
                    )
            params, b, metrics = step(params, b, batch, kr)
            history.append(
                {
                    "round": r,
                    "loss_first": float(metrics["loss_first"]),
                    "loss_last": float(metrics["loss_last"]),
                    "b": float(b),
                    "wire_bytes": float(metrics["wire_bytes"]),
                    "seconds": time.time() - t0,
                }
            )
            print(
                f"round {r}: loss {history[-1]['loss_first']:.4f} -> "
                f"{history[-1]['loss_last']:.4f}  b={float(b):.5f}  "
                f"wire={history[-1]['wire_bytes']/1e6:.3f}MB  "
                f"({history[-1]['seconds']:.1f}s)"
            )
        stats = jax.devices()[0].memory_stats() or {}
        peak = stats.get("peak_bytes_in_use")
        if peak is not None:
            print(f"peak_bytes_in_use: {peak}")
        if args.ckpt_dir:
            path = save_checkpoint(args.ckpt_dir, args.rounds, params, {"arch": cfg.name})
            print("checkpoint:", path)
    result = {
        "arch": cfg.name,
        "layers": cfg.n_layers,
        "aggregator": args.aggregator,
        "rand_bits": args.rand_bits,
        "clients": args.clients,
        "pods": n_pods,
        "wire": wire,
        "memory": memory,
        "peak_bytes_in_use": peak,
        "rounds": history,
        "params": params,
    }
    if args.json_out:
        with open(args.json_out, "w") as f:
            json.dump(
                {k: v for k, v in result.items() if k != "params"}, f, indent=2
            )
        print("json:", args.json_out)
    if args.smoke:
        finite = all(
            np.isfinite(h["loss_first"]) and np.isfinite(h["loss_last"])
            for h in history
        )
        wired = all(h["wire_bytes"] > 0 for h in history) and wire["wire_bytes"] > 0
        if not (finite and wired):
            print(f"SMOKE FAIL: finite={finite} wired={wired}", file=sys.stderr)
            sys.exit(1)
        print("SMOKE OK")
    return result


if __name__ == "__main__":
    main()
