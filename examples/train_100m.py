"""End-to-end driver: federated fine-tuning of a transformer LM through
the packed one-bit pytree wire, with a FedAvg full-precision baseline.

Default is a CPU-friendly ~6M model for a quick demonstration; pass
``--full`` for the ~100M-parameter qwen2 variant and a few hundred rounds
(sized for a real accelerator — it will run on CPU, just slowly).

Per round it reports the uplink wire bytes of the packed one-bit wire
next to the int8 (8x) and f32 (32x) baselines; after training it
evaluates next-token accuracy on held-out client streams for BOTH the
PRoBit+ run and the FedAvg baseline run (same data, same init, same
round budget) — the acc-vs-FedAvg comparison the paper's experiments
make. ``--json-out`` writes the whole report.

Run:  PYTHONPATH=src python examples/train_100m.py [--full] [--rounds N] \
          [--json-out report.json] [--skip-fedavg]
"""

import argparse
import dataclasses
import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, "src")

from repro import configs
from repro.checkpoint import save_checkpoint
from repro.core import build_pipeline
from repro.data import make_lm_streams
from repro.fl.pytree_wire import pytree_wire_bytes
from repro.launch.fl_step import DistFLConfig, make_fl_train_step
from repro.launch.mesh import make_host_mesh
from repro.models import build_specs
from repro.models.config import ModelConfig
from repro.models.model import prefill
from repro.models.spec import count_params, init_params, param_pspecs


def model_config(full: bool) -> ModelConfig:
    if full:  # ~100M-parameter qwen2-family model
        return dataclasses.replace(
            configs.get_config("qwen2-1.5b"),
            name="qwen2-100m",
            n_layers=8, d_model=640, n_heads=10, n_kv_heads=2,
            d_ff=1792, vocab=32768, d_head=64,
        )
    return dataclasses.replace(
        configs.get_config("qwen2-1.5b"),
        name="qwen2-6m",
        n_layers=4, d_model=192, n_heads=6, n_kv_heads=2,
        d_ff=512, vocab=4096, d_head=32,
    )


def next_token_accuracy(params, cfg, tokens, labels, batch_size=8):
    """Mean next-token top-1 accuracy under the training objective's
    shift/mask convention (matches ``train_loss``: labels rolled by -1,
    last position masked)."""
    correct = total = 0
    for i in range(0, tokens.shape[0], batch_size):
        tb = tokens[i : i + batch_size]
        lb = labels[i : i + batch_size]
        logits = prefill(params, {"tokens": tb}, cfg)
        pred = jnp.argmax(logits, axis=-1)
        shifted = jnp.roll(lb, -1, axis=1)
        hit = (pred == shifted)[:, :-1]  # last position has no next token
        correct += int(jnp.sum(hit))
        total += int(hit.size)
    return correct / max(total, 1)


def run_training(cfg, fl, rounds, clients, seq, streams, report_every):
    """One federated run: returns (params, per-round history)."""
    specs = build_specs(cfg)
    params = init_params(specs, jax.random.PRNGKey(0))
    step = jax.jit(make_fl_train_step(cfg, fl, param_pspecs(specs)))
    b = jnp.float32(0.01)
    key = jax.random.PRNGKey(1)
    history = []
    t0 = time.time()
    for r in range(rounds):
        toks = np.stack(
            [s[4 * r : 4 * (r + 1)].reshape(2, 2, seq + 1) for s in streams]
        )[:, None]
        batch = {
            "tokens": jnp.asarray(toks[..., :-1]),
            "labels": jnp.asarray(toks[..., 1:]),
        }
        key, kr = jax.random.split(key)
        params, b, metrics = step(params, b, batch, kr)
        history.append(
            {
                "round": r,
                "loss_first": float(metrics["loss_first"]),
                "loss_last": float(metrics["loss_last"]),
                "b": float(b),
                "wire_bytes": float(metrics["wire_bytes"]),
            }
        )
        if r % report_every == 0 or r == rounds - 1:
            print(
                f"  [{fl.aggregator}] round {r:4d}: loss "
                f"{history[-1]['loss_first']:.4f} -> {history[-1]['loss_last']:.4f}  "
                f"b={float(b):.5f}  wire={history[-1]['wire_bytes']/1e6:.3f}MB  "
                f"[{time.time()-t0:.0f}s]"
            )
    return params, history


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--rounds", type=int, default=None)
    ap.add_argument("--clients", type=int, default=4)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--eval-seqs", type=int, default=32)
    ap.add_argument("--skip-fedavg", action="store_true")
    ap.add_argument("--json-out", default=None)
    ap.add_argument("--ckpt-dir", default="/tmp/probit_ckpts")
    args = ap.parse_args()
    rounds = args.rounds or (300 if args.full else 30)

    cfg = model_config(args.full)
    with jax.set_mesh(make_host_mesh()):
        specs = build_specs(cfg)
        print(f"{cfg.name}: {count_params(specs)/1e6:.1f}M params, {rounds} rounds")
        params0 = init_params(specs, jax.random.PRNGKey(0))
        wire = pytree_wire_bytes(
            build_pipeline("probit_plus"), params0, args.clients
        )
        print(
            f"uplink/round ({args.clients} clients): "
            f"{wire['wire_bytes']/1e6:.3f} MB packed "
            f"(ideal {wire['wire_bytes_ideal']/1e6:.3f}) — "
            f"{wire['wire_bytes_int8']/max(wire['wire_bytes_ideal'],1):.1f}x smaller than int8, "
            f"{wire['wire_bytes_f32']/max(wire['wire_bytes_ideal'],1):.1f}x smaller than f32"
        )
        del params0

        # training + held-out streams (held-out = fresh sequences from the
        # same per-client bigram models, different seed)
        streams = make_lm_streams(0, args.clients, cfg.vocab, args.seq + 1, 4 * rounds)
        ev = make_lm_streams(7, args.clients, cfg.vocab, args.seq + 1, args.eval_seqs)
        ev_toks = jnp.asarray(np.concatenate(ev))[:, :-1]
        ev_labels = jnp.asarray(np.concatenate(ev))[:, 1:]

        report_every = max(rounds // 10, 1)
        fl = DistFLConfig(clients_per_round=args.clients, local_steps=2, lr=0.02)
        print("training: PRoBit+ (packed one-bit wire)")
        params, hist = run_training(
            cfg, fl, rounds, args.clients, args.seq, streams, report_every
        )
        acc = next_token_accuracy(params, cfg, ev_toks, ev_labels)
        print(f"PRoBit+ next-token accuracy: {acc:.4f}")

        result = {
            "arch": cfg.name,
            "rounds": rounds,
            "clients": args.clients,
            "wire": wire,
            "probit_plus": {"history": hist, "accuracy": acc},
        }

        if not args.skip_fedavg:
            print("training: FedAvg fp32 baseline (same data, init, budget)")
            fl_avg = dataclasses.replace(fl, aggregator="fedavg_fp32")
            params_avg, hist_avg = run_training(
                cfg, fl_avg, rounds, args.clients, args.seq, streams, report_every
            )
            acc_avg = next_token_accuracy(params_avg, cfg, ev_toks, ev_labels)
            print(
                f"FedAvg next-token accuracy:  {acc_avg:.4f}  "
                f"(PRoBit+ {acc:.4f} at {wire['wire_bytes_f32']/max(wire['wire_bytes'],1):.1f}x "
                "less uplink)"
            )
            result["fedavg"] = {"history": hist_avg, "accuracy": acc_avg}
            result["acc_vs_fedavg"] = acc - acc_avg

        if args.ckpt_dir:
            path = save_checkpoint(args.ckpt_dir, rounds, params, {"arch": cfg.name})
            print("saved:", path)
        if args.json_out:
            with open(args.json_out, "w") as f:
                json.dump(result, f, indent=2)
            print("json:", args.json_out)


if __name__ == "__main__":
    main()
