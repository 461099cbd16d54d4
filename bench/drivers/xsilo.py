"""Cross-silo rounds of a decoder LM through the program's distributed
PRoBit+ step (``repro.launch.fl_step.make_fl_train_step``).

The step is built as ``repro.launch.train`` builds it on one chip: a
host mesh with one device and ``jax.jit`` of the step over FSDP-style
parameter specs. One call of the step is one round: every silo trains
``local_steps`` prox-SGD steps from the global weights, compresses its
per-leaf delta onto the one-bit wire, the int32 vote counts are summed
and Eq. 13 updates the weights; the loss votes update b.

Set-up makes the weights on the device in one jitted call from the seed,
compiles the step once and drives the first ``CHECKED_ROUNDS`` rounds
through the same call and feed as the window; their losses, per-leaf
update norms and every weight after the first round are what the check
compares with the plain reference (``bench.reference.decoder``).
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench import flops
from bench.harness import Check, limit_of
from bench.reference import decoder as ref
from bench.traffic import generators as gen

CHECKED_ROUNDS = 3
# faults a broken round can have; no exchange crosses chips on one chip
FAULTS = ("state_unchanged", "half_batch", "altered_token", "altered_answer")
_BUILT: dict = {}  # (config, traffic, chips) -> the program's built step


def make(ctx):
    return XSilo(ctx)


def program_model_config(cfg: dict):
    """The program's ModelConfig for a configuration file."""
    from repro import configs

    base = configs.get_config(cfg["program"]["arch"])
    return dataclasses.replace(
        base,
        name=cfg["name"],
        n_layers=cfg["num_hidden_layers"],
        d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"],
        vocab=cfg["vocab_size"],
        rope_theta=cfg["rope_theta"],
        norm_eps=cfg["rms_norm_eps"],
        tie_embeddings=cfg["tie_word_embeddings"],
        qkv_bias=True,
    )


def _leaf_norms(a, b):
    return [
        jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32) - y.astype(jnp.float32))))
        for x, y in zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b))
    ]


leaf_norms = jax.jit(_leaf_norms)


def flip_largest_update(before, after):
    """``after`` with the update of its largest leaf negated: a round's
    answer altered where the round produces it."""
    leaves, treedef = jax.tree_util.tree_flatten(after)
    old = jax.tree_util.tree_leaves(before)
    i = max(range(len(leaves)), key=lambda j: leaves[j].size)
    leaves[i] = (2 * old[i].astype(jnp.float32)
                 - leaves[i].astype(jnp.float32)).astype(leaves[i].dtype)
    return jax.tree_util.tree_unflatten(treedef, leaves)


def weight_bits(weights) -> list:
    """Every leaf's bf16 bit patterns, on the host."""
    return [
        np.asarray(jax.device_get(x)).view(np.uint16).reshape(-1)
        for x in jax.tree_util.tree_leaves(weights)
    ]


class XSilo:
    def __init__(self, ctx):
        self.ctx = ctx
        self.cfg = ctx.cell.config
        self.tr = ctx.cell.traffic
        self.hp = self.cfg["protocol"]
        self.silos = self.tr["silos"]
        self.readings = {}

    # -- keys and data -----------------------------------------------------

    def _keys(self):
        k = gen.seed_key(self.ctx.seed)
        kw, kd, kr, kl = jax.random.split(k, 4)
        return {"weights": kw, "data": kd, "round": kr, "laws": kl}

    def _batch_fn(self):
        tr = self.tr
        steps, pb, seq = tr["local_steps"], tr["per_batch"], tr["seq"]
        vocab = self.cfg["vocab_size"]

        def batch(laws, key):
            toks = gen.lm_tokens(key, laws, steps * pb, seq, vocab)
            return toks.reshape(self.silos, steps, pb, seq)

        def program_batch(toks):
            # (silos, pods, steps, B, S): all silos on the one chip's pod
            t = toks.reshape(self.silos, 1, steps, pb, seq)
            # labels are the tokens: the step's loss shifts them by one
            return {"tokens": t, "labels": t}

        return batch, program_batch

    def round_tokens(self, r: int) -> jax.Array:
        """(silos, steps, B, S) tokens of round ``r``."""
        return self._batch(self._laws, jax.random.fold_in(self.keys["data"], r))

    # -- set-up ------------------------------------------------------------

    def prepare(self):
        """Keys and data generators: everything the reference needs,
        without the program."""
        tr = self.tr
        self.keys = self._keys()
        batch, self._program_batch_fn = self._batch_fn()
        self._batch = jax.jit(batch)
        self._laws = jax.jit(
            lambda k: gen.lm_client_laws(k, self.silos, tr["vocab_subset"], tr["alpha"])
        )(self.keys["laws"])

    def _build(self):
        """The program's jitted step, built as ``repro.launch.train`` builds
        it; shared by the drivers of one process with the same shapes."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        from repro.launch.fl_step import DistFLConfig, make_fl_train_step
        from repro.launch.mesh import make_host_mesh
        from repro.models import build_specs
        from repro.models.spec import abstract_params, param_pspecs

        cfg, tr, hp = self.cfg, self.tr, self.hp
        mesh = make_host_mesh()
        mc = program_model_config(cfg)
        fl = DistFLConfig(
            clients_per_round=self.silos,
            local_steps=tr["local_steps"],
            lr=hp["lr"], lam=hp["lam"],
            b_up=hp["b_up"], b_down=hp["b_down"],
            aggregator=hp["aggregator"], rand_bits=hp["rand_bits"],
        )
        with jax.set_mesh(mesh):
            specs = build_specs(mc)
            step = jax.jit(
                make_fl_train_step(mc, fl, param_pspecs(specs, fsdp_axis="data"))
            )
        want = abstract_params(specs)
        shapes = jax.eval_shape(lambda k: ref.init_weights(k, cfg), jax.random.PRNGKey(0))
        if jax.tree.structure(want) != jax.tree.structure(shapes) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype)
            for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(shapes))
        ):
            raise ValueError("the benchmark's weights do not match the program's layout")
        rep = NamedSharding(mesh, P())
        return {
            "mesh": mesh, "step": step, "rep": rep,
            "init": jax.jit(lambda k: ref.init_weights(k, cfg), out_shardings=rep),
            "batch": jax.jit(self._program_batch_fn, out_shardings=rep),
        }

    def setup(self):
        span = self.ctx.span
        with span("setup.build"):
            self.prepare()
            key = (self.cfg["name"], self.ctx.cell.traffic_name, self.ctx.chips)
            if key not in _BUILT:
                _BUILT[key] = self._build()
            built = _BUILT[key]
            self.mesh, self.step, self.init = built["mesh"], built["step"], built["init"]
            self._program_batch = built["batch"]
        with span("setup.weights"):
            self.params = self.init(self.keys["weights"])
            self.b = jax.device_put(jnp.float32(self.hp["b_init"]), built["rep"])
            jax.block_until_ready(self.params)
        self.r = 0
        first, last, bs = [], [], []
        for r in range(CHECKED_ROUNDS):
            prev = self.params
            with span(f"setup.round{r}"):
                out = self.round()
            met = self._last_metrics
            first.append(float(met["loss_first"]))
            last.append(float(met["loss_last"]))
            bs.append(float(self.b))
            if r == 0:
                self.readings["norms1"] = np.asarray(
                    jax.device_get(leaf_norms(self.params, prev)), np.float64
                )
                self.readings["weights1"] = weight_bits(self.params)
            del prev
            if not out["ok"]:
                break
        with span("setup.readings"):
            p0 = self.init(self.keys["weights"])
            self.readings["norms3"] = np.asarray(
                jax.device_get(leaf_norms(self.params, p0)), np.float64
            )
            del p0
        self.readings.update(loss_first=first, loss_last=last, b=bs)

    # -- the timed path ------------------------------------------------------

    def round(self) -> dict:
        span = self.ctx.span
        r = self.r
        with span("batch"):
            batch = self._program_batch(self.round_tokens(r))
        with span("round"):
            with jax.set_mesh(self.mesh):
                self.params, self.b, met = self.step(
                    self.params, self.b, batch,
                    jax.random.fold_in(self.keys["round"], r),
                )
        with span("sync"):
            jax.block_until_ready((self.params, self.b, met))
        self._last_metrics = met
        self.r += 1
        ok = bool(np.isfinite(float(met["loss_first"])) and np.isfinite(float(met["loss_last"])))
        tr = self.tr
        return {
            "tokens": self.silos * tr["local_steps"] * tr["per_batch"] * tr["seq"],
            "clients": self.silos,
            "ok": ok,
        }

    def facts(self) -> dict:
        tr = self.tr
        return {
            "train_flops_per_token": flops.decoder_train_flops_per_token(
                self.cfg, tr["seq"]
            ),
            "matmul_params": flops.decoder_matmul_params(self.cfg),
            "tokens_per_round": self.silos * tr["local_steps"] * tr["per_batch"] * tr["seq"],
            "kernels": {},
        }

    def release(self):
        for name in ("params", "b", "_last_metrics"):
            if hasattr(self, name):
                delattr(self, name)

    # -- the check -----------------------------------------------------------

    def reference_readings(self, precision: str = "f32", fault: str | None = None) -> dict:
        """The reference's readings over the checked rounds, from the seed.
        ``fault`` plants one of ``FAULTS`` in the reference, which then
        stands in for a broken program."""
        cfg, hp = self.cfg, self.hp
        w = jax.jit(lambda k: ref.init_weights(k, cfg))(self.keys["weights"])
        b = jnp.float32(hp["b_init"])
        out = {"loss_first": [], "loss_last": [], "b": []}
        for r in range(CHECKED_ROUNDS):
            prev, b_prev = w, b
            toks = self.round_tokens(r)
            if fault == "half_batch":
                toks = toks[: toks.shape[0] // 2]
            elif fault == "altered_token":
                toks = toks.at[0, 0, 0, 3].set((toks[0, 0, 0, 3] + 1) % cfg["vocab_size"])
            w, b, l0, l1 = ref.fl_round(
                w, b, toks, jax.random.fold_in(self.keys["round"], r), hp, cfg,
                precision,
            )
            if fault == "state_unchanged":
                w, b = prev, b_prev
            elif fault == "altered_answer":
                w = flip_largest_update(prev, w)
            out["loss_first"].append(l0)
            out["loss_last"].append(l1)
            out["b"].append(float(b))
            if r == 0:
                out["norms1"] = np.asarray(jax.device_get(leaf_norms(w, prev)), np.float64)
                out["weights1"] = weight_bits(w)
            del prev
        w0 = jax.jit(lambda k: ref.init_weights(k, cfg))(self.keys["weights"])
        out["norms3"] = np.asarray(jax.device_get(leaf_norms(w, w0)), np.float64)
        return out

    def check(self) -> list:
        values = compare(self.readings, self.reference_readings())
        return [Check(k, v, limit_of(self.ctx.cell.limits, k)) for k, v in values.items()]

    # the control: the reference one precision below the configuration's
    LOWER_PRECISION = "fp8"

    def values(self, got: dict, want: dict) -> dict:
        return compare(got, want)


def norm_gap(got: np.ndarray, want: np.ndarray) -> float:
    """Worst leaf's gap between two per-leaf norms, against the larger of
    that leaf's reference norm and the median leaf's."""
    floor = max(float(np.median(want)), 1e-30)
    return float(np.max(np.abs(got - want) / np.maximum(want, floor)))


def compare(got: dict, want: dict) -> dict:
    """The numbers the check compares, by name."""
    n = min(len(got.get("loss_first", [])), len(want["loss_first"]))
    gaps = [
        abs(got[k][r] - want[k][r])
        for k in ("loss_first", "loss_last") for r in range(n)
    ]
    loss_gap = max(gaps) if n == CHECKED_ROUNDS else math.inf
    mism = sum(int(np.count_nonzero(a != b))
               for a, b in zip(got["weights1"], want["weights1"]))
    total = sum(a.size for a in got["weights1"])
    values = {
        "loss_gap": loss_gap,
        "update1_gap": norm_gap(got["norms1"], want["norms1"]),
        "change3_gap": norm_gap(got["norms3"], want["norms3"]),
        "weights1_mismatch": mism / total,
    }
    return values
