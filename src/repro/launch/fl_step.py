"""Distributed PRoBit+ FL round for the production mesh (pjit path).

Cluster-simulated cross-silo FL (DESIGN.md §3): the global model is
FSDP+TP-sharded over ("data", "model"); a ``lax.scan`` multiplexes clients
in time, while the "pod" axis (when present) runs client groups in space.
Per scan step each pod trains ONE client (its batch data-parallel over
"data"), compresses its per-leaf delta through the shared packed wire,
and folds the packed codes into int32 vote counts. After the scan the
Eq.-13 ML estimate updates the global model and the dynamic-b controller
consumes the clients' one-bit loss votes.

Wire contract (per parameter leaf)
----------------------------------
Nothing quantization-related is re-implemented here: the client at cohort
position ``g`` compresses leaf ``l`` with the shared ``ClientCompressor``
(``build_pipeline("probit_plus", rand_bits=...)``) keyed
``fold_in(fold_in(round_key, l), g)`` — the
:mod:`repro.fl.pytree_wire` schedule — so the mesh path, the CPU
simulation (``fl/rounds.py``), the pytree simulation wire, and the Pallas
kernels all emit bit-for-bit the same ``PackedWire`` rows:
``padded_dim(d_l)/8`` uint8 bytes per leaf per client, **1 bit per
parameter on the uplink** (the paper's 32x saving vs f32; leaves with
``size % 8 != 0`` pad with deterministic 0 bits that ``finalize`` slices
off). ``rand_bits=16`` selects the uint16-draw wire (same schedule,
half the RNG memory; see :func:`repro.core.quantizer.threshold_u16` —
saturated |delta| >= b votes stay certain, the sign-flip bug the shared
path regression-guards).

Count-dtype policy
------------------
The uint8 claim applies to the packed *wire rows only*. Vote counts
accumulate in **int32** (matching ``ServerAggregator.init_counts``) —
exact for cohorts up to 2**31 clients; a uint8 accumulator silently
wraps mod 256 past 255 clients (the bug this rewrite fixes). Cross-pod
traffic is the psum of the int32 count pytree induced by the sum over
the pod axis.

State
-----
This step is stateless round-to-round (params, b) -> (params, b): EF
residuals and top-k masks need a per-client per-parameter buffer, which
lives in :class:`repro.fl.pytree_wire.PytreeWireState` on the stateful
simulation path — the mesh step runs the EF-off, dense-packed wire.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..core import build_pipeline
from ..core.bcontrol import BControlConfig, BState, update_b_from_vote
from ..distributed import current_mesh
from ..fl.pytree_wire import leaf_key
from ..models import train_loss
from ..models.config import ModelConfig


@dataclasses.dataclass(frozen=True)
class DistFLConfig:
    clients_per_round: int = 16  # total across pods; must be divisible by n_pods
    local_steps: int = 1
    lr: float = 0.01
    lam: float = 0.2
    b_up: float = 1.01
    b_down: float = 0.98
    # aggregator: "probit_plus" (paper, 1-bit votes) or "fedavg_fp32"
    # (full-precision baseline — what the paper's 32x claim compares against)
    aggregator: str = "probit_plus"
    # quantizer randomness width: 16-bit draws halve the uniform-draw
    # memory vs f32 at a 2^-16 probability granularity (§Perf lever)
    rand_bits: int = 32


def bcontrol_config(fl: DistFLConfig) -> BControlConfig:
    """The b-controller config this step shares with ``fl/rounds.py``."""
    return BControlConfig(mode="dynamic", up=fl.b_up, down=fl.b_down)


def update_b_dist(b: jax.Array, vote: jax.Array, fl: DistFLConfig) -> jax.Array:
    """One controller step from the summed loss-bit vote.

    Routed through :func:`repro.core.bcontrol.update_b_from_vote` — the
    same function the simulation rounds call — so tie-vote handling
    (vote == 0 contracts by ``down``) can never drift between the mesh
    path and ``fl/rounds.py``.
    """
    state = update_b_from_vote(
        BState(b=b, prev_vote=jnp.float32(0.0)), vote, bcontrol_config(fl)
    )
    return state.b


def _n_pods() -> int:
    mesh = current_mesh()
    if mesh is None:
        return 1
    sizes = dict(zip(mesh.axis_names, mesh.axis_sizes))
    return sizes.get("pod", 1)


def _constrain_clients(tree, leaf_specs):
    """Constrain a (n_pods, ...)-leading pytree: leading dim over "pod"."""
    mesh = current_mesh()
    if mesh is None or "pod" not in mesh.axis_names:
        return tree

    def one(x, spec):
        return jax.lax.with_sharding_constraint(x, P("pod", *spec))

    return jax.tree.map(one, tree, leaf_specs)


def _constrain_pod(tree):
    """Constrain wire/count leaves (n_pods, ...): leading dim over "pod"."""
    mesh = current_mesh()
    if mesh is None or "pod" not in mesh.axis_names:
        return tree
    return jax.tree.map(
        lambda x: jax.lax.with_sharding_constraint(x, P("pod")), tree
    )


def make_fl_train_step(cfg: ModelConfig, fl: DistFLConfig, param_specs):
    """Returns train_step(params, b, batch, key) -> (params, b, metrics).

    batch leaves: (m_seq, n_pods, local_steps, per_batch, ...) where
    m_seq * n_pods = clients_per_round. Metrics include the per-round
    uplink ``wire_bytes`` (packed, as shipped); the int8 and f32 baselines
    are static (:func:`repro.fl.pytree_wire.pytree_wire_bytes`).

    Each phase of the round runs under a ``jax.named_scope``
    (``fl.train``, ``fl.compress``, ``fl.count``, ``fl.finalize``,
    ``fl.update``), which names its ops in a profiler trace and changes
    nothing in the compiled program.
    """

    # The full shared pipeline: Eq.-5 compressor (client half) and the
    # count-accumulate -> Eq.-13 server half — the same objects the CPU
    # simulation and the kernels dispatch through.
    pipeline = build_pipeline("probit_plus", rand_bits=fl.rand_bits)
    compressor, server = pipeline.compressor, pipeline.server

    def train_step(params, b, batch, key):
        m_seq = jax.tree.leaves(batch)[0].shape[0]
        n_pods = jax.tree.leaves(batch)[0].shape[1]
        m_total = m_seq * n_pods
        probit = fl.aggregator == "probit_plus"

        p_leaves, treedef = jax.tree_util.tree_flatten(params)
        dims = [int(w.size) for w in p_leaves]
        pbytes = [compressor.wire_bytes(d) for d in dims]

        def one_client(client_batch, gidx):
            """client_batch leaves: (local_steps, per_batch, ...); ``gidx``
            is the client's cohort position — it keys the quantizer rows."""

            def lstep(local, sb):
                loss, g = jax.value_and_grad(train_loss)(local, sb, cfg)
                new = jax.tree.map(
                    lambda w, gg, w0: (
                        w - fl.lr * (gg.astype(jnp.float32) + fl.lam * (w - w0).astype(jnp.float32))
                    ).astype(w.dtype),
                    local,
                    g,
                    params,
                )
                return new, loss

            with jax.named_scope("fl.train"):
                local, losses = jax.lax.scan(lstep, params, client_batch)
                delta = jax.tree.map(lambda a, c: a - c, local, params)
            if probit:
                # Flatten each leaf to 1-D before adding the client axis:
                # the TPU compiler emits code that grows with the leaf for
                # a direct (L, r, c) -> (1, d) relayout (~45 s and 23 MB
                # per 27.5M-element leaf on v5e); the barrier keeps XLA
                # from merging the two reshapes back into that one.
                with jax.named_scope("fl.compress"):
                    d_leaves = [
                        jax.lax.optimization_barrier(dl.reshape(-1))
                        for dl in jax.tree.leaves(delta)
                    ]
                    out = [
                        compressor.compress(
                            leaf_key(key, i),
                            dl[None].astype(jnp.float32),
                            b,
                            jnp.zeros((), jnp.float32),  # EF off on the mesh path
                            row_offset=gidx,
                        )[0].packed
                        for i, (dl, d) in enumerate(zip(d_leaves, dims))
                    ]
            else:
                out = delta  # full-precision upload (FedAvg baseline)
            return out, (losses[0], losses[-1])

        def client_chunk(carry, xs):
            """Per-pod partial accumulation: the (n_pods, ...) accumulator
            stays sharded over "pod", so the client loop is collective-free
            across pods; ONE deferred psum happens after the scan. The
            uplink itself is the packed uint8 wire (1 bit/param/client);
            what crosses pods is the int32 count pytree."""
            acc, votes = carry
            cb, s = xs  # leaves (n_pods, local_steps, pb, ...); s = scan step
            gidx = s * n_pods + jnp.arange(n_pods)
            contrib, (l0, l1) = jax.vmap(one_client)(cb, gidx)
            if probit:
                # contrib: per-leaf packed (n_pods, 1, P_i) uint8 wire rows
                contrib = _constrain_pod(contrib)
                acc = [
                    jax.vmap(server.accumulate_counts)(a, w)
                    for a, w in zip(acc, contrib)
                ]
            else:
                contrib = _constrain_clients(contrib, param_specs)
                acc = jax.tree.map(
                    lambda c, d: c + d.astype(jnp.float32), acc, contrib
                )
            with jax.named_scope("fl.update"):
                votes = votes + jnp.sum(jnp.where(l1 < l0, 1, -1))
                losses = (jnp.mean(l0), jnp.mean(l1))
            return (acc, votes), losses

        if probit:
            # per-leaf int32 vote-count carries, one row per pod
            acc0 = [
                jnp.tile(server.init_counts(p)[None], (n_pods, 1))
                for p in pbytes
            ]
            acc0 = _constrain_pod(acc0)
        else:
            acc0 = jax.tree.map(
                lambda w: jnp.zeros((n_pods,) + w.shape, jnp.float32), params
            )
            acc0 = _constrain_clients(acc0, param_specs)
        (acc, votes), (loss0, loss1) = jax.lax.scan(
            client_chunk, (acc0, jnp.int32(0)), (batch, jnp.arange(m_seq))
        )
        # the single cross-pod reduction: int32 counts (exact up to 2**31
        # clients — NOT the uint8 wire dtype) / f32 delta sums
        if probit:
            with jax.named_scope("fl.count"):
                acc = [jnp.sum(a, axis=0, dtype=jnp.int32) for a in acc]

            # Eq. 13 ML estimate per leaf from the exact vote counts
            with jax.named_scope("fl.update"):
                new_leaves = [
                    (
                        w.astype(jnp.float32)
                        + server.finalize(cnt, m_total, compressor.b_vector(d, b)).reshape(w.shape)
                    ).astype(w.dtype)
                    for w, cnt, d in zip(p_leaves, acc, dims)
                ]
            new_params = jax.tree_util.tree_unflatten(treedef, new_leaves)
            wire_row_bytes = sum(pbytes)
        else:
            acc = jax.tree.map(lambda a: jnp.sum(a, axis=0), acc)
            new_params = jax.tree.map(
                lambda s, w: (w.astype(jnp.float32) + s / m_total).astype(w.dtype),
                acc,
                params,
            )
            wire_row_bytes = 4 * sum(dims)

        with jax.named_scope("fl.update"):
            b_new = update_b_dist(b, votes, fl)
            metrics = {
                "loss_first": jnp.mean(loss0),
                "loss_last": jnp.mean(loss1),
                "b": b_new,
                # f32 round-trips ~7 digits; exact ints come from
                # fl.pytree_wire.pytree_wire_bytes (static, outside the jit)
                "wire_bytes": jnp.float32(m_total * wire_row_bytes),
            }
        return new_params, b_new, metrics

    return train_step
