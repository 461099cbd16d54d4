"""Pure functional FL round core — paper Algorithm 1 as state -> state.

Three round variants share one client-side recipe (participation
sampling, local prox-training, delta attack, Eq.-5 compression):

* :func:`fl_round` — the paper's synchronous protocol, *dense* execution:
  all M sampled clients train under one ``vmap`` and the full
  ``(M, d_pad/8)`` wire materializes before the estimate
  (:func:`_client_uploads`);
* :func:`stream_fl_round` — the same synchronous protocol under a
  **chunked execution model**: the cohort is scanned in chunks of
  ``FLConfig.client_chunk`` clients (``lax.scan``), and each chunk's
  train -> attack -> compress -> count-accumulate pipeline folds into
  additive carries (packed vote counts, the b-controller's loss-bit vote,
  metric sums). Resident memory is **O(client_chunk * d/8)** for the wire
  plus O(d) for the accumulators — independent of M — which is what lets
  a single CPU host run million-client PRoBit+ rounds. Per-client PRNG is
  counter-derived (batches keyed ``fold_in(kb, client_id)``, quantizer
  rows keyed ``fold_in(k_q, cohort_position)``), so under
  ``jax_threefry_partitionable`` any chunking of the cohort draws exactly
  the dense round's bits: count-streaming schemes (PRoBit+ / signSGD-MV /
  RSA) are *bit-identical* to :func:`fl_round` in eager mode and agree to
  1e-6 under jit (reassociation only). Byzantine membership, active-client
  masks, and staleness-style weights all enter as per-chunk row weights
  folded into the same accumulation. With ``FLConfig.stream_shard`` the
  chunk scan itself is sharded across the campaign mesh
  (:func:`repro.launch.mesh.make_campaign_mesh`): each device scans its
  slice of the client axis and the additive carries ``psum`` — the
  weighted-count reduction is the cross-device collective.
* :func:`async_fl_round` — buffered-asynchronous rounds (beyond paper):
  uploads arrive per a latency model, the server estimates from a bounded
  staleness buffer with age-weighted vote counts, and the ``straggler``
  timing adversary can withhold Byzantine uploads. See
  :class:`AsyncRoundState` / :func:`async_fl_round` for exactly which
  paper assumptions are relaxed.

This module is the engine under both execution harnesses:

* :class:`repro.fl.FLSimulation` — the stateful, host-driven wrapper that
  keeps the original experiment API (one jitted round per Python-loop
  iteration, host-side eval every ``eval_every`` rounds);
* :mod:`repro.sim` — the campaign engine, which runs *whole scenario
  grids* as one computation: :func:`run_rounds` multi-rounds via
  ``lax.scan`` and is vmapped over (cell, seed) batches.

The split between static and traced scenario state is what makes the
vmapping possible:

* :class:`RoundContext` — everything that shapes the trace: the
  :class:`~repro.fl.runtime.FLConfig`, task functions, client data, the
  resolved :class:`~repro.core.AggregatorPipeline`, and the static
  ``flip_n`` of the ``bit_flip`` wire adversary. One context == one XLA
  program; cells sharing a context can be batched.
* :class:`CellParams` — per-cell *traced* scenario knobs (lr, momentum,
  prox weight, delta-attack id, wire-flip gate). Cells that differ only
  here ride one vmapped trace (the attack id dispatches via
  ``lax.switch``, see :func:`repro.core.attacks.apply_attack`).
* :class:`RoundState` — the evolving per-run state (global/local weights,
  dynamic-b controller, error-feedback residuals).

:func:`fl_round` reproduces the pre-refactor ``FLSimulation._round_impl``
operation-for-operation (same RNG schedule: client batches from one key,
attack/quantizer keys from ``fold_in(key, 1)``, participation sampling
from ``fold_in(key, 99)``), so a campaign cell at a fixed seed matches the
sequential simulation to float tolerance.

Each phase of a round runs under a ``jax.named_scope``: ``fl.gather``
(cohort choice and the gathers of its state and batches), ``fl.train``,
``fl.attack``, ``fl.compress`` and ``fl.count`` / ``fl.finalize`` (in
:mod:`repro.core.aggregation`), ``fl.update`` (b-control, the global step,
metrics) and ``fl.writeback`` (the per-client state). The scopes name the
ops in a profiler trace (``bench/phases.py``); the compiled program is the
same without them.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.flatten_util import ravel_pytree

from ..core import (
    BState,
    DenseWire,
    apply_attack,
    attack_id as _attack_id,
    init_b_state,
    is_timing_attack,
    is_wire_attack,
    loss_bit,
    staleness_weights,
    update_b,
)
from ..core.attacks import apply_attack_stream
from ..core.bcontrol import update_b_from_vote
from ..optim import local_prox_train

__all__ = [
    "RoundState",
    "AsyncRoundState",
    "CellParams",
    "RoundContext",
    "make_context",
    "init_state",
    "init_async_state",
    "init_run_state",
    "cell_params",
    "client_mask",
    "round_batches",
    "fl_round",
    "stream_fl_round",
    "async_fl_round",
    "round_fn",
    "evaluate",
    "run_rounds",
]


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class RoundState:
    """Evolving state of one FL run (all leaves are device arrays)."""

    w_global: jax.Array  # (d,)
    w_locals: jax.Array  # (n_clients, d) personal models
    b: BState  # dynamic-b controller state
    residuals: jax.Array  # (n_clients, d) error-feedback residuals


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class AsyncRoundState:
    """State of one *buffered-asynchronous* FL run (paper assumption relaxed).

    The paper's Theorems 2-4 analyze synchronous rounds: all M sampled
    clients upload in lockstep and the server estimates from exactly this
    round's codes. ``AsyncRoundState`` relaxes that arrival assumption —
    the server keeps a bounded buffer of the last-arrived packed one-bit
    uploads (one wire row per slot) tagged with staleness ages, and each
    round estimates from the *buffer*, not the fresh cohort. Everything
    else (Eq. 5 compression, the packed uint8 wire, the Eq. 13 estimate
    shape, the dynamic-b controller) is unchanged; staleness enters only
    as a per-row weight folded into the vote counts.

    The first four fields mirror :class:`RoundState` (the sync state
    embeds structurally, so drivers can read ``w_global`` etc. off either).
    """

    w_global: jax.Array  # (d,)
    w_locals: jax.Array  # (n_clients, d) personal models
    b: BState  # dynamic-b controller state
    residuals: jax.Array  # (n_clients, d) error-feedback residuals
    buf_rows: jax.Array  # (B, P) uint8 packed wire rows | (B, d) f32 dense
    buf_age: jax.Array  # (B,) int32 rounds since the slot's upload arrived
    buf_valid: jax.Array  # (B,) bool slot holds an upload
    buf_owner: jax.Array  # (B,) int32 client index that wrote the slot (-1)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class CellParams:
    """Traced per-cell scenario knobs — the vmappable campaign axes.

    Leaves may be Python scalars (the simulation path closes over them, so
    they fold into the trace as constants, reproducing the pre-refactor
    program exactly) or batched arrays (the campaign path maps over them).
    """

    lr: Any
    momentum: Any
    lam: Any
    attack_id: Any  # int index into repro.core.ATTACK_IDS (delta stage)
    flip_gate: Any  # bool: arm the bit_flip wire adversary (needs flip_n>0)
    latency: Any  # f32 mean upload latency in rounds; P(arrive) = 1/(1+lat)
    staleness_decay: Any  # f32 age-weight exponent: w(age) = (1+age)^(-decay)
    straggler_gate: Any  # bool: arm the straggler timing adversary
    # Number of *real* clients in this cell. Only read when the context is
    # ``masked`` (a fused heterogeneous-M campaign group): the client axis
    # is padded to the group max and rows >= m_active are masked out of
    # the estimate, the b-vote, and the metrics — M moves from a static
    # shape to a traced value. Unmasked contexts ignore it entirely, so
    # the single-config path compiles the exact pre-refactor program.
    m_active: Any = None


@dataclasses.dataclass(frozen=True)
class RoundContext:
    """Static context closed over by the round functions (not a pytree).

    Two cells can share a context — and therefore a compiled program —
    iff every field here compares equal (the campaign engine groups by the
    FLConfig fields this depends on; see ``repro.sim.campaign``).
    """

    cfg: Any  # FLConfig (static hyperparameters & shapes)
    loss_fn: Callable  # loss_fn(params_pytree, {"x","y"}) -> scalar
    acc_fn: Callable
    unravel: Callable
    pipeline: Any  # repro.core.AggregatorPipeline
    w0: jax.Array  # (d,) flat initial parameters
    client_x: jax.Array  # (n_clients, per_client, ...)
    client_y: jax.Array  # (n_clients, per_client)
    test: dict
    flip_n: int  # rows bit-flipped on the wire when a cell's flip_gate is on
    # True for fused heterogeneous-M campaign groups: the client axis is
    # padded to the group max and every round threads the 0/1 active-client
    # mask (rows < CellParams.m_active) through the estimate, the b-vote,
    # and the metrics. False compiles the exact unmasked program.
    masked: bool = False

    @property
    def d(self) -> int:
        return self.w0.shape[0]


def make_context(
    cfg,
    init_params,
    loss_fn: Callable,
    acc_fn: Callable,
    client_x,
    client_y,
    test: dict,
    *,
    wire_flip: bool | None = None,
    masked: bool = False,
) -> RoundContext:
    """Resolve a config + task into a RoundContext.

    ``wire_flip`` arms the static wire-flip slot even when ``cfg.attack``
    itself is not ``bit_flip`` — the campaign engine sets it when *any*
    cell in a vmapped group is a bit_flip cell (per-cell ``flip_gate``
    then selects). ``masked`` marks a fused heterogeneous-M context whose
    client axis is padded (``cfg.n_clients`` is the group max; the real
    per-cell M arrives as the traced ``CellParams.m_active``).
    """
    w0, unravel = ravel_pytree(init_params)
    if wire_flip is None:
        wire_flip = is_wire_attack(cfg.attack)
    if cfg.stream_shard:
        import warnings

        n_dev = len(jax.devices())
        if n_dev <= 1:
            warnings.warn(
                "stream_shard is a no-op: only one local device is visible. "
                "For CPU scaling runs set "
                "XLA_FLAGS=--xla_force_host_platform_device_count=N before "
                "importing jax.",
                RuntimeWarning,
            )
        elif cfg.n_active % n_dev:
            warnings.warn(
                f"stream_shard falling back to a single-device scan: "
                f"cohort size {cfg.n_active} does not divide across "
                f"{n_dev} devices.",
                RuntimeWarning,
            )
    if cfg.tree_shard:
        import warnings

        n_dev = len(jax.devices())
        if n_dev <= 1:
            warnings.warn(
                "tree_shard is a no-op: only one local device is visible. "
                "For CPU scaling runs set "
                "XLA_FLAGS=--xla_force_host_platform_device_count=N before "
                "importing jax.",
                RuntimeWarning,
            )
        elif cfg.tree_edges % n_dev:
            warnings.warn(
                f"tree_shard falling back to a host-loop edge sweep: "
                f"{cfg.tree_edges} edges do not divide across "
                f"{n_dev} devices.",
                RuntimeWarning,
            )
    if masked and (cfg.async_buffer or cfg.participation < 1.0):
        raise ValueError(
            "masked (fused heterogeneous-M) contexts require synchronous "
            "rounds at full participation; see repro.sim.plan.fusable"
        )
    n_byz = int(cfg.n_active * cfg.byz_frac)
    return RoundContext(
        cfg=cfg,
        loss_fn=loss_fn,
        acc_fn=acc_fn,
        unravel=unravel,
        pipeline=cfg.pipeline(),
        w0=w0,
        client_x=jnp.asarray(client_x),
        client_y=jnp.asarray(client_y),
        test={k: jnp.asarray(v) for k, v in test.items()},
        flip_n=n_byz if wire_flip else 0,
        masked=masked,
    )


def init_state(ctx: RoundContext, b_init=None) -> RoundState:
    """Fresh run state; ``b_init`` overrides the config's (may be traced).

    ``stateless_clients`` collapses the per-client state planes to one
    broadcast row — clients train from ``w_global`` each round and carry
    nothing, so the server holds O(d) state however large M grows.
    """
    cfg = ctx.cfg
    if b_init is None:
        b = init_b_state(cfg.bctrl)
    else:
        b = BState(b=jnp.asarray(b_init, jnp.float32), prev_vote=jnp.float32(0.0))
    n_rows = 1 if cfg.stateless_clients else cfg.n_clients
    return RoundState(
        w_global=ctx.w0,
        w_locals=jnp.tile(ctx.w0[None], (n_rows, 1)),
        b=b,
        residuals=jnp.zeros((n_rows, ctx.w0.shape[0]), jnp.float32),
    )


def init_async_state(ctx: RoundContext, b_init=None) -> AsyncRoundState:
    """Fresh async run state: empty staleness buffer, sync fields as usual.

    Buffer row shape follows the pipeline's wire format (packed uint8 for
    bit schemes, dense f32 for FedAvg / Fed-GM); all slots start invalid,
    so an estimate before any arrival is zero.
    """
    cfg = ctx.cfg
    base = init_state(ctx, b_init)
    n_bytes = ctx.pipeline.compressor.wire_bytes(ctx.d)
    if n_bytes is None:
        rows = jnp.zeros((cfg.async_buffer, ctx.d), jnp.float32)
    else:
        rows = jnp.zeros((cfg.async_buffer, n_bytes), jnp.uint8)
    return AsyncRoundState(
        w_global=base.w_global,
        w_locals=base.w_locals,
        b=base.b,
        residuals=base.residuals,
        buf_rows=rows,
        buf_age=jnp.zeros((cfg.async_buffer,), jnp.int32),
        buf_valid=jnp.zeros((cfg.async_buffer,), bool),
        buf_owner=jnp.full((cfg.async_buffer,), -1, jnp.int32),
    )


def init_run_state(ctx: RoundContext, b_init=None):
    """The state the context's config calls for (sync, async, or tree)."""
    if ctx.cfg.async_buffer:
        return init_async_state(ctx, b_init)
    if ctx.cfg.tree_edges and ctx.cfg.edge_buffer:
        from .hierarchy import init_tree_state

        return init_tree_state(ctx, b_init)
    return init_state(ctx, b_init)


def round_fn(ctx: RoundContext):
    """The round function matching the context (sync, streamed, async, tree)."""
    if ctx.cfg.async_buffer:
        return async_fl_round
    if ctx.cfg.tree_edges:
        from .hierarchy import tree_fl_round

        return tree_fl_round
    if ctx.cfg.client_chunk:
        return stream_fl_round
    return fl_round


def cell_params(cfg) -> CellParams:
    """The CellParams a single FLConfig describes (scalar leaves)."""
    return CellParams(
        lr=cfg.lr,
        momentum=cfg.momentum,
        lam=cfg.lam,
        attack_id=_attack_id(cfg.attack),
        flip_gate=is_wire_attack(cfg.attack),
        latency=cfg.async_latency,
        staleness_decay=cfg.staleness_decay,
        straggler_gate=is_timing_attack(cfg.attack),
        m_active=cfg.n_active,
    )


def client_mask(ctx: RoundContext, params: CellParams) -> jax.Array | None:
    """The 0/1 active-client row mask of a masked (fused) context.

    ``None`` for unmasked contexts — every weighted path downstream
    (estimate, b-vote, metric means) treats ``None`` as "use the exact
    unweighted ops", preserving bit-exactness of single-M execution.
    """
    if not ctx.masked:
        return None
    return (
        jnp.arange(ctx.cfg.n_active) < jnp.asarray(params.m_active)
    ).astype(jnp.float32)


def _batch_steps(ctx: RoundContext) -> int:
    cfg = ctx.cfg
    per_client = ctx.client_x.shape[1]
    return max(cfg.local_epochs * per_client // cfg.batch_size, 1)


def _client_batch_idx(ctx: RoundContext, key: jax.Array, client_id) -> jax.Array:
    """Client ``client_id``'s batch indices for the round keyed by ``key``.

    Keyed by *global client id* via ``fold_in``, not a position in one
    blocked ``(n_clients, ...)`` draw — so the streaming round can draw
    any client's batches inside its chunk scan and get exactly the indices
    the dense round drew for that client (``jax_threefry_partitionable``
    makes the fold_in schedule stable across chunkings).
    """
    cfg = ctx.cfg
    per_client = ctx.client_x.shape[1]
    return jax.random.randint(
        jax.random.fold_in(key, client_id),
        (_batch_steps(ctx), cfg.batch_size),
        0,
        per_client,
    )


def round_batches(ctx: RoundContext, key: jax.Array) -> dict:
    """Sample one round's local-training batches for every client.

    Streaming contexts (``client_chunk > 0``) defer the draw: the chunk
    scan materializes only its own C clients' batches, so the full
    ``(n_clients, steps, batch)`` gather never exists — the round key is
    passed through instead.
    """
    cfg = ctx.cfg
    if cfg.client_chunk:
        return {"key": key}
    idx = jax.vmap(lambda m: _client_batch_idx(ctx, key, m))(
        jnp.arange(cfg.n_clients)
    )
    bx = jax.vmap(lambda x, i: x[i])(ctx.client_x, idx)
    by = jax.vmap(lambda y, i: y[i])(ctx.client_y, idx)
    return {"x": bx, "y": by}


def _client_uploads(ctx, params, key, state, batches):
    """The client side of a round, shared by the sync and async variants:
    participation sampling, local prox-training, delta attack, and
    compression onto the wire. Returns everything the two server variants
    need; the RNG schedule is byte-identical between them, which is half
    of the zero-latency bit-exactness guarantee (the other half is the
    unit-weight count path, see ``packed_weighted_counts``)."""
    cfg = ctx.cfg
    w_global = state.w_global
    with jax.named_scope("fl.gather"):
        if cfg.participation < 1.0:
            sel = jax.random.choice(
                jax.random.fold_in(key, 99), cfg.n_clients,
                (cfg.n_active,), replace=False,
            )
        else:
            sel = jnp.arange(cfg.n_clients)
        w_sel = state.w_locals[sel]
        res_sel = state.residuals[sel]
        batches = jax.tree.map(lambda a: a[sel], batches)

    def client(w_local, cb, ck):
        return local_prox_train(
            ctx.loss_fn,
            w_global,
            w_local,
            ctx.unravel,
            cb,
            lr=params.lr,
            mu=params.momentum,
            lam=params.lam,
            use_kernel=cfg.use_kernels,
        )

    with jax.named_scope("fl.train"):
        ckeys = jax.random.split(key, cfg.n_active)
        w_new, loss_before, loss_after = jax.vmap(client)(w_sel, batches, ckeys)
        deltas = w_new - w_global[None]

    with jax.named_scope("fl.attack"):
        k_att, k_q = jax.random.split(jax.random.fold_in(key, 1))
        n_byz = int(cfg.n_active * cfg.byz_frac)
        deltas_att = apply_attack(params.attack_id, k_att, deltas, n_byz)

    wire, res_new = ctx.pipeline.compress_wire(
        k_q, deltas_att, state.b.b, res_sel,
        flip_n=ctx.flip_n, flip_gate=params.flip_gate,
    )
    return sel, w_new, loss_before, loss_after, deltas_att, wire, res_new


def _finish_round(ctx, state, sel, w_new, loss_before, loss_after, res_new, theta, deltas_att, state_cls, mask=None, **extra):
    """Server epilogue shared by both variants: global step, b-control,
    state write-back, metrics.

    ``mask`` (fused heterogeneous-M groups only) is the 0/1 active-client
    row mask: padded clients cast no b-vote and drop out of the loss /
    theta_mse means. ``None`` keeps the exact unmasked ops.
    """
    cfg = ctx.cfg
    with jax.named_scope("fl.update"):
        bits = jax.vmap(loss_bit)(loss_before, loss_after)
        b_new = update_b(state.b, bits, cfg.bctrl, weights=mask)
        w_global = state.w_global + theta
    with jax.named_scope("fl.writeback"):
        w_locals = state.w_locals.at[sel].set(w_new)
        residuals = state.residuals.at[sel].set(res_new)
    new_state = state_cls(
        w_global=w_global,
        w_locals=w_locals,
        b=b_new,
        residuals=residuals,
        **extra,
    )
    with jax.named_scope("fl.update"):
        if mask is None:
            loss = jnp.mean(loss_after)
            delta_mean = jnp.mean(deltas_att, axis=0)
        else:
            m_eff = jnp.maximum(jnp.sum(mask), 1.0)
            loss = jnp.sum(loss_after * mask) / m_eff
            delta_mean = jnp.sum(deltas_att * mask[:, None], axis=0) / m_eff
        metrics = {
            "loss": loss,
            "b": b_new.b,
            "theta_mse": jnp.mean((theta - delta_mean) ** 2),
        }
    return new_state, metrics


def fl_round(
    ctx: RoundContext,
    params: CellParams,
    key: jax.Array,
    state: RoundState,
    batches: dict,
) -> tuple[RoundState, dict]:
    """One FL round: local prox-training, attack, aggregate, b-control.

    Returns the next state and per-round metrics: ``loss`` (mean post-
    training local loss), ``b`` (controller value after the vote), and
    ``theta_mse`` — the mean squared error of the aggregated ``theta_hat``
    against the true mean of the (post-attack) uploaded updates, i.e. the
    pure aggregation error the paper's Theorem 1 bounds at O(1/M).

    Under a ``masked`` context (fused heterogeneous-M campaign group) the
    active-client mask rides the *weighted* count path of PR 3 into the
    Eq. 13 vote counts: ``N_i^w`` sums only real clients and the effective
    cohort ``M^w = m_active`` is traced, so one compiled program serves
    every M in the group while the wire format is unchanged.
    """
    sel, w_new, loss_before, loss_after, deltas_att, wire, res_new = (
        _client_uploads(ctx, params, key, state, batches)
    )
    mask = client_mask(ctx, params)
    theta = ctx.pipeline.estimate(wire, weights=mask)
    return _finish_round(
        ctx, state, sel, w_new, loss_before, loss_after, res_new,
        theta, deltas_att, RoundState, mask=mask,
    )


def _scan_chunks(
    ctx: RoundContext,
    params: CellParams,
    kb: jax.Array,
    k_att: jax.Array,
    k_q: jax.Array,
    w_global: jax.Array,
    b_scalar: jax.Array,
    w_locals: jax.Array | None,
    residuals: jax.Array | None,
    sel_rows: jax.Array,
    client_x: jax.Array,
    client_y: jax.Array,
    data_offset,
    row0,
    limit,
    n_byz: int,
    weighted: bool,
) -> dict:
    """Scan one shard of the client axis in chunks of ``cfg.client_chunk``.

    ``sel_rows`` are the shard's selected client ids in cohort order;
    ``row0`` is the global cohort position of its first row (device
    ``k`` of a sharded scan passes ``k * n_local``), which keys the
    per-row quantizer streams, Byzantine membership, and wire flips;
    ``data_offset`` maps client ids to rows of the (possibly device-local)
    ``client_x`` block. Rows at cohort position >= ``limit`` carry weight
    zero (fused heterogeneous-M masks and chunk padding alike).

    Returns the additive carries: the stream accumulator ``acc`` (packed
    vote counts / weighted dense sum / row buffer, per the server's
    ``stream_kind``), the b-controller vote, the loss and delta sums, the
    effective cohort weight ``wsum``, and — stateful mode only — the
    written-back per-client planes. Every carry except the fed_gm row
    buffer is O(d), which is the streaming memory bound.
    """
    cfg = ctx.cfg
    C = cfg.client_chunk
    d = ctx.d
    server = ctx.pipeline.server
    kind = server.stream_kind
    n_loc = sel_rows.shape[0]
    n_chunks = -(-n_loc // C)
    n_pad = n_chunks * C
    # Padded tail rows wrap onto earlier clients; their weight is zero and
    # their state write-back is dropped, so the duplicates are inert.
    sel_p = sel_rows[jnp.arange(n_pad) % n_loc]
    stateless = cfg.stateless_clients
    steps = _batch_steps(ctx)

    if kind == "counts":
        p_bytes = ctx.pipeline.compressor.wire_bytes(d)
        acc0 = server.init_counts(p_bytes, weighted=weighted)
    elif kind == "sum":
        acc0 = server.init_stream_sum(d)
    else:  # "buffer" — fed_gm touches every row per Weiszfeld iteration
        acc0 = jnp.zeros((n_pad, d), jnp.float32)

    carry0 = dict(
        acc=acc0,
        vote=jnp.float32(0.0),
        loss=jnp.float32(0.0),
        dsum=jnp.zeros((d,), jnp.float32),
        wsum=jnp.float32(0.0),
    )
    if not stateless:
        carry0["w_locals"] = w_locals
        carry0["residuals"] = residuals

    def body(carry, g0):
        local = g0 + jnp.arange(C)  # shard-local row positions
        gidx = row0 + local  # global cohort positions
        sel_c = jax.lax.dynamic_slice(sel_p, (g0,), (C,))
        # Padded tail rows must mask on the *local* axis: a sharded scan's
        # pad rows carry global positions that run into the next shard's
        # range, where `gidx < limit` alone would leave them weighted.
        w_c = ((gidx < limit) & (local < n_loc)).astype(jnp.float32)

        with jax.named_scope("fl.gather"):
            idx = jax.vmap(lambda m: _client_batch_idx(ctx, kb, m))(sel_c)
            rows = sel_c - data_offset
            bx = jax.vmap(lambda r, i: client_x[r][i])(rows, idx)
            by = jax.vmap(lambda r, i: client_y[r][i])(rows, idx)

            if stateless:
                w_start = jnp.broadcast_to(w_global, (C, d))
                res_c = jnp.zeros((C, d), jnp.float32)
            else:
                w_start = carry["w_locals"][sel_c]
                res_c = carry["residuals"][sel_c]

        def client(w_local, cb):
            return local_prox_train(
                ctx.loss_fn,
                w_global,
                w_local,
                ctx.unravel,
                cb,
                lr=params.lr,
                mu=params.momentum,
                lam=params.lam,
                use_kernel=cfg.use_kernels,
            )

        with jax.named_scope("fl.train"):
            w_new, loss_before, loss_after = jax.vmap(client)(
                w_start, {"x": bx, "y": by}
            )
            deltas = w_new - w_global[None]
        with jax.named_scope("fl.attack"):
            deltas_att = apply_attack_stream(
                params.attack_id, k_att, deltas, gidx < n_byz, gidx
            )
        wire, res_new = ctx.pipeline.compress_wire(
            k_q,
            deltas_att,
            b_scalar,
            res_c,
            flip_n=ctx.flip_n,
            flip_gate=params.flip_gate,
            row_offset=row0 + g0,
        )

        if kind == "counts":
            acc = server.accumulate_counts(
                carry["acc"], wire.packed, w_c if weighted else None
            )
        elif kind == "sum":
            acc = server.accumulate_sum(carry["acc"], wire.updates, w_c)
        else:
            acc = jax.lax.dynamic_update_slice(
                carry["acc"], wire.updates, (g0, 0)
            )

        bits = jax.vmap(loss_bit)(loss_before, loss_after).astype(jnp.float32)
        new = dict(
            acc=acc,
            vote=carry["vote"] + jnp.sum(bits * w_c),
            loss=carry["loss"] + jnp.sum(loss_after * w_c),
            dsum=carry["dsum"] + jnp.sum(deltas_att * w_c[:, None], axis=0),
            wsum=carry["wsum"] + jnp.sum(w_c),
        )
        if not stateless:
            # mode="drop": padded wrap rows target index n_clients (out of
            # bounds) so they cannot clobber a real client's row.
            with jax.named_scope("fl.writeback"):
                tgt = jnp.where(local < n_loc, sel_c, cfg.n_clients)
                new["w_locals"] = carry["w_locals"].at[tgt].set(
                    w_new, mode="drop"
                )
                new["residuals"] = (
                    carry["residuals"].at[tgt].set(res_new, mode="drop")
                )
        return new, None

    carry, _ = jax.lax.scan(body, carry0, jnp.arange(n_chunks) * C)
    return carry


def _stream_shard_devices(ctx: RoundContext) -> int:
    """How many devices the streaming scan shards over (1 = unsharded)."""
    cfg = ctx.cfg
    if not cfg.stream_shard:
        return 1
    n_dev = len(jax.devices())
    if n_dev <= 1 or cfg.n_active % n_dev:
        return 1
    return n_dev


def _sharded_scan(
    ctx: RoundContext,
    params: CellParams,
    kb: jax.Array,
    k_att: jax.Array,
    k_q: jax.Array,
    w_global: jax.Array,
    b_scalar: jax.Array,
    limit,
    n_byz: int,
    weighted: bool,
    n_dev: int,
) -> dict:
    """:func:`_scan_chunks` sharded over the campaign mesh's client slices.

    Each device scans its contiguous ``n_active / n_dev`` client rows
    (``stream_shard`` validation pins participation to 1.0, so cohort
    position == client id and the client data shards as plain blocks) and
    the additive carries ``psum`` — the weighted-count reduction is the
    only cross-device collective. Stateful planes are excluded by the
    ``stateless_clients`` requirement.
    """
    from jax.sharding import PartitionSpec as P

    from ..launch.mesh import make_campaign_mesh

    cfg = ctx.cfg
    n_loc = cfg.n_active // n_dev
    mesh = make_campaign_mesh(n_dev)

    def body(cx, cy, kb_, ka_, kq_, wg, bs, lim, prm):
        k = jax.lax.axis_index("data")
        row0 = k * n_loc
        sel_rows = row0 + jnp.arange(n_loc)
        carry = _scan_chunks(
            ctx, prm, kb_, ka_, kq_, wg, bs, None, None,
            sel_rows, cx, cy, row0, row0, lim, n_byz, weighted,
        )
        return jax.tree.map(lambda x: jax.lax.psum(x, "data"), carry)

    in_specs = (P("data"), P("data")) + (P(),) * 7
    kwargs = dict(mesh=mesh, in_specs=in_specs, out_specs=P())
    fn = jax.shard_map(body, check_vma=False, **kwargs)
    return fn(
        ctx.client_x, ctx.client_y, kb, k_att, k_q,
        w_global, b_scalar, jnp.asarray(limit, jnp.int32), params,
    )


def stream_fl_round(
    ctx: RoundContext,
    params: CellParams,
    key: jax.Array,
    state: RoundState,
    batches: dict,
) -> tuple[RoundState, dict]:
    """One synchronous FL round under the chunked (streaming) client axis.

    Protocol-identical to :func:`fl_round` — same participation sampling,
    RNG schedule, attack semantics, estimate, b-vote, and metrics — but
    executed as a ``lax.scan`` over ``cfg.client_chunk``-client chunks:
    the wire and update matrices exist only chunk-sized, and the server
    carries additive accumulators (see :func:`_scan_chunks`). Count-
    streaming schemes are bit-identical to the dense round in eager mode;
    jit agreement is 1e-6 (reassociation of f32 partial sums only —
    integer vote counts are exact under any chunking).
    """
    cfg = ctx.cfg
    n = cfg.n_active
    C = cfg.client_chunk
    d = ctx.d
    server = ctx.pipeline.server
    kind = server.stream_kind
    kb = batches["key"]

    if cfg.participation < 1.0:
        sel = jax.random.choice(
            jax.random.fold_in(key, 99), cfg.n_clients,
            (n,), replace=False,
        )
    else:
        sel = jnp.arange(cfg.n_clients)
    k_att, k_q = jax.random.split(jax.random.fold_in(key, 1))
    n_byz = int(n * cfg.byz_frac)
    limit = jnp.asarray(params.m_active) if ctx.masked else n

    n_dev = _stream_shard_devices(ctx)
    n_loc = n // n_dev
    weighted = ctx.masked or (-(-n_loc // C)) * C != n_loc
    if n_dev > 1:
        carry = _sharded_scan(
            ctx, params, kb, k_att, k_q, state.w_global, state.b.b,
            limit, n_byz, weighted, n_dev,
        )
    else:
        carry = _scan_chunks(
            ctx, params, kb, k_att, k_q, state.w_global, state.b.b,
            None if cfg.stateless_clients else state.w_locals,
            None if cfg.stateless_clients else state.residuals,
            sel, ctx.client_x, ctx.client_y, 0, 0, limit, n_byz, weighted,
        )

    acc, vote, wsum = carry["acc"], carry["vote"], carry["wsum"]
    if kind == "counts":
        b_vec = ctx.pipeline.compressor.b_vector(d, state.b.b)
        if weighted:
            est = server.finalize(acc, jnp.maximum(wsum, 1e-12), b_vec)
            theta = jnp.where(wsum > 0, est, 0.0)
        else:
            theta = server.finalize(acc, n, b_vec)
    elif kind == "sum":
        theta = server.finalize_sum(acc)
    else:
        w_all = (jnp.arange(acc.shape[0]) < limit).astype(jnp.float32)
        theta = server.from_dense(acc, w_all if weighted else None)

    b_new = update_b_from_vote(state.b, vote, cfg.bctrl)
    new_state = RoundState(
        w_global=state.w_global + theta,
        w_locals=(
            state.w_locals if cfg.stateless_clients else carry["w_locals"]
        ),
        b=b_new,
        residuals=(
            state.residuals if cfg.stateless_clients else carry["residuals"]
        ),
    )
    m_eff = jnp.maximum(wsum, 1.0)
    delta_mean = carry["dsum"] / m_eff
    metrics = {
        "loss": carry["loss"] / m_eff,
        "b": b_new.b,
        "theta_mse": jnp.mean((theta - delta_mean) ** 2),
    }
    return new_state, metrics


def async_fl_round(
    ctx: RoundContext,
    params: CellParams,
    key: jax.Array,
    state: AsyncRoundState,
    batches: dict,
) -> tuple[AsyncRoundState, dict]:
    """One buffered-asynchronous FL round (relaxes the paper's synchrony).

    Assumptions of the paper this variant relaxes, and what replaces them:

    * **Lockstep arrival** (Theorems 2-4 assume all M sampled clients
      upload every round): each client's upload instead *arrives* with
      probability ``1/(1 + latency)`` (``CellParams.latency``, traced, so
      a latency axis vmaps). A non-arriving client leaves its buffer slot
      holding its last delivered upload, one round staler.
    * **Fresh-cohort estimation** (Eq. 13 averages this round's codes):
      the server estimates from its bounded buffer (``async_buffer``
      slots; client m writes slot ``m mod B``, so ``B = M`` is one slot
      per client and ``B < M`` models slot contention under server memory
      pressure). Each buffered row is weighted ``(1+age)^(-staleness_decay)``
      — the weight folds into the vote counts *before* the Eq. 13 MLE
      (``packed_weighted_counts``), so the packed uint8 wire format and
      the estimate shape are unchanged.
    * **Range consistency**: a stale row's bits were drawn against the
      ``b`` of its production round but are estimated under the current
      ``b`` — one-bit codes are range-free votes, and the resulting scale
      error is bounded by the controller's per-round step (``1.01/0.98``)
      to the power of the age.

    The one-bit loss vote for the b-controller and the EF residual
    write-back stay synchronous: both are client-side state or O(1-bit)
    signals that piggyback on the round heartbeat, not model uploads.

    Degenerate parity: with ``async_buffer == n_active``, zero latency,
    and ``staleness_decay == 0`` every slot refreshes every round with
    weight exactly 1.0, and the trajectory is bit-exact with
    :func:`fl_round` (asserted in ``tests/test_async_rounds.py``).

    Extra metrics: ``buf_fill`` (fraction of valid slots) and ``mean_age``
    (mean staleness over valid slots).
    """
    cfg = ctx.cfg
    m_act, n_buf = cfg.n_active, cfg.async_buffer
    sel, w_new, loss_before, loss_after, deltas_att, wire, res_new = (
        _client_uploads(ctx, params, key, state, batches)
    )
    rows = wire.updates if isinstance(wire, DenseWire) else wire.packed

    # Arrival model: Bernoulli(1/(1+latency)) per (round, client). The
    # straggler timing adversary overrides its Byzantine rows' arrivals:
    # a (colluding) Byzantine client delivers only while its slot holds no
    # Byzantine upload, then the cohort withholds — the poisoned upload
    # sits in the buffer at ever-growing staleness, and if a slot-sharing
    # honest client evicts it (B < M), a Byzantine sharer re-delivers to
    # re-poison the slot. Gating on "any Byzantine resident" rather than
    # "my upload resident" keeps colluders from evicting each other
    # (which would reset the slot's age every round).
    p_arrive = 1.0 / (1.0 + params.latency)
    u = jax.random.uniform(jax.random.fold_in(key, 7), (m_act,))
    delivered = u < p_arrive
    slot = jnp.arange(m_act) % n_buf
    n_byz = int(m_act * cfg.byz_frac)
    byz = jnp.arange(m_act) < n_byz
    slot_owner = state.buf_owner[slot]
    byz_resident = (slot_owner >= 0) & (slot_owner < n_byz)
    delivered = jnp.where(params.straggler_gate & byz, ~byz_resident, delivered)

    # Fold the M fresh rows into the B slots, later clients winning shared
    # slots (static unrolled generations keep shapes vmappable).
    n_gen = -(-m_act // n_buf)
    pad = n_gen * n_buf - m_act
    rows_p = jnp.pad(rows, ((0, pad),) + ((0, 0),) * (rows.ndim - 1))
    del_p = jnp.pad(delivered, (0, pad))
    buf, hit = state.buf_rows, jnp.zeros((n_buf,), bool)
    owner = state.buf_owner
    for g in range(n_gen):
        d_g = del_p[g * n_buf : (g + 1) * n_buf]
        r_g = rows_p[g * n_buf : (g + 1) * n_buf]
        buf = jnp.where(d_g.reshape((-1,) + (1,) * (rows.ndim - 1)), r_g, buf)
        owner = jnp.where(d_g, g * n_buf + jnp.arange(n_buf), owner)
        hit = hit | d_g
    age = jnp.where(hit, 0, state.buf_age + 1)
    valid = state.buf_valid | hit

    # Age-weighted estimate from the buffered wire (current public b).
    weights = staleness_weights(age, params.staleness_decay, valid)
    if isinstance(wire, DenseWire):
        buf_wire = DenseWire(updates=buf)
    else:
        buf_wire = dataclasses.replace(wire, packed=buf)
    theta = ctx.pipeline.estimate(buf_wire, weights=weights)

    new_state, metrics = _finish_round(
        ctx, state, sel, w_new, loss_before, loss_after, res_new,
        theta, deltas_att, AsyncRoundState,
        buf_rows=buf, buf_age=age, buf_valid=valid, buf_owner=owner,
    )
    n_valid = jnp.sum(valid.astype(jnp.float32))
    metrics["buf_fill"] = n_valid / n_buf
    metrics["mean_age"] = jnp.sum(
        age.astype(jnp.float32) * valid
    ) / jnp.maximum(n_valid, 1.0)
    return new_state, metrics


def evaluate(ctx: RoundContext, w_global: jax.Array) -> jax.Array:
    """Test accuracy of the flat global model (jittable)."""
    return ctx.acc_fn(ctx.unravel(w_global), ctx.test)


def run_rounds(
    ctx: RoundContext,
    params: CellParams,
    key: jax.Array,
    state: RoundState,
    rounds: int | None = None,
    *,
    with_acc: bool = True,
) -> tuple[RoundState, dict]:
    """Run ``rounds`` FL rounds under ``lax.scan``.

    Follows the exact per-round key schedule of ``FLSimulation.run``
    (``key, kb, kr = split(key, 3)``; batches from ``kb``, round from
    ``kr``), so at a fixed seed this reproduces the sequential driver.
    Returns the final state and the metrics trajectory (each metric is a
    ``(rounds,)`` array; ``acc`` included when ``with_acc``). The round
    variant follows the carried state: an :class:`AsyncRoundState` scans
    :func:`async_fl_round`, a :class:`RoundState` the synchronous round.
    """
    rounds = rounds or ctx.cfg.rounds
    if isinstance(state, AsyncRoundState):
        step = async_fl_round
    elif ctx.cfg.tree_edges:
        from .hierarchy import tree_fl_round

        step = tree_fl_round
    else:
        step = stream_fl_round if ctx.cfg.client_chunk else fl_round

    def body(carry, _):
        key, state = carry
        key, kb, kr = jax.random.split(key, 3)
        batches = round_batches(ctx, kb)
        state, m = step(ctx, params, kr, state, batches)
        if with_acc:
            m = dict(m, acc=evaluate(ctx, state.w_global))
        return (key, state), m

    (_, final_state), traj = jax.lax.scan(body, (key, state), None, length=rounds)
    return final_state, traj
