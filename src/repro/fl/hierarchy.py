"""Hierarchical count-tree aggregation: clients -> edge aggregators -> root.

Because packed vote counts are *additive* (PR 6's ``init_counts /
accumulate_counts / finalize`` protocol), a round does not have to funnel
all M clients through one root: the cohort splits into ``tree_edges``
contiguous client slices, each **edge** runs the exact chunked
count-accumulation scan of the flat streaming round
(:func:`repro.fl.rounds._scan_chunks`) over its slice, and ships the root
only

* its ``(8 * p_bytes,)`` f32 count tensor (the per-plane vote histogram),
* its active-mass scalar (the slice's effective cohort weight), and
* the synchronous round-heartbeat sums (b-controller loss-bit vote, loss /
  delta metric sums) that piggyback on every upload wave.

The root merges E count tensors instead of M uploads — the fan-in that
turns a single-host bottleneck into a tree of independent reductions.

**Bit-exactness (zero staleness).** Per-client PRNG is counter-derived
(batches keyed ``fold_in(kb, client_id)``, quantizer rows keyed by global
cohort position via ``row_offset``, streaming attacks keyed by row id), so
an edge reproduces exactly the bits the flat scan drew for its rows.
Edge partial counts are integer-valued f32 sums of 0/1-weighted bits
(exact below 2**24 clients per the count-dtype policy), and ``jnp.sum``
over the stacked edge axis reassociates *integers* — so the merged root
counts, and therefore ``w_global``, ``b``, EF residuals, and personal
models, are **bit-identical** to :func:`repro.fl.rounds.stream_fl_round`
for every count-streaming scheme (PRoBit+ / signSGD-MV / RSA), any edge
count (including ``E`` not dividing M), under participation sampling and
error feedback. Only the f32 *metric* sums (loss, delta mean) reassociate
non-integrally (~1e-6, the PR-3 precedent).

**Async edges (``edge_buffer > 0``).** Reuses the PR-3 buffered-async
semantics one level up: each edge's shipped (counts, mass) pair arrives
with probability ``1/(1 + CellParams.latency)`` into a bounded root
buffer (edge e writes slot ``e mod B``, later edges winning shared
slots), slots age when their edge misses a round, and the root merge
weights slot tensors ``(1 + age) ** (-CellParams.staleness_decay)``
(:func:`repro.core.staleness_weights`). The b-vote and metric heartbeat
stay synchronous, exactly as PR-3 keeps the loss vote and EF write-back
out of the client buffer. Degenerate parity: ``edge_buffer == tree_edges``
at zero latency and zero decay refreshes every slot every round with
weight exactly 1.0 — bit-identical to the unbuffered tree (asserted in
``tests/test_hierarchy.py``).

**Byzantine edges.** A new adversary class (Egger & Bitar, arxiv
2506.09870): the first ``FLConfig.byz_edges`` edges ship corrupted count
tensors (:data:`repro.core.attacks.EDGE_ATTACK_IDS` — per-plane
complement, count saturation, stale replay). The naive additive merge
inherits the full corruption; ``edge_merge="median"`` /
``edge_merge="trimmed"`` instead merge per-coordinate over the edges'
*vote rates* ``N_i / mass`` (median, or the mean of the
``edge_trim``-trimmed order statistics) and rescale by the total mass, so
the root estimate survives any minority of bad edges.

**Device mapping (``tree_shard``).** Edges map onto
:func:`repro.launch.mesh.make_campaign_mesh` devices via ``shard_map``:
device k runs its ``E / n_dev`` edge reductions over its client-data
block and returns the *stacked per-edge tensors* (``out_specs``
sharded over the edge axis) — no ``psum``; the root merge is a single
host-side tree-reduce over the gathered ``(E, 8 * p_bytes)`` stack. This
is the psum-free contrast to ``stream_shard``, whose carries collapse
inside the collective.

Memory: resident state is O(client_chunk * d/8) per edge scan plus
O(E * d/8) for the stacked edge tensors — still independent of M. The
round driver donates the carried round state
(``jax.jit(..., donate_argnums=...)`` in ``FLSimulation`` and the tree
benchmark), so per-round count/buffer planes reuse their buffers instead
of reallocating; ``tests/test_hierarchy.py`` pins peak RSS under the same
RLIMIT_AS harness as the flat streaming round.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from ..core import BState, staleness_weights
from ..core.attacks import apply_edge_attack, edge_attack_id
from ..core.bcontrol import update_b_from_vote
from .rounds import (
    CellParams,
    RoundContext,
    RoundState,
    _scan_chunks,
    init_state,
)

__all__ = [
    "EDGE_MERGES",
    "TreeRoundState",
    "edge_slices",
    "init_tree_state",
    "tree_fl_round",
    "tree_shard_devices",
]

# Root merge rules over the stacked (E, 8 * p_bytes) edge count tensors:
# "sum" is the exact additive protocol (bit-identical to flat at zero
# staleness); "median" / "trimmed" are the robust rate-space merges.
EDGE_MERGES: tuple[str, ...] = ("sum", "median", "trimmed")


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class TreeRoundState:
    """State of a *buffered-async* tree run (``edge_buffer > 0``).

    The first four fields mirror :class:`~repro.fl.rounds.RoundState`
    (drivers read ``w_global`` etc. off either); the buffer planes hold
    the root's bounded per-edge async buffer — shipped count tensors, not
    wire rows, which is what keeps the buffer O(B * d/8) however many
    clients sit behind each edge. Unbuffered trees (``edge_buffer == 0``)
    carry a plain ``RoundState``.
    """

    w_global: jax.Array  # (d,)
    w_locals: jax.Array  # (n_clients, d) personal models
    b: BState  # dynamic-b controller state
    residuals: jax.Array  # (n_clients, d) error-feedback residuals
    edge_counts: jax.Array  # (B, 8 * p_bytes) f32 buffered edge count tensors
    edge_mass: jax.Array  # (B,) f32 buffered active-mass scalars
    edge_age: jax.Array  # (B,) int32 rounds since the slot's edge delivered
    edge_valid: jax.Array  # (B,) bool slot holds a delivery


def edge_slices(n: int, n_edges: int) -> list[tuple[int, int]]:
    """Static ``(row0, n_e)`` cohort slices, one per edge, balanced.

    The first ``n mod E`` edges take ``ceil(n/E)`` rows, the rest
    ``floor(n/E)`` — every edge is non-empty for ``E <= n`` and the sizes
    are Python ints, so each edge's scan compiles with its true static
    length (no wrap padding that could alias another edge's clients).
    """
    q, r = divmod(n, n_edges)
    sizes = [q + 1] * r + [q] * (n_edges - r)
    out, row0 = [], 0
    for n_e in sizes:
        out.append((row0, n_e))
        row0 += n_e
    return out


def init_tree_state(ctx: RoundContext, b_init=None) -> TreeRoundState:
    """Fresh buffered-tree state: empty edge buffer, sync fields as usual."""
    cfg = ctx.cfg
    base = init_state(ctx, b_init)
    n_buf = cfg.edge_buffer
    p_bytes = ctx.pipeline.compressor.wire_bytes(ctx.d)
    return TreeRoundState(
        w_global=base.w_global,
        w_locals=base.w_locals,
        b=base.b,
        residuals=base.residuals,
        edge_counts=jnp.zeros((n_buf, 8 * p_bytes), jnp.float32),
        edge_mass=jnp.zeros((n_buf,), jnp.float32),
        edge_age=jnp.zeros((n_buf,), jnp.int32),
        edge_valid=jnp.zeros((n_buf,), bool),
    )


def tree_shard_devices(ctx: RoundContext) -> int:
    """How many devices the edge reductions spread over (1 = host loop)."""
    cfg = ctx.cfg
    if not cfg.tree_shard:
        return 1
    n_dev = len(jax.devices())
    if n_dev <= 1 or cfg.tree_edges % n_dev or cfg.n_active % cfg.tree_edges:
        return 1
    return n_dev


def _edge_carries(
    ctx: RoundContext,
    params: CellParams,
    kb: jax.Array,
    k_att: jax.Array,
    k_q: jax.Array,
    w_global: jax.Array,
    b_scalar: jax.Array,
    w_locals: jax.Array | None,
    residuals: jax.Array | None,
    sel: jax.Array,
    limit,
    n_byz: int,
) -> tuple[dict, jax.Array | None, jax.Array | None]:
    """Run every edge's chunked reduction; stack the shipped tensors.

    Each edge scans its static cohort slice with ``row0`` pinned to the
    slice start, so per-row PRNG / Byzantine membership / masks key by
    global cohort position exactly as in the flat scan. Stateful planes
    (w_locals / EF residuals) thread edge-to-edge — slices are disjoint,
    so the threading order is immaterial and each client row is written
    once with its flat-scan value. Returns the stacked carry dict
    (leading axis E) and the written-back planes.
    """
    stateless = ctx.cfg.stateless_clients
    outs = []
    for row0, n_e in edge_slices(ctx.cfg.n_active, ctx.cfg.tree_edges):
        carry = _scan_chunks(
            ctx, params, kb, k_att, k_q, w_global, b_scalar,
            w_locals, residuals, sel[row0:row0 + n_e],
            ctx.client_x, ctx.client_y, 0, row0, limit, n_byz, True,
        )
        if not stateless:
            w_locals = carry.pop("w_locals")
            residuals = carry.pop("residuals")
        outs.append(carry)
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *outs)
    return stacked, w_locals, residuals


def _sharded_edges(
    ctx: RoundContext,
    params: CellParams,
    kb: jax.Array,
    k_att: jax.Array,
    k_q: jax.Array,
    w_global: jax.Array,
    b_scalar: jax.Array,
    limit,
    n_byz: int,
    n_dev: int,
) -> dict:
    """One edge reduction per device slice, psum-free.

    Device k owns edges ``[k * E/n_dev, (k+1) * E/n_dev)`` — contiguous
    equal slices (``tree_shard`` validation pins ``E | n_active`` and
    participation to 1.0), so its client-data block is exactly its edges'
    rows. ``out_specs`` shards the *edge axis*: the stacked per-edge
    tensors come back whole and the root merge happens outside the
    ``shard_map`` — no cross-device collective in the reduction at all,
    unlike ``stream_shard``'s psum.
    """
    from jax.sharding import PartitionSpec as P

    from ..launch.mesh import make_campaign_mesh

    cfg = ctx.cfg
    E = cfg.tree_edges
    n_e = cfg.n_active // E
    e_loc = E // n_dev
    mesh = make_campaign_mesh(n_dev)

    def body(cx, cy, kb_, ka_, kq_, wg, bs, lim, prm):
        k = jax.lax.axis_index("data")
        data_offset = k * (e_loc * n_e)
        outs = []
        for j in range(e_loc):
            row0 = (k * e_loc + j) * n_e
            sel_rows = row0 + jnp.arange(n_e)
            outs.append(
                _scan_chunks(
                    ctx, prm, kb_, ka_, kq_, wg, bs, None, None,
                    sel_rows, cx, cy, data_offset, row0, lim, n_byz, True,
                )
            )
        return jax.tree.map(lambda *xs: jnp.stack(xs), *outs)

    in_specs = (P("data"), P("data")) + (P(),) * 7
    kwargs = dict(mesh=mesh, in_specs=in_specs, out_specs=P("data"))
    fn = jax.shard_map(body, check_vma=False, **kwargs)
    return fn(
        ctx.client_x, ctx.client_y, kb, k_att, k_q,
        w_global, b_scalar, jnp.asarray(limit, jnp.int32), params,
    )


def _root_merge(
    cfg, counts_e: jax.Array, mass_e: jax.Array, weights: jax.Array | None
) -> tuple[jax.Array, jax.Array]:
    """Merge the (E', 8 * p_bytes) edge tensors into root (counts, mass).

    ``sum`` is the exact additive protocol — optionally
    staleness-weighted (the buffered-async path), where weight 1.0 rows
    reduce bit-identically to the unweighted sum. The robust merges work
    in *rate* space (per-coordinate vote fraction ``N_i / mass``), where
    every honest edge estimates the same population quantity regardless
    of its slice size, then rescale the consensus rate by the total mass
    so the per-scheme ``finalize`` is unchanged downstream.
    """
    if cfg.edge_merge == "sum":
        if weights is not None:
            counts_e = counts_e * weights[:, None]
            mass_e = mass_e * weights
        return jnp.sum(counts_e, axis=0), jnp.sum(mass_e)
    # Robust merges see fresh tensors only (config validation keeps them
    # out of buffered trees); an all-zero-mass edge contributes rate 0 and
    # is trimmed like any outlier — E is small, so per-coordinate order
    # statistics over the edge axis are cheap.
    rates = counts_e / jnp.maximum(mass_e, 1.0)[:, None]
    if cfg.edge_merge == "median":
        rate = jnp.median(rates, axis=0)
    else:  # "trimmed"
        t = cfg.edge_trim
        rate = jnp.mean(jnp.sort(rates, axis=0)[t:rates.shape[0] - t], axis=0)
    mass = jnp.sum(mass_e)
    return rate * mass, mass


def tree_fl_round(
    ctx: RoundContext,
    params: CellParams,
    key: jax.Array,
    state,
    batches: dict,
) -> tuple[object, dict]:
    """One hierarchical FL round: edge reductions, root merge, b-control.

    Protocol-identical to :func:`repro.fl.rounds.stream_fl_round` on the
    client side (same participation sampling, RNG schedule, attack
    semantics); the server side replaces the single cohort scan with E
    per-slice scans and a root merge over their shipped count tensors —
    see the module docstring for the exactness / async / Byzantine
    semantics. Extra metrics beyond the flat round: ``edge_mass_min``
    (the lightest edge's shipped mass — load-balance health), and for
    buffered trees the PR-3 ``buf_fill`` / ``mean_age`` pair.
    """
    cfg = ctx.cfg
    n, E, B = cfg.n_active, cfg.tree_edges, cfg.edge_buffer
    d = ctx.d
    server = ctx.pipeline.server
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

    stateless = cfg.stateless_clients
    n_dev = tree_shard_devices(ctx)
    if n_dev > 1:
        edges = _sharded_edges(
            ctx, params, kb, k_att, k_q, state.w_global, state.b.b,
            limit, n_byz, n_dev,
        )
        new_wl, new_res = state.w_locals, state.residuals
    else:
        edges, new_wl, new_res = _edge_carries(
            ctx, params, kb, k_att, k_q, state.w_global, state.b.b,
            None if stateless else state.w_locals,
            None if stateless else state.residuals,
            sel, limit, n_byz,
        )
        if stateless:
            new_wl, new_res = state.w_locals, state.residuals

    counts_f, mass_f = edges["acc"], edges["wsum"]  # (E, 8P), (E,)
    # Synchronous round heartbeat: the b-vote and metric sums ride the
    # upload wave outside the edge buffer (the PR-3 convention), honest
    # regardless of edge attacks (they forge the shipped count tensor).
    vote = jnp.sum(edges["vote"])
    loss_sum = jnp.sum(edges["loss"])
    dsum = jnp.sum(edges["dsum"], axis=0)
    wsum = jnp.sum(mass_f)

    if cfg.byz_edges:
        byz_mask = jnp.arange(E) < cfg.byz_edges
        if B:
            slot_of = jnp.arange(E) % B
            prev_c = state.edge_counts[slot_of]
            prev_m = state.edge_mass[slot_of]
            prev_v = state.edge_valid[slot_of]
        else:
            prev_c = jnp.zeros_like(counts_f)
            prev_m = jnp.zeros_like(mass_f)
            prev_v = jnp.zeros((E,), bool)
        counts_s, mass_s = apply_edge_attack(
            edge_attack_id(cfg.edge_attack),
            counts_f, mass_f, prev_c, prev_m, prev_v, byz_mask,
        )
    else:
        counts_s, mass_s = counts_f, mass_f

    if B:
        # PR-3 buffer semantics, one level up: edge e -> slot e mod B,
        # Bernoulli arrival, later edges win shared slots (unrolled
        # generations), misses age their slot.
        p_arrive = 1.0 / (1.0 + params.latency)
        u = jax.random.uniform(jax.random.fold_in(key, 7), (E,))
        delivered = u < p_arrive
        n_gen = -(-E // B)
        pad = n_gen * B - E
        c_p = jnp.pad(counts_s, ((0, pad), (0, 0)))
        m_p = jnp.pad(mass_s, (0, pad))
        del_p = jnp.pad(delivered, (0, pad))
        buf_c, buf_m = state.edge_counts, state.edge_mass
        hit = jnp.zeros((B,), bool)
        for g in range(n_gen):
            d_g = del_p[g * B:(g + 1) * B]
            buf_c = jnp.where(d_g[:, None], c_p[g * B:(g + 1) * B], buf_c)
            buf_m = jnp.where(d_g, m_p[g * B:(g + 1) * B], buf_m)
            hit = hit | d_g
        age = jnp.where(hit, 0, state.edge_age + 1)
        valid = state.edge_valid | hit
        weights = staleness_weights(age, params.staleness_decay, valid)
        counts_root, mass_root = _root_merge(cfg, buf_c, buf_m, weights)
    else:
        counts_root, mass_root = _root_merge(cfg, counts_s, mass_s, None)

    b_vec = ctx.pipeline.compressor.b_vector(d, state.b.b)
    est = server.finalize(counts_root, jnp.maximum(mass_root, 1e-12), b_vec)
    theta = jnp.where(mass_root > 0, est, 0.0)

    b_new = update_b_from_vote(state.b, vote, cfg.bctrl)
    if B:
        new_state = TreeRoundState(
            w_global=state.w_global + theta,
            w_locals=new_wl,
            b=b_new,
            residuals=new_res,
            edge_counts=buf_c,
            edge_mass=buf_m,
            edge_age=age,
            edge_valid=valid,
        )
    else:
        new_state = RoundState(
            w_global=state.w_global + theta,
            w_locals=new_wl,
            b=b_new,
            residuals=new_res,
        )
    m_eff = jnp.maximum(wsum, 1.0)
    delta_mean = dsum / m_eff
    metrics = {
        "loss": loss_sum / m_eff,
        "b": b_new.b,
        "theta_mse": jnp.mean((theta - delta_mean) ** 2),
        "edge_mass_min": jnp.min(mass_f),
    }
    if B:
        n_valid = jnp.sum(valid.astype(jnp.float32))
        metrics["buf_fill"] = n_valid / B
        metrics["mean_age"] = jnp.sum(
            age.astype(jnp.float32) * valid
        ) / jnp.maximum(n_valid, 1.0)
    return new_state, metrics
