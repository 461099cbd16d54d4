"""FL simulation runtime — paper Algorithm 1 end-to-end.

One round (jit-compiled, clients vmapped):
  1. every client trains its *personal* model from its previous local
     parameters, prox-regularized toward the current global model (Eq. 4);
  2. model differences ``delta^m = w_local^m - w_global`` are formed;
  3. Byzantine clients replace their delta per the configured attack
     (delta-level attacks from :data:`repro.core.ATTACKS`; the ``bit_flip``
     wire adversary instead inverts post-quantization codes inside the
     pipeline);
  4. the configured :class:`repro.core.AggregatorPipeline` (resolved once
     from the registry — no aggregator branching here) compresses the
     updates onto the packed one-bit wire and estimates theta_hat —
     PRoBit+ quantizes with the dynamic/fixed/oracle ``b`` (+ DP margin)
     and ML-estimates (Eq. 13); baselines: FedAvg / Fed-GM / signSGD-MV /
     RSA ride the same registry;
  5. the global model steps by ``theta_hat``; the dynamic-b controller
     majority-votes the clients' one-bit loss signals (§VI-B).

The round itself lives in :mod:`repro.fl.rounds` as a pure
``RoundState -> RoundState`` function; :class:`FLSimulation` is the thin
stateful driver (host loop + periodic eval) kept for the original
experiment API. Whole scenario *grids* — many (aggregator, attack,
byz_frac, M, seed) cells at once — run through the vmapped campaign
engine in :mod:`repro.sim` instead.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Callable

import jax
import numpy as np

from ..core import (
    ACCOUNTANTS,
    BControlConfig,
    DPConfig,
    PrivacyLedger,
    available_aggregators,
    build_pipeline,
    is_timing_attack,
    parse_attack,
)
from . import rounds as _rounds

_B_MODES = ("dynamic", "fixed", "oracle")


@dataclasses.dataclass(frozen=True)
class FLConfig:
    n_clients: int = 20
    byz_frac: float = 0.0
    attack: str = "none"
    aggregator: str = "probit_plus"  # | fedavg | fed_gm | signsgd_mv | rsa
    rounds: int = 30
    local_epochs: int = 5
    batch_size: int = 10
    lr: float = 0.01
    momentum: float = 0.5
    lam: float = 0.2
    dp_epsilon: float = 0.0  # 0 disables DP
    l1_sensitivity: float = 2e-4  # paper: 0.02 * lr
    b_mode: str = "dynamic"  # dynamic | fixed | oracle
    b_init: float = 0.01
    # BEYOND-PAPER: error feedback — each client carries the quantization
    # residual e_m into the next round (delta_eff = delta + e_m;
    # e_m' = delta_eff - b*c_m). Classical EF for 1-bit compressors;
    # the paper does not use it. DP note: EF reuses the residual across
    # rounds, so the per-round (eps,0) guarantee composes differently —
    # we therefore disable EF when dp_epsilon > 0.
    error_feedback: bool = False
    # BEYOND-PAPER: top-k sparse PRoBit+ (the paper's stated future work).
    # Fraction of coordinates each client uploads (1.0 = dense Eq. 5/13).
    # Refused under DP: the data-dependent index set breaks (eps,0)-DP
    # (see core/sparse.py).
    topk_frac: float = 1.0
    # Partial participation: fraction of clients sampled per round
    # (cross-device FL standard; M in Eq. 13 becomes the sampled count).
    # The mechanism keeps Theorem 3's per-round eps; what *tightens* under
    # participation < 1 is the reported budget, via the ledger's
    # amplification-by-subsampling accountant (see dp_accountant).
    participation: float = 1.0
    # DP accountant for the run's PrivacyLedger: "subsampled" (default —
    # per-round eps amplified by the sampling rate q = m/M before basic
    # composition; q = 1 is bit-identical to "basic"), "basic"
    # (conservative sum), "advanced" (DRV strong composition at
    # delta_slack = 1e-5), or "renyi" (exact randomized-response RDP
    # composed in the Rényi domain, converted at delta_slack — dominates
    # both basic and advanced on every trajectory). Host-side bookkeeping
    # only — never traced.
    dp_accountant: str = "subsampled"
    # BEYOND-PAPER: buffered-asynchronous rounds (the ROADMAP's
    # async/straggler item). 0 = the paper's synchronous protocol; B > 0
    # keeps a B-slot server buffer of the last-arrived packed uploads and
    # estimates from it with age-weighted vote counts (see
    # repro.fl.rounds.async_fl_round for the exact assumptions relaxed).
    async_buffer: int = 0
    # Mean upload latency in rounds; per-round arrival probability is
    # 1/(1 + async_latency). Traced (vmappable campaign axis).
    async_latency: float = 0.0
    # Staleness discount exponent: a buffered upload of age a is weighted
    # (1 + a)^(-staleness_decay) in the vote counts. 0 = uniform weights.
    staleness_decay: float = 0.0
    agg_step: float = 0.01  # server step for signSGD-MV / RSA
    gm_iters: int = 16
    use_kernels: bool = False
    # Streaming client axis (ROADMAP item). 0 = dense round (the whole
    # (M, d) update matrix and (M, d_pad/8) wire materialize at once);
    # C > 0 scans the cohort in chunks of C clients, accumulating packed
    # vote counts — resident memory drops from O(M * d/8) to O(C * d/8).
    # Per-client PRNG is counter-derived, so for count-streaming schemes
    # the chunked round is bit-identical to the dense one in eager mode
    # (<= 1e-6 under jit, the PR-3 reassociation precedent).
    client_chunk: int = 0
    # With client_chunk > 0: drop per-client persistent state (w_locals /
    # residuals collapse to a single broadcast row). Clients train from
    # w_global each round — the cross-device regime where M is far larger
    # than any per-client state the server could hold. Required for
    # stream_shard and for M beyond host memory.
    stateless_clients: bool = False
    # Packer d-chunk override (0 = quantizer.PACK_CHUNK). The streaming
    # benchmark shrinks it so the per-chunk scratch stays cache-sized.
    pack_chunk: int = 0
    # Shard the client axis of each chunk scan across the campaign mesh
    # (launch/mesh.make_campaign_mesh) via the weighted-count reduction.
    stream_shard: bool = False
    # Wire width k in {1, 2, 4} bits/parameter (probit_plus only). 1 is
    # the paper's one-bit wire, bit-exact with pre-k-bit history; k > 1
    # stochastically quantizes onto the uniform 2**k-level grid and, under
    # DP, mixes in L-level randomized response (core.privacy.rr_gamma) so
    # the per-round (eps, 0) guarantee — and all four accountants —
    # compose unchanged.
    wire_bits: int = 1
    # BEYOND-PAPER: HeteroSAg-style per-client bit-widths — one entry per
    # cohort row, each in {1, 2, 4}. Overrides wire_bits; the server
    # aggregates per equal-bits group and MLE-merges. Restricted to the
    # dense synchronous probit_plus wire (no kernels / top-k / streaming /
    # async).
    client_bits: tuple | None = None
    # Hierarchical count-tree aggregation (fl/hierarchy.py, ROADMAP's
    # serving-scale item). 0 = flat aggregation; E > 0 splits the cohort
    # into E contiguous edge slices, each running the chunked count scan
    # (requires client_chunk > 0 and a count-streaming aggregator) and
    # shipping one count tensor + active-mass scalar to the root. Zero
    # staleness is bit-exact with the flat streaming round.
    tree_edges: int = 0
    # Bounded per-edge async buffer at the root (PR-3 semantics one level
    # up): 0 = synchronous tree; B > 0 buffers edge deliveries (edge e ->
    # slot e mod B) with Bernoulli(1/(1+async_latency)) arrivals and
    # (1+age)^(-staleness_decay) root merge weights.
    edge_buffer: int = 0
    # Map edge reductions onto make_campaign_mesh devices (one device per
    # E/n_dev edge group, psum-free root merge over the gathered edge
    # tensors). Mirrors stream_shard's requirements: stateless clients,
    # full participation, and E must divide n_active.
    tree_shard: bool = False
    # Byzantine *edge aggregators* (Egger & Bitar, arxiv 2506.09870): the
    # first byz_edges edges ship count tensors corrupted per edge_attack
    # (core.attacks.EDGE_ATTACK_IDS: edge_sign_flip / edge_inflate /
    # edge_replay).
    byz_edges: int = 0
    edge_attack: str = "none"
    # Root merge rule over the stacked edge count tensors: "sum" (exact
    # additive protocol), "median" / "trimmed" (robust per-coordinate
    # rate-space merges surviving a minority of Byzantine edges;
    # edge_trim edges are cut from each end of the order statistics).
    edge_merge: str = "sum"
    edge_trim: int = 0
    seed: int = 0

    def __post_init__(self):
        if self.aggregator not in available_aggregators():
            raise ValueError(
                f"unknown aggregator {self.aggregator!r}; "
                f"available: {available_aggregators()}"
            )
        parse_attack(self.attack)  # raises ValueError on unknown names
        if self.dp_accountant not in ACCOUNTANTS:
            raise ValueError(
                f"unknown dp_accountant {self.dp_accountant!r}; "
                f"available: {ACCOUNTANTS}"
            )
        if not 0.0 < self.participation <= 1.0:
            raise ValueError(
                f"participation must be in (0, 1], got {self.participation}"
            )
        if self.b_mode not in _B_MODES:
            raise ValueError(
                f"unknown b_mode {self.b_mode!r}; available: {_B_MODES}"
            )
        if self.topk_frac < 1.0 and self.dp_epsilon > 0:
            raise ValueError(
                "topk_frac < 1 releases a data-dependent index set and "
                "breaks the (eps,0)-DP guarantee; use dense PRoBit+ with DP."
            )
        if self.async_buffer < 0:
            raise ValueError(f"async_buffer must be >= 0, got {self.async_buffer}")
        if self.async_latency < 0:
            raise ValueError(f"async_latency must be >= 0, got {self.async_latency}")
        if self.staleness_decay < 0:
            raise ValueError(
                f"staleness_decay must be >= 0 (weights must be monotone "
                f"non-increasing in age), got {self.staleness_decay}"
            )
        if not self.async_buffer:
            if (self.async_latency > 0 or self.staleness_decay > 0) and (
                not self.edge_buffer
            ):
                raise ValueError(
                    "async_latency/staleness_decay require buffered-async "
                    "rounds (set async_buffer > 0 for client rounds or "
                    "edge_buffer > 0 for a buffered-async tree root)"
                )
            if is_timing_attack(self.attack):
                raise ValueError(
                    f"timing attack {self.attack!r} needs asynchronous rounds "
                    "(set async_buffer > 0); synchronous rounds have no "
                    "arrival schedule to attack"
                )
        else:
            if self.participation < 1.0:
                raise ValueError(
                    "async rounds require participation == 1.0: buffer "
                    "slots, staleness ages, and the straggler gate are keyed "
                    "to client identity, which a per-round resampled cohort "
                    "breaks. Model partial availability with async_latency "
                    "instead (a client arriving with probability "
                    "1/(1+latency) subsumes sampling)."
                )
            if self.topk_frac < 1.0:
                raise ValueError(
                    "async rounds buffer dense packed wires; topk_frac < 1 "
                    "(SparseWire) cannot be staleness-buffered"
                )
            if self.async_buffer > self.n_active:
                raise ValueError(
                    f"async_buffer={self.async_buffer} exceeds the cohort "
                    f"({self.n_active} clients); slots beyond one per client "
                    "would never be written"
                )
        if self.client_chunk < 0:
            raise ValueError(f"client_chunk must be >= 0, got {self.client_chunk}")
        if self.pack_chunk < 0 or self.pack_chunk % 8:
            raise ValueError(
                f"pack_chunk must be a non-negative multiple of 8, "
                f"got {self.pack_chunk}"
            )
        if self.client_chunk:
            if self.async_buffer:
                raise ValueError(
                    "client_chunk streams the synchronous round; the "
                    "buffered-async server holds a persistent wire buffer "
                    "and cannot stream (set async_buffer=0)"
                )
            if self.topk_frac < 1.0:
                raise ValueError(
                    "client_chunk requires the dense packed wire; "
                    "topk_frac < 1 (SparseWire) has no count accumulator"
                )
            if self.b_mode == "oracle":
                raise ValueError(
                    "b_mode='oracle' maxes |delta| over the full cohort and "
                    "cannot stream; use 'dynamic' or 'fixed' with client_chunk"
                )
            if self.byz_frac > 0:
                from ..core.attacks import STREAM_ATTACKS

                payload, _ = parse_attack(self.attack)
                if payload not in STREAM_ATTACKS:
                    raise ValueError(
                        f"attack {self.attack!r} colludes across the cohort "
                        f"and cannot run under a client-chunk scan; "
                        f"streamable attacks: {tuple(sorted(STREAM_ATTACKS))}"
                    )
        if self.stateless_clients:
            if not self.client_chunk:
                raise ValueError("stateless_clients requires client_chunk > 0")
            if self.error_feedback:
                raise ValueError(
                    "error feedback carries a per-client residual across "
                    "rounds and contradicts stateless_clients"
                )
        from ..core.quantizer import WIRE_BITS

        if self.wire_bits not in WIRE_BITS:
            raise ValueError(
                f"wire_bits must be one of {WIRE_BITS}, got {self.wire_bits}"
            )
        if self.wire_bits != 1:
            if self.aggregator != "probit_plus":
                raise ValueError(
                    f"wire_bits={self.wire_bits} is only supported by the "
                    f"probit_plus wire, not {self.aggregator!r} (the k-bit "
                    "level protocol is PRoBit+'s count/MLE machinery)"
                )
            if self.topk_frac < 1.0:
                raise ValueError(
                    "wire_bits > 1 is not supported on the top-k wire "
                    "(SparseWire packs one bit per surviving coordinate); "
                    "set topk_frac=1.0"
                )
        if self.client_bits is not None:
            object.__setattr__(
                self, "client_bits", tuple(int(k) for k in self.client_bits)
            )
            for k in self.client_bits:
                if k not in WIRE_BITS:
                    raise ValueError(
                        f"client_bits entries must be in {WIRE_BITS}, got {k}"
                    )
            if self.aggregator != "probit_plus":
                raise ValueError(
                    "per-client bit-widths (client_bits) are only supported "
                    f"by probit_plus, not {self.aggregator!r}"
                )
            if len(self.client_bits) != self.n_active:
                raise ValueError(
                    f"client_bits needs one entry per cohort row: got "
                    f"{len(self.client_bits)} for a {self.n_active}-client "
                    "cohort"
                )
            if self.use_kernels:
                raise ValueError(
                    "client_bits is not supported on the kernel wire yet; "
                    "unset use_kernels (homogeneous wire_bits works with "
                    "kernels)"
                )
            if self.topk_frac < 1.0:
                raise ValueError(
                    "client_bits is not supported on the top-k wire; "
                    "set topk_frac=1.0"
                )
            if self.client_chunk or self.stream_shard:
                raise ValueError(
                    "client_bits emits a per-group HeteroWire and cannot "
                    "stream through the flat count accumulator; unset "
                    "client_chunk/stream_shard"
                )
            if self.async_buffer:
                raise ValueError(
                    "client_bits rows have heterogeneous wire widths and "
                    "cannot share the fixed-width async buffer; set "
                    "async_buffer=0"
                )
            if self.byz_frac > 0:
                from ..core import is_wire_attack

                if is_wire_attack(self.attack):
                    raise ValueError(
                        f"wire attack {self.attack!r} is not supported on "
                        "the heterogeneous wire yet; use a delta-level "
                        "attack or homogeneous wire_bits"
                    )
        if self.stream_shard:
            if not self.client_chunk:
                raise ValueError("stream_shard requires client_chunk > 0")
            if not self.stateless_clients:
                raise ValueError(
                    "stream_shard requires stateless_clients: scattering "
                    "per-client state back from device-local chunk rows "
                    "is not supported"
                )
            if self.participation < 1.0:
                raise ValueError(
                    "stream_shard requires participation == 1.0 (the static "
                    "client-data shard layout cannot follow a resampled "
                    "cohort)"
                )
            if self.aggregator == "fed_gm":
                raise ValueError(
                    "fed_gm buffers all rows (stream_kind='buffer') and "
                    "cannot reduce across shards; pick a count- or "
                    "sum-streaming aggregator"
                )
        if self.tree_edges < 0:
            raise ValueError(f"tree_edges must be >= 0, got {self.tree_edges}")
        if self.edge_buffer < 0:
            raise ValueError(f"edge_buffer must be >= 0, got {self.edge_buffer}")
        if not self.tree_edges:
            tree_only = {
                "edge_buffer": (self.edge_buffer, 0),
                "tree_shard": (self.tree_shard, False),
                "byz_edges": (self.byz_edges, 0),
                "edge_attack": (self.edge_attack, "none"),
                "edge_merge": (self.edge_merge, "sum"),
                "edge_trim": (self.edge_trim, 0),
            }
            for name, (val, default) in tree_only.items():
                if val != default:
                    raise ValueError(
                        f"{name}={val!r} requires a hierarchical tree round "
                        "(set tree_edges > 0)"
                    )
        else:
            from ..core.attacks import EDGE_ATTACK_IDS

            _COUNT_STREAM_AGGREGATORS = ("probit_plus", "signsgd_mv", "rsa")
            if self.aggregator not in _COUNT_STREAM_AGGREGATORS:
                raise ValueError(
                    f"tree_edges requires a count-streaming aggregator "
                    f"(edges ship additive count tensors); "
                    f"{self.aggregator!r} is not in "
                    f"{_COUNT_STREAM_AGGREGATORS}"
                )
            if not self.client_chunk:
                raise ValueError(
                    "tree_edges requires client_chunk > 0: each edge runs "
                    "the chunked count-accumulation scan over its slice"
                )
            if self.tree_edges > self.n_active:
                raise ValueError(
                    f"tree_edges={self.tree_edges} exceeds the cohort "
                    f"({self.n_active} clients); an edge needs at least "
                    "one client"
                )
            if self.async_buffer:
                raise ValueError(
                    "tree_edges and async_buffer are exclusive: the tree "
                    "buffers *edge count tensors* at the root "
                    "(edge_buffer), not client wire rows"
                )
            if self.stream_shard:
                raise ValueError(
                    "tree_edges shards by edge (tree_shard), not by the "
                    "flat client axis; unset stream_shard"
                )
            if self.edge_buffer > self.tree_edges:
                raise ValueError(
                    f"edge_buffer={self.edge_buffer} exceeds tree_edges="
                    f"{self.tree_edges}; slots beyond one per edge would "
                    "never be written"
                )
            if self.edge_attack not in EDGE_ATTACK_IDS:
                raise ValueError(
                    f"unknown edge_attack {self.edge_attack!r}; "
                    f"available: {EDGE_ATTACK_IDS}"
                )
            if not 0 <= self.byz_edges <= self.tree_edges:
                raise ValueError(
                    f"byz_edges must be in [0, tree_edges], got "
                    f"{self.byz_edges} with tree_edges={self.tree_edges}"
                )
            if self.byz_edges and self.edge_attack == "none":
                raise ValueError(
                    "byz_edges > 0 needs an edge_attack from "
                    f"{EDGE_ATTACK_IDS[1:]}"
                )
            if self.edge_attack == "edge_replay" and not self.edge_buffer:
                raise ValueError(
                    "edge_replay re-ships the root's buffered slot content "
                    "and needs a buffered tree (set edge_buffer > 0)"
                )
            from .hierarchy import EDGE_MERGES

            if self.edge_merge not in EDGE_MERGES:
                raise ValueError(
                    f"unknown edge_merge {self.edge_merge!r}; "
                    f"available: {EDGE_MERGES}"
                )
            if self.edge_merge != "sum" and self.edge_buffer:
                raise ValueError(
                    "robust edge merges (median/trimmed) operate on fresh "
                    "edge tensors; staleness-weighted robust merging is "
                    "not supported (set edge_buffer=0)"
                )
            if self.edge_trim and self.edge_merge != "trimmed":
                raise ValueError(
                    "edge_trim only applies to edge_merge='trimmed'"
                )
            if self.edge_merge == "trimmed" and (
                2 * self.edge_trim >= self.tree_edges
            ):
                raise ValueError(
                    f"edge_trim={self.edge_trim} trims away all "
                    f"{self.tree_edges} edges (need 2*edge_trim < tree_edges)"
                )
            if self.tree_shard:
                if not self.stateless_clients:
                    raise ValueError(
                        "tree_shard requires stateless_clients: scattering "
                        "per-client state back from device-local edge "
                        "slices is not supported"
                    )
                if self.participation < 1.0:
                    raise ValueError(
                        "tree_shard requires participation == 1.0 (the "
                        "static client-data shard layout cannot follow a "
                        "resampled cohort)"
                    )
                if self.n_active % self.tree_edges:
                    raise ValueError(
                        f"tree_shard needs equal edge slices: tree_edges="
                        f"{self.tree_edges} does not divide the "
                        f"{self.n_active}-client cohort"
                    )

    @property
    def n_active(self) -> int:
        return max(int(self.n_clients * self.participation), 1)

    @property
    def n_byz(self) -> int:
        return int(self.n_clients * self.byz_frac)

    @property
    def dp(self) -> DPConfig:
        return DPConfig(self.dp_epsilon, self.l1_sensitivity)

    @property
    def sampling_rate(self) -> float:
        """Effective per-round client sampling rate ``q = m_sampled / M``.

        Derived from the *actual* cohort size (``n_active``, which floors
        and clamps), not the raw ``participation`` fraction — the
        amplification bound needs the realized inclusion probability.
        Full participation is exactly 1.0.
        """
        if self.participation >= 1.0:
            return 1.0
        return self.n_active / self.n_clients

    def ledger(self) -> PrivacyLedger:
        """A fresh :class:`~repro.core.PrivacyLedger` for one run of this
        config: per-round eps from Theorem 3's ``dp_epsilon``, sampling
        rate from the realized cohort, accountant per ``dp_accountant``."""
        return PrivacyLedger(
            eps_per_round=self.dp_epsilon,
            q=self.sampling_rate,
            accountant=self.dp_accountant,
        )

    @property
    def bctrl(self) -> BControlConfig:
        return BControlConfig(self.b_mode, self.b_init)

    def pipeline(self):
        """The shared :class:`repro.core.AggregatorPipeline` for this run."""
        from ..core.quantizer import PACK_CHUNK

        return build_pipeline(
            self.aggregator,
            dp=self.dp,
            b_mode=self.b_mode,
            error_feedback=self.error_feedback,
            topk_frac=self.topk_frac,
            agg_step=self.agg_step,
            gm_iters=self.gm_iters,
            use_kernels=self.use_kernels,
            chunk=self.pack_chunk or PACK_CHUNK,
            wire_bits=self.wire_bits,
            client_bits=self.client_bits,
        )


class FLSimulation:
    """Simulation-mode FL (CPU): the paper-faithful experiment harness.

    A thin stateful wrapper over the pure round core in
    :mod:`repro.fl.rounds` — it owns a :class:`~repro.fl.rounds.RoundState`
    and drives one jitted round per loop iteration, evaluating on the host
    every ``eval_every`` rounds. The per-round math, RNG schedule, and
    therefore the trajectories are identical to the campaign engine's
    scanned execution of the same config.
    """

    def __init__(
        self,
        cfg: FLConfig,
        init_params,
        loss_fn: Callable,  # loss_fn(params_pytree, {"x","y"}) -> scalar
        acc_fn: Callable,
        client_x: np.ndarray,  # (M, per_client, ...)
        client_y: np.ndarray,  # (M, per_client)
        test: dict,
    ):
        self.cfg = cfg
        self.ctx = _rounds.make_context(
            cfg, init_params, loss_fn, acc_fn, client_x, client_y, test
        )
        self.state = _rounds.init_run_state(self.ctx)
        self._params = _rounds.cell_params(cfg)
        # The carried round state is donated: each round's count/buffer
        # planes reuse the previous round's buffers instead of
        # reallocating (the driver below never re-reads the old state).
        # Callers must snapshot arrays (np.asarray) before run(), not hold
        # live references across it.
        round_impl = functools.partial(
            _rounds.round_fn(self.ctx), self.ctx, self._params
        )

        # named, so that the program and its ops' name paths read
        # ``jit(fl_round)`` in a profile rather than ``jit(<unknown>)``
        def fl_round(key, state, batches):
            return round_impl(key, state, batches)

        self._round = jax.jit(fl_round, donate_argnums=(1,))
        self.history: list[dict] = []
        # One DP event is recorded per executed round; eps_spent in the
        # history is the cumulative budget under cfg.dp_accountant.
        self.ledger = cfg.ledger()

    # State views (the arrays live in self.state; these keep the original
    # attribute API used by tests and examples).
    @property
    def w_global(self):
        return self.state.w_global

    @property
    def w_locals(self):
        return self.state.w_locals

    @property
    def b_state(self):
        return self.state.b

    @property
    def residuals(self):
        return self.state.residuals

    @property
    def unravel(self):
        return self.ctx.unravel

    @property
    def loss_fn(self):
        return self.ctx.loss_fn

    @property
    def acc_fn(self):
        return self.ctx.acc_fn

    @property
    def client_x(self):
        return self.ctx.client_x

    @property
    def client_y(self):
        return self.ctx.client_y

    @property
    def test(self):
        return self.ctx.test

    @property
    def pipeline(self):
        return self.ctx.pipeline

    @property
    def d(self) -> int:
        return self.ctx.d

    # -- data --------------------------------------------------------------

    def _round_batches(self, key):
        return _rounds.round_batches(self.ctx, key)

    # -- driver --------------------------------------------------------------

    @property
    def eps_trajectory(self):
        """Cumulative DP budget after each executed round (ledger view)."""
        return self.ledger.trajectory()

    def evaluate(self) -> float:
        params = self.unravel(self.w_global)
        return float(self.acc_fn(params, self.test))

    def run(self, rounds: int | None = None, eval_every: int = 5, verbose: bool = False):
        cfg = self.cfg
        rounds = rounds or cfg.rounds
        key = jax.random.PRNGKey(cfg.seed)
        for t in range(rounds):
            key, kb, kr = jax.random.split(key, 3)
            batches = self._round_batches(kb)
            self.state, metrics = self._round(kr, self.state, batches)
            self.ledger.record_round()
            if (t + 1) % eval_every == 0 or t == rounds - 1:
                acc = self.evaluate()
                rec = {
                    "round": t + 1,
                    "acc": acc,
                    "loss": float(metrics["loss"]),
                    "b": float(self.state.b.b),
                    "eps_spent": self.ledger.eps_spent,
                }
                self.history.append(rec)
                if verbose:
                    print(
                        f"[{cfg.aggregator}|{cfg.attack}|byz={cfg.byz_frac:.0%}] "
                        f"round {t+1}: acc={acc:.4f} loss={rec['loss']:.4f} "
                        f"b={rec['b']:.5f} eps={rec['eps_spent']:.4g}"
                    )
        return self.history
