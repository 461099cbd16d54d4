"""Plain reference of a cross-device PRoBit+ round of the paper's CNN.

Written from the paper (arXiv:2507.03973, Algorithm 1 and §VI-A) alone
and importing nothing of the program:

* the CNN: 3x3 SAME convolution to ``width`` channels, ReLU, 2x2 max-pool,
  3x3 convolution to ``2 width``, ReLU, 2x2 max-pool, dense ``hidden``
  with ReLU, dense classifier; mean softmax cross-entropy;
* the cohort: ``cohort`` of ``population`` clients drawn without
  replacement; each trains its personal model from where it last left
  it, prox-regularised toward the global model with momentum SGD
  (Eq. 4: g + lam (w - w_g), m <- mu m + g, w <- w - lr m) over its
  round batches; it votes +1 when its loss on the last batch after
  training is below its loss on the first batch before;
* the first ``byz_frac`` of the cohort are Byzantine and upload
  N(0, 10^2) noise in place of their delta;
* Eq. 5: each coordinate votes +1 with probability
  (b' + clip(delta, -b', b')) / (2 b'), with the DP range
  b' = b + (1 + 1/eps) * Delta_1 (Theorem 3);
* Eq. 13: theta = (2 N - M) / M * b' from the +1 counts N, and the
  dynamic b: times 1.01 when the loss votes sum above 0, else 0.98.

Every product runs in float32 at ``HIGHEST`` precision (``precision=
"f32"``); ``precision="bf16"`` is the control, the configuration's
float32 at default precision computed one step lower: weights,
activations and the local optimiser state in bfloat16.

Randomness follows the run's key schedule: per round the caller's
``key, kb, kr = split(key, 3)``; cohort ``choice(fold_in(kr, 99))``;
client m's batch indices ``randint(fold_in(kb, m), (steps, batch))``;
attack and quantizer keys ``split(fold_in(kr, 1))``; the uniform of
coordinate ``8192 j + t`` of the client at cohort position g is element
t of ``uniform(fold_in(fold_in(k_q, g), j), (8192,))``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.flatten_util import ravel_pytree

HIGHEST = jax.lax.Precision.HIGHEST
UNIFORM_CHUNK = 8192


def init_weights(key: jax.Array, cfg: dict) -> dict:
    """The paper's CNN initialisation: N(0, 0.1^2) convolution kernels,
    N(0, 1/fan_in) dense weights, zero biases; f32."""
    cin, w, h, c = cfg["in_channels"], cfg["width"], cfg["hidden"], cfg["classes"]
    flat = (cfg["image"] // 4) ** 2 * 2 * w
    ks = jax.random.split(key, 4)
    return {
        "c1": 0.1 * jax.random.normal(ks[0], (3, 3, cin, w), jnp.float32),
        "c2": 0.1 * jax.random.normal(ks[1], (3, 3, w, 2 * w), jnp.float32),
        "w1": flat ** -0.5 * jax.random.normal(ks[2], (flat, h), jnp.float32),
        "b1": jnp.zeros((h,), jnp.float32),
        "w2": h ** -0.5 * jax.random.normal(ks[3], (h, c), jnp.float32),
        "b2": jnp.zeros((c,), jnp.float32),
    }


def leaf_slices(cfg: dict) -> list:
    """(name, start, stop) of each weight in the flat vector."""
    shapes = jax.eval_shape(lambda: init_weights(jax.random.PRNGKey(0), cfg))
    out, start = [], 0
    for name in sorted(shapes):
        n = shapes[name].size
        out.append((name, start, start + n))
        start += n
    return out


def _dt(precision: str):
    return jnp.bfloat16 if precision == "bf16" else jnp.float32


def logits(p: dict, x: jax.Array, precision: str = "f32") -> jax.Array:
    dt = _dt(precision)
    conv = functools.partial(
        jax.lax.conv_general_dilated, window_strides=(1, 1), padding="SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HIGHEST,
    )

    def pool(h):
        return jax.lax.reduce_window(
            h, -jnp.inf, jax.lax.max,
            (1, 2, 2, 1), (1, 2, 2, 1), "VALID",
        )

    h = pool(jax.nn.relu(conv(x.astype(dt), p["c1"].astype(dt))))
    h = pool(jax.nn.relu(conv(h, p["c2"].astype(dt))))
    h = h.reshape(h.shape[0], -1)
    h = jax.nn.relu(
        jnp.dot(h, p["w1"].astype(dt), precision=HIGHEST) + p["b1"].astype(dt)
    )
    return (jnp.dot(h, p["w2"].astype(dt), precision=HIGHEST)
            + p["b2"].astype(dt)).astype(jnp.float32)


def loss(p: dict, x: jax.Array, y: jax.Array, precision: str = "f32"):
    lg = logits(p, x, precision)
    lse = jax.nn.logsumexp(lg, axis=-1)
    ll = jnp.take_along_axis(lg, y[:, None], axis=-1)[:, 0]
    return jnp.mean(lse - ll)


def local_train(w_flat, w_global, xs, ys, unravel, hp, precision):
    """One client: momentum prox-SGD over (steps, batch, ...) data from its
    personal model. Returns (w, loss before on the first batch, loss after
    on the last batch)."""
    dt = _dt(precision)

    def data_loss(w, x, y):
        return loss(unravel(w), x, y, precision)

    def step(carry, xy):
        w, m = carry
        g = jax.grad(data_loss)(w, *xy).astype(dt)
        g = g + hp["lam"] * (w - w_global.astype(dt))
        m = hp["momentum"] * m + g
        return (w - hp["lr"] * m, m), None

    w = w_flat.astype(dt)
    before = data_loss(w, xs[0], ys[0])
    (w, _), _ = jax.lax.scan(step, (w, jnp.zeros_like(w)), (xs, ys))
    after = data_loss(w, xs[-1], ys[-1])
    return w.astype(jnp.float32), before, after


def uniforms(client_key: jax.Array, n: int) -> jax.Array:
    chunks = -(-n // UNIFORM_CHUNK)
    u = jax.vmap(
        lambda j: jax.random.uniform(
            jax.random.fold_in(client_key, j), (UNIFORM_CHUNK,), jnp.float32
        )
    )(jnp.arange(chunks))
    return u.reshape(-1)[:n]


@functools.partial(
    jax.jit, static_argnames=("cfg_items", "hp_items", "precision", "fault")
)
def fl_round(state, kb, kr, client_x, client_y, cfg_items, hp_items, precision,
             fault=None):
    """One round from ``state`` = (w_global (d,), w_locals (n, d), b), with
    the round's batch key ``kb`` and round key ``kr``. Returns (state',
    mean loss after local training, counts (d,) int32, the wire's b').

    ``fault`` plants a fault, for the reference to stand in for a broken
    program: ``half_batch`` trains on half of every batch (the mean over
    the rest), ``altered_token`` alters one label of every client's first
    batch, ``altered_answer`` negates the global update of the largest
    weight, ``state_unchanged`` returns the state it was given."""
    cfg, hp = dict(cfg_items), dict(hp_items)
    w_global, w_locals, b = state
    n, per_client = client_x.shape[:2]
    cohort = hp["cohort"]
    steps = max(hp["local_epochs"] * per_client // hp["batch_size"], 1)
    _, unravel = ravel_pytree(init_weights(jax.random.PRNGKey(0), cfg))

    sel = jax.random.choice(jax.random.fold_in(kr, 99), n, (cohort,), replace=False)
    idx = jax.vmap(
        lambda m: jax.random.randint(
            jax.random.fold_in(kb, m), (steps, hp["batch_size"]), 0, per_client
        )
    )(sel)
    xs = jax.vmap(lambda m, i: client_x[m][i])(sel, idx)
    ys = jax.vmap(lambda m, i: client_y[m][i])(sel, idx)
    if fault == "half_batch":
        xs, ys = xs[:, :, : xs.shape[2] // 2], ys[:, :, : ys.shape[2] // 2]
    elif fault == "altered_token":
        ys = ys.at[:, 0, 0].set((ys[:, 0, 0] + 1) % cfg["classes"])
    w_new, before, after = jax.vmap(
        lambda w, x, y: local_train(w, w_global, x, y, unravel, hp, precision)
    )(w_locals[sel], xs, ys)
    deltas = w_new - w_global[None]
    k_att, k_q = jax.random.split(jax.random.fold_in(kr, 1))
    n_byz = int(cohort * hp["byz_frac"])
    noise = 10.0 * jax.random.normal(k_att, (n_byz, deltas.shape[1]), jnp.float32)
    deltas = deltas.at[:n_byz].set(noise)

    b_wire = b + (1.0 + 1.0 / hp["dp_epsilon"]) * hp["l1_sensitivity"]
    d = deltas.shape[1]

    def client_bits(g, delta):
        u = uniforms(jax.random.fold_in(k_q, g), d)
        p = 0.5 + 0.5 * jnp.clip(delta, -b_wire, b_wire) / b_wire
        return (u < p).astype(jnp.int32)

    counts = jnp.zeros((d,), jnp.int32)
    counts = jax.lax.fori_loop(
        0, cohort, lambda g, c: c + client_bits(g, deltas[g]), counts
    )
    theta = (2.0 * counts.astype(jnp.float32) - cohort) / cohort * b_wire
    vote = jnp.sum(jnp.where(after < before, 1.0, -1.0))
    b_new = b * jnp.where(vote > 0, hp["b_up"], hp["b_down"])
    if fault == "altered_answer":
        _, a, z = max(leaf_slices(cfg), key=lambda t: t[2] - t[1])
        theta = theta.at[a:z].multiply(-1.0)
    new_state = (w_global + theta, w_locals.at[sel].set(w_new), b_new)
    if fault == "state_unchanged":
        new_state = state
    return new_state, jnp.mean(after), counts, b_wire
