"""Plain reference of a cross-silo PRoBit+ round of a Qwen2-style decoder.

Written from the published descriptions alone and importing nothing of
the program: the decoder of the Qwen2 report (arXiv:2407.10671: RMSNorm,
grouped-query attention with q/k/v biases, rotary embeddings, SwiGLU
MLP; the LM head is the embedding's transpose where the configuration
ties them, as Qwen2-1.5B does), the paper's
prox-regularised local step (Eq. 4 without momentum, as the cross-silo
step runs it), its one-bit stochastic compressor (Eq. 5) and the Eq.-13
maximum-likelihood estimate from the vote counts, and the dynamic-b
controller (up 1.01 / down 0.98 on the majority of the clients' loss
votes).

Weights are stored in bfloat16, as the configuration states; every
product is computed in float32 at ``HIGHEST`` precision (``precision=
"f32"``). ``precision="fp8"`` is the control: every matrix product takes
its operands rounded to float8 e4m3 with one scale per tensor, the next
precision below the configuration's bfloat16.

Quantizer randomness follows the published key schedule of the wire: the
uniform of coordinate ``8192 j + t`` of leaf ``l`` for the client at
cohort position ``g`` is element ``t`` of
``uniform(fold_in(fold_in(fold_in(round_key, l), g), j), (8192,))``,
leaves in ``jax.tree_util`` order.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
UNIFORM_CHUNK = 8192
FP8 = jnp.float8_e4m3fn
FP8_MAX = 448.0


# ---------------------------------------------------------------------------
# Weights, in the program's parameter layout
# ---------------------------------------------------------------------------

def weight_shapes(cfg: dict) -> dict:
    """The parameter tree (shapes) of a decoder of ``cfg``'s sizes, in the
    layout the program consumes: per-layer weights stacked on a leading
    layer axis, attention projections split per head."""
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    kv = cfg["num_key_value_heads"]
    hd = d // h
    ff = cfg["intermediate_size"]
    n = cfg["num_hidden_layers"]
    v = cfg["vocab_size"]
    block = {
        "norm1": {"w": (n, d)},
        "mixer": {
            "wq": (n, d, h, hd), "wk": (n, d, kv, hd), "wv": (n, d, kv, hd),
            "wo": (n, h, hd, d),
            "bq": (n, h, hd), "bk": (n, kv, hd), "bv": (n, kv, hd),
        },
        "norm2": {"w": (n, d)},
        "ffn": {"w1": (n, d, ff), "w3": (n, d, ff), "w2": (n, ff, d)},
    }
    embed = {"embed": (v, d)}
    if not cfg["tie_word_embeddings"]:
        embed["head"] = (d, v)
    return {
        "embed": embed,
        "blocks": [block],
        "final_norm": {"w": (d,)},
    }


def _is_shape(x) -> bool:
    return isinstance(x, tuple) and all(isinstance(i, int) for i in x)


def init_weights(key: jax.Array, cfg: dict) -> dict:
    """bf16 weights: N(0, initializer_range^2) for matrices, ones for the
    norms, zeros for the biases (the published initialisation)."""
    shapes = weight_shapes(cfg)
    paths = jax.tree_util.tree_leaves_with_path(shapes, is_leaf=_is_shape)
    std = cfg["initializer_range"]
    out = []
    for i, (path, shape) in enumerate(paths):
        name = jax.tree_util.keystr(path)
        if "norm" in name:
            out.append(jnp.ones(shape, jnp.bfloat16))
        elif "'b" in name and "mixer" in name:
            out.append(jnp.zeros(shape, jnp.bfloat16))
        else:
            k = jax.random.fold_in(key, i)
            out.append(
                (std * jax.random.normal(k, shape, jnp.float32)).astype(jnp.bfloat16)
            )
    return jax.tree_util.tree_unflatten(
        jax.tree_util.tree_structure(shapes, is_leaf=_is_shape), out
    )


# ---------------------------------------------------------------------------
# Forward pass and loss
# ---------------------------------------------------------------------------

def _fp8(x: jax.Array) -> jax.Array:
    """Round to float8 e4m3 with one scale for the tensor, back in f32."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / FP8_MAX
    return (x / scale).astype(FP8).astype(jnp.float32) * scale


def _mm(a, b, precision: str, eq: str):
    a = a.astype(jnp.float32)
    b = b.astype(jnp.float32)
    if precision == "fp8":
        a, b = _fp8(a), _fp8(b)
    return jnp.einsum(eq, a, b, precision=HIGHEST)


def _rmsnorm(x, w, eps):
    ms = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(ms + eps) * w.astype(jnp.float32)


def _rope(x, theta):
    """Rotary embedding, rotate-half form; x (B, S, H, hd)."""
    s, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs[None]
    cos = jnp.cos(ang)[None, :, None, :]
    sin = jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _layer(cfg, precision, x, p):
    eps = cfg["rms_norm_eps"]
    h = _rmsnorm(x, p["norm1"]["w"], eps)
    m = p["mixer"]
    q = _mm(h, m["wq"], precision, "bsd,dhk->bshk") + m["bq"].astype(jnp.float32)
    k = _mm(h, m["wk"], precision, "bsd,dhk->bshk") + m["bk"].astype(jnp.float32)
    v = _mm(h, m["wv"], precision, "bsd,dhk->bshk") + m["bv"].astype(jnp.float32)
    q = _rope(q, cfg["rope_theta"])
    k = _rope(k, cfg["rope_theta"])
    heads, kvh = q.shape[2], k.shape[2]
    k = jnp.repeat(k, heads // kvh, axis=2)
    v = jnp.repeat(v, heads // kvh, axis=2)
    scores = _mm(q, k, precision, "bqhk,bshk->bhqs") / math.sqrt(q.shape[-1])
    s = x.shape[1]
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    att = jax.nn.softmax(scores, axis=-1)
    o = _mm(att, v, precision, "bhqs,bshk->bqhk")
    x = x + _mm(o, m["wo"], precision, "bshk,hkd->bsd")
    h = _rmsnorm(x, p["norm2"]["w"], eps)
    f = p["ffn"]
    g = jax.nn.silu(_mm(h, f["w1"], precision, "bsd,df->bsf"))
    u = _mm(h, f["w3"], precision, "bsd,df->bsf")
    return x + _mm(g * u, f["w2"], precision, "bsf,fd->bsd")


def loss(weights, tokens, cfg: dict, precision: str = "f32"):
    """Mean next-token cross-entropy of (B, S) ``tokens``: position s
    predicts token s + 1, the last position predicts nothing."""
    x = weights["embed"]["embed"][tokens].astype(jnp.float32)
    body = jax.checkpoint(functools.partial(_layer, cfg, precision))

    def scan_body(x, p):
        return body(x, p), None

    x, _ = jax.lax.scan(scan_body, x, weights["blocks"][0])
    x = _rmsnorm(x, weights["final_norm"]["w"], cfg["rms_norm_eps"])
    head = weights["embed"].get("head")
    if head is None:
        head = weights["embed"]["embed"].T
    logits = _mm(x, head, precision, "bsd,dv->bsv")
    logits = logits[:, :-1]
    target = tokens[:, 1:]
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, target[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - ll)


# ---------------------------------------------------------------------------
# One client's local training, one round of the federation
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("cfg_items", "precision"))
def local_train(weights, batches, lr, lam, cfg_items, precision):
    """Prox-regularised SGD from the global weights over (steps, B, S)
    token batches: w <- w - lr (g + lam (w - w_global)), stored in bf16.
    Returns the local weights and the loss before each step."""
    cfg = dict(cfg_items)
    w0 = weights

    def step(w, toks):
        lval, g = jax.value_and_grad(loss)(w, toks, cfg, precision)
        w = jax.tree.map(
            lambda a, gg, a0: (
                a.astype(jnp.float32)
                - lr * (gg.astype(jnp.float32) + lam * (a - a0).astype(jnp.float32))
            ).astype(a.dtype),
            w, g, w0,
        )
        return w, lval

    return jax.lax.scan(step, w0, batches)


def uniforms(client_key: jax.Array, n: int) -> jax.Array:
    chunks = -(-n // UNIFORM_CHUNK)
    u = jax.vmap(
        lambda j: jax.random.uniform(
            jax.random.fold_in(client_key, j), (UNIFORM_CHUNK,), jnp.float32
        )
    )(jnp.arange(chunks))
    return u.reshape(-1)[:n]


@jax.jit
def _vote_leaf(counts, local, glob, key, g, b):
    """Add the client's Eq.-5 +1 votes of one leaf to its counts."""
    delta = (local - glob).astype(jnp.float32).reshape(-1)
    u = uniforms(jax.random.fold_in(key, g), delta.shape[0])
    safe = jnp.where(b > 0, b, 1.0)
    p = jnp.where(b > 0, 0.5 + 0.5 * jnp.clip(delta, -b, b) / safe, 0.5)
    return counts + (u < p).astype(counts.dtype)


@functools.partial(jax.jit, static_argnames=("m",))
def _estimate_leaf(w, counts, m, b):
    """Eq. 13: w + (2 N - M) / M * b, stored in the leaf's dtype."""
    theta = (2.0 * counts.astype(jnp.float32) - m) / m * b
    return (w.astype(jnp.float32) + theta.reshape(w.shape)).astype(w.dtype)


def fl_round(weights, b, batches, round_key, hp: dict, cfg: dict,
             precision: str = "f32"):
    """One synchronous round. ``batches`` (M, steps, B, S) tokens, client
    g at cohort position g. Returns (weights, b, loss_first, loss_last):
    the losses are the clients' mean loss before their first and before
    their last local step."""
    m = batches.shape[0]
    leaves, treedef = jax.tree_util.tree_flatten(weights)
    counts = [jnp.zeros((x.size,), jnp.int8) for x in leaves]
    first, last, vote = [], [], 0
    cfg_items = tuple(sorted(
        (k, v) for k, v in cfg.items() if isinstance(v, (int, float, str))
    ))
    for g in range(m):
        local, losses = local_train(
            weights, batches[g], hp["lr"], hp["lam"], cfg_items, precision
        )
        first.append(float(losses[0]))
        last.append(float(losses[-1]))
        vote += 1 if losses[-1] < losses[0] else -1
        for i, (lw, gw) in enumerate(zip(jax.tree_util.tree_leaves(local), leaves)):
            counts[i] = _vote_leaf(
                counts[i], lw, gw, jax.random.fold_in(round_key, i), g, b
            )
        del local
    new = [_estimate_leaf(w, c, m, b) for w, c in zip(leaves, counts)]
    b_new = b * (hp["b_up"] if vote > 0 else hp["b_down"])
    return (
        jax.tree_util.tree_unflatten(treedef, new),
        jnp.float32(b_new),
        sum(first) / m,
        sum(last) / m,
    )
