"""Traffic generators: client data made on the device from the seed.

Copies of the program's generators (``repro.data.synthetic``
``make_lm_streams`` / ``make_image_classification`` and
``repro.data.partition.partition_label_skew``), changed to draw with
``jax.random`` on the device in one jitted call each, so that set-up
moves no data from the host and later changes to the program cannot move
the yardstick's data. What each mix draws is set by its file in this
directory; every seed draws the same sizes.

* LM streams: each client draws tokens from its own unigram law, half a
  shared Dirichlet(10) law and half a client-specific Dirichlet(alpha)
  law over the first ``vocab_subset`` ids, so the clients' data are
  skewed as in label-skew partitioning.
* Images: ten smooth class prototypes (4 x 4 spectra upsampled to the
  image) plus Gaussian noise; each client holds samples of
  ``classes_per_client`` classes only (the paper's label skew). Samples
  are drawn fresh rather than picked from a finite pool of 10,000, which
  is the same law as an unbounded pool.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def seed_key(seed: int) -> jax.Array:
    """A PRNG key for any non-negative seed, also above 2**32."""
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)


# ---------------------------------------------------------------------------
# LM streams
# ---------------------------------------------------------------------------

def lm_client_laws(key: jax.Array, n_clients: int, vocab_subset: int,
                   alpha: float) -> jax.Array:
    """(n_clients, vocab_subset) log-probabilities of each client's law."""
    kb, ks = jax.random.split(key)
    base = jax.random.dirichlet(kb, jnp.full((vocab_subset,), 10.0))
    skew = jax.random.dirichlet(
        ks, jnp.full((vocab_subset,), alpha), shape=(n_clients,)
    )
    p = 0.5 * base[None] + 0.5 * skew
    return jnp.log(jnp.maximum(p, 1e-30))


def lm_tokens(key: jax.Array, laws: jax.Array, rows: int, seq: int,
              vocab: int) -> jax.Array:
    """(n_clients, rows, seq) int32 tokens, client c drawn from laws[c]."""
    keys = jax.random.split(key, laws.shape[0])
    toks = jax.vmap(
        lambda k, lp: jax.random.categorical(k, lp, shape=(rows, seq))
    )(keys, laws)
    return (toks % vocab).astype(jnp.int32)


# ---------------------------------------------------------------------------
# Images with label skew
# ---------------------------------------------------------------------------

def image_prototypes(key: jax.Array, n_classes: int, img: int,
                     channels: int) -> jax.Array:
    """(n_classes, img, img, channels) smooth class prototypes."""
    freq = jax.random.normal(key, (n_classes, 4, 4, channels), jnp.float32)
    rep = img // 4
    protos = jnp.repeat(jnp.repeat(freq, rep, axis=1), rep, axis=2)
    return protos[:, :img, :img]


def image_draw(key: jax.Array, protos: jax.Array, labels: jax.Array,
               noise: float) -> jax.Array:
    x = protos[labels]
    return x + noise * jax.random.normal(key, x.shape, jnp.float32)


def label_skew_clients(key: jax.Array, protos: jax.Array, n_clients: int,
                       per_client: int, classes_per_client: int,
                       noise: float) -> tuple[jax.Array, jax.Array]:
    """(n_clients, per_client, img, img, ch) images and int32 labels; each
    client's labels are uniform over its own ``classes_per_client``
    distinct classes. Drawn client by client from per-client keys, in
    blocks, so that the draw needs little memory beside its result."""
    n_classes = protos.shape[0]

    def client(k):
        kc, kl, kx = jax.random.split(k, 3)
        classes = jax.random.permutation(kc, n_classes)[:classes_per_client]
        labels = classes[
            jax.random.randint(kl, (per_client,), 0, classes_per_client)
        ].astype(jnp.int32)
        return image_draw(kx, protos, labels, noise), labels

    return jax.lax.map(client, jax.random.split(key, n_clients), batch_size=64)


def test_set(key: jax.Array, protos: jax.Array, n: int,
             noise: float) -> dict:
    kl, kx = jax.random.split(key)
    y = jax.random.randint(kl, (n,), 0, protos.shape[0]).astype(jnp.int32)
    return {"x": image_draw(kx, protos, y, noise), "y": y}
