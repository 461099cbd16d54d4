"""Compile rehearsal of the Pallas wire for a described TPU v5e chip.

Each test lowers and compiles one kernel, in the form a round calls it,
for a v5e chip that is described (``topologies.get_topology_desc``) and
not attached: the TPU compiler refuses here what it would refuse on the
chip (block shapes, layouts, memory spaces). Nothing runs, so these tests
say nothing about results or times; ``chip_smoke.py`` checks results on
the chip.

The topology is described inside a module fixture and never while a
module is imported, so every pytest-xdist worker collects the same tests
and only the worker running this file loads the TPU library.
"""

import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

# One qwen2-1.5b MLP matrix (d_model x d_ff) as a flat wire.
D_MLP = 1536 * 8960
# The Pallas engine is named explicitly: on this CPU host resolve_engine()
# would pick "ref", which compiles no kernel.
PALLAS = "pallas"


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip must not land in a persistent cache
    # (one a benchmark import may have turned on): no chip can read it.
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


def _shape(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("want_residual", [False, True], ids=["pack", "ef"])
def test_compress_batch_compiles(one_chip, want_residual):
    """Client-vmapped compress (stoch_quant_pack_2d / stoch_quant_ef_2d),
    as ``ClientCompressor.compress`` calls it on the kernel wire."""
    m = 2
    fn = functools.partial(
        ops.stoch_quant_compress_batch, want_residual=want_residual,
        engine=PALLAS,
    )
    compiled = _compile(
        fn,
        _shape(one_chip, (2,), jnp.uint32),
        _shape(one_chip, (m, D_MLP)),
        _shape(one_chip, (D_MLP,)),
    )
    out = compiled.memory_analysis()
    assert out is None or out.output_size_in_bytes >= m * D_MLP // 8


@pytest.mark.parametrize("m", [256, 37], ids=["m256", "ragged"])
def test_bit_aggregate_compiles(one_chip, m):
    """Server count + Eq.-13 at one whole M_BLOCK tile and at a ragged M."""
    fn = functools.partial(ops.bit_aggregate, n=D_MLP, engine=PALLAS)
    _compile(
        fn,
        _shape(one_chip, (m, ops.padded_len(D_MLP) // 8), jnp.uint8),
        _shape(one_chip, (D_MLP,)),
    )


def test_prox_sgd_vmapped_compiles(one_chip):
    """The fused local step, vmapped over clients with the global model
    and the (eta, lam, mu) scalars shared, as ``fl/rounds.py`` calls it."""
    m = 2

    def step(w, w0, g, mom, eta, lam, mu):
        return jax.vmap(
            lambda wi, gi, mi: ops.prox_sgd(
                wi, w0, gi, mi, eta, lam, mu, engine=PALLAS
            )
        )(w, g, mom)

    batch = _shape(one_chip, (m, D_MLP))
    scalar = _shape(one_chip, ())
    _compile(
        step, batch, _shape(one_chip, (D_MLP,)), batch, batch,
        scalar, scalar, scalar,
    )


def test_quant_pack_u_vmapped_compiles(one_chip):
    """The top-k wire: explicit-uniforms binarize + pack per client."""
    m, k = 2, D_MLP // 100
    fn = jax.vmap(functools.partial(ops.quant_pack_u, engine=PALLAS))
    _compile(fn, *[_shape(one_chip, (m, k))] * 3)
