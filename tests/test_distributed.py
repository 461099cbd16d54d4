"""Distribution-layer tests.

The production 512-device dry-run is exercised by ``repro.launch.dryrun``
(separate process — XLA device-count flag). Here we test:
  - the logical-axis sharding rules,
  - the distributed FL round on a 1-device host mesh (semantics),
  - a REAL subprocess dry-run of one reduced case on 8 fake devices.
"""

import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro import configs
from repro.distributed import spec_for, use_batch_axes
from repro.launch.fl_step import DistFLConfig, make_fl_train_step
from repro.launch.mesh import make_host_mesh
from repro.models import build_specs, sample_batch
from repro.models.spec import init_params, param_pspecs

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def test_spec_rules_divisibility():
    mesh = make_host_mesh()  # sizes 1 -> everything divisible
    with jax.set_mesh(mesh):
        assert spec_for(("batch", None), (4, 8)) == P("data", None)
        assert spec_for(("heads", None), (3, 8)) == P("model", None)


def test_spec_rules_drop_nondivisible():
    # simulate a 2-way model axis with a 3-head tensor: must replicate
    import repro.distributed as dist

    class FakeMesh:
        axis_names = ("data", "model")
        axis_sizes = (2, 2)
        empty = False

    old = dist.current_mesh
    dist.current_mesh = lambda: FakeMesh()
    try:
        assert dist.spec_for(("heads",), (3,)) == P(None)
        assert dist.spec_for(("heads",), (4,)) == P("model")
        # duplicate axis use: second logical wanting "model" is dropped
        assert dist.spec_for(("seq", "kv"), (8, 8)) == P("model", None)
    finally:
        dist.current_mesh = old


def test_fl_round_semantics_host_mesh():
    """The distributed FL round must decrease client loss and keep the
    global params finite on a 1-device mesh (pure semantics check)."""
    cfg = configs.reduced(configs.get_config("qwen2-1.5b"))
    with jax.set_mesh(make_host_mesh()):
        specs = build_specs(cfg)
        params = init_params(specs, jax.random.PRNGKey(0))
        fl = DistFLConfig(clients_per_round=2, local_steps=2, lr=0.05)
        step = jax.jit(make_fl_train_step(cfg, fl, param_pspecs(specs)))
        b = jnp.float32(0.01)
        sb = sample_batch(cfg, 2, 32, "train")
        batch = jax.tree.map(
            lambda a: jnp.broadcast_to(a[None, None, None], (2, 1, 2) + a.shape), sb
        )
        losses = []
        key = jax.random.PRNGKey(1)
        for r in range(8):
            key, kr = jax.random.split(key)
            params, b, m = step(params, b, batch, kr)
            losses.append(float(m["loss_first"]))
        assert all(np.isfinite(losses))
        assert losses[-1] < losses[0], losses  # global model is learning
        gn = sum(jnp.sum(jnp.square(x.astype(jnp.float32))) for x in jax.tree.leaves(params))
        assert bool(jnp.isfinite(gn))


def test_counts_bounded_by_clients():
    """Vote counts are in [0, M] — the ML estimate stays within [-b, b]."""
    cfg = configs.reduced(configs.get_config("qwen2-1.5b"))
    with jax.set_mesh(make_host_mesh()):
        specs = build_specs(cfg)
        params = init_params(specs, jax.random.PRNGKey(0))
        p0 = jax.tree.map(lambda a: a.astype(jnp.float32), params)
        fl = DistFLConfig(clients_per_round=4, local_steps=1, lr=0.0)  # lr=0: delta=0
        step = jax.jit(make_fl_train_step(cfg, fl, param_pspecs(specs)))
        sb = sample_batch(cfg, 2, 32, "train")
        batch = jax.tree.map(
            lambda a: jnp.broadcast_to(a[None, None, None], (4, 1, 1) + a.shape), sb
        )
        b = jnp.float32(0.01)
        new_params, _, _ = step(params, b, batch, jax.random.PRNGKey(3))
        # with delta == 0 the update is pure quantization noise <= b
        # (plus one bf16 rounding ulp of the parameter value, ~0.008 near 1.0)
        diff = jax.tree.map(
            lambda a, c: jnp.max(jnp.abs(a.astype(jnp.float32) - c.astype(jnp.float32))),
            new_params, p0,
        )
        assert max(float(x) for x in jax.tree.leaves(diff)) <= 0.01 + 0.008


@pytest.mark.slow
def test_dryrun_subprocess_8_devices(tmp_path):
    """True SPMD lower+compile in a subprocess with 8 placeholder devices
    and a reduced config — the same code path as the 512-chip dry-run."""
    script = textwrap.dedent(
        """
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, json, sys
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro import configs
        from repro.models import build_specs, abstract_params
        from repro.models.spec import param_pspecs
        from repro.launch.fl_step import DistFLConfig, make_fl_train_step
        from repro.models import input_specs, input_logical
        from repro.distributed import spec_for
        from repro.launch.mesh import make_mesh

        cfg = configs.reduced(configs.get_config("qwen3-moe-30b-a3b"))
        mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
        with jax.set_mesh(mesh):
            specs = build_specs(cfg)
            pspecs = param_pspecs(specs, fsdp_axis="data")
            params_abs = jax.tree.map(
                lambda s, sp: jax.ShapeDtypeStruct(s.shape, s.dtype,
                    sharding=NamedSharding(mesh, sp)),
                abstract_params(specs), pspecs,
                is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))
            struct = input_specs(cfg, 2, 64, "train")
            batch_abs = jax.tree.map(
                lambda a: jax.ShapeDtypeStruct((2, 2, 1) + a.shape, a.dtype,
                    sharding=NamedSharding(mesh, P(None, "pod", None, "data"))),
                struct, is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))
            step = make_fl_train_step(cfg, DistFLConfig(clients_per_round=4), pspecs)
            b_abs = jax.ShapeDtypeStruct((), jnp.float32, sharding=NamedSharding(mesh, P()))
            k_abs = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=NamedSharding(mesh, P()))
            compiled = jax.jit(step).lower(params_abs, b_abs, batch_abs, k_abs).compile()
            txt = compiled.as_text()
            has_coll = any(op in txt for op in ("all-reduce", "all-gather", "reduce-scatter"))
            print(json.dumps({"ok": True, "has_collectives": has_coll}))
        """
    )
    env = dict(os.environ, PYTHONPATH=SRC)
    env.pop("XLA_FLAGS", None)
    res = subprocess.run(
        [sys.executable, "-c", script], capture_output=True, text=True, env=env,
        timeout=540,
    )
    assert res.returncode == 0, res.stderr[-3000:]
    payload = json.loads(res.stdout.strip().splitlines()[-1])
    assert payload["ok"] and payload["has_collectives"]


def test_with_depth_keeps_published_widths():
    """--layers cuts whole layers only; every width stays published."""
    full = configs.get_config("qwen2-1.5b")
    cut = configs.with_depth(full, 2)
    assert cut.n_layers == 2 and cut.name == "qwen2-1.5b-2L"
    for field in ("d_model", "d_ff", "n_heads", "n_kv_heads", "vocab"):
        assert getattr(cut, field) == getattr(full, field)
    assert configs.with_depth(full, full.n_layers) is full
    with pytest.raises(ValueError):
        configs.with_depth(full, full.n_layers + 1)


def test_pod_mesh_round_matches_one_device_subprocess():
    """train.py --pod-mesh (one silo per device, 4 CPU devices) gives the
    one-device round's params exactly: same cohort, key and int32 counts."""
    script = textwrap.dedent(
        """
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import json
        import jax, numpy as np
        from repro.launch import train

        args = ["--arch", "qwen2-1.5b", "--reduced", "--rounds", "1",
                "--clients", "4", "--local-steps", "2", "--per-batch", "2",
                "--seq", "32"]
        pod = train.main(args + ["--pod-mesh"])
        one = train.main(args)
        n_diff = sum(
            int(np.sum(np.asarray(a, np.float32) != np.asarray(b, np.float32)))
            for a, b in zip(jax.tree.leaves(pod["params"]),
                            jax.tree.leaves(one["params"]))
        )
        print(json.dumps({"pods": [pod["pods"], one["pods"]], "n_diff": n_diff}))
        """
    )
    env = dict(os.environ, PYTHONPATH=SRC)
    env.pop("XLA_FLAGS", None)
    res = subprocess.run(
        [sys.executable, "-c", script], capture_output=True, text=True, env=env,
        timeout=540,
    )
    assert res.returncode == 0, res.stderr[-3000:]
    payload = json.loads(res.stdout.strip().splitlines()[-1])
    assert payload == {"pods": [4, 1], "n_diff": 0}
