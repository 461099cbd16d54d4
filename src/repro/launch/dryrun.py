import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST precede any jax import: jax locks the device count on first init.
# The dry-run (and ONLY the dry-run) builds the 512-chip production mesh
# out of host placeholder devices; smoke tests / benches see 1 CPU device.

"""Multi-pod dry-run: lower + compile every (architecture x input shape)
combination on the production mesh and extract roofline terms.

Usage:
  python -m repro.launch.dryrun --arch qwen2-1.5b --shape train_4k [--multi-pod]
  python -m repro.launch.dryrun --all [--multi-pod] --out reports/
"""

import argparse
import json
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from .. import configs
from ..distributed import spec_for, use_batch_axes, use_rules
from ..models import (
    SHAPES,
    abstract_params,
    build_specs,
    cache_logical,
    init_cache,
    input_logical,
    input_specs,
    prefill,
    serve_step,
)
from ..models.config import ModelConfig, ShapeConfig
from ..models.spec import param_pspecs
from .analysis import roofline_terms
from .flopcount import count_fn
from .fl_step import DistFLConfig, make_fl_train_step
from .mesh import make_production_mesh

SKIPS: dict[tuple[str, str], str] = {
    ("hubert-xlarge", "decode_32k"): "encoder-only: no autoregressive decode step",
    ("hubert-xlarge", "long_500k"): "encoder-only: no autoregressive decode step",
}

# long_500k window variant for full-attention archs (DESIGN.md §5)
LONG_WINDOW = 8192


def cache_plan(cfg: ModelConfig, shape: ShapeConfig) -> tuple[int, int]:
    """(cache_len, ring_window) for decode shapes."""
    if "attn" not in cfg.pattern:
        return 8, 0  # no attention cache; minimal placeholder length
    if cfg.sliding_window and shape.seq_len > cfg.sliding_window:
        return cfg.sliding_window, cfg.sliding_window
    if shape.name == "long_500k" and cfg.family in ("dense", "moe", "vlm"):
        return LONG_WINDOW, LONG_WINDOW
    return shape.seq_len, 0


def _sds(shape, dtype, spec, mesh):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=NamedSharding(mesh, spec))


def _abstract_tree_with_sharding(abs_tree, logical_tree, mesh):
    def one(a, logical):
        spec = spec_for(tuple(logical), a.shape)
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=NamedSharding(mesh, spec))

    return jax.tree.map(
        one, abs_tree, logical_tree,
        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct),
    )


def build_lowerable(cfg: ModelConfig, shape: ShapeConfig, mesh, fl_clients: int = 16, fl_agg: str = "probit_plus", rand_bits: int = 32, fsdp: bool = True):
    """Returns (fn, abstract_args) ready for jit(...).lower(*args)."""
    n_pods = dict(zip(mesh.axis_names, mesh.axis_sizes)).get("pod", 1)
    specs = build_specs(cfg)
    pspecs = param_pspecs(specs, fsdp_axis="data" if fsdp else None)
    params_abs = jax.tree.map(
        lambda s, sp: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=NamedSharding(mesh, sp)
        ),
        abstract_params(specs),
        pspecs,
        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct),
    )

    if shape.kind == "train":
        m_seq = fl_clients // n_pods
        pb = shape.global_batch // fl_clients
        assert pb >= 1, (shape.name, fl_clients)
        struct = input_specs(cfg, pb, shape.seq_len, "train")
        logical = input_logical(cfg, pb, shape.seq_len, "train")

        def expand(a, log):
            sh = (m_seq, n_pods, 1) + a.shape  # (clients_seq, pods, local_steps, ...)
            spec = spec_for(("clients",) + tuple(log), (n_pods,) + a.shape)
            entries = list(spec) + [None] * (1 + len(a.shape) - len(spec))
            full = P(None, entries[0], None, *entries[1:])
            return jax.ShapeDtypeStruct(sh, a.dtype, sharding=NamedSharding(mesh, full))

        batch_abs = jax.tree.map(
            expand, struct, logical,
            is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct),
        )
        b_abs = _sds((), jnp.float32, P(), mesh)
        key_abs = _sds((2,), jnp.uint32, P(), mesh)
        step = make_fl_train_step(
            cfg,
            DistFLConfig(clients_per_round=fl_clients, aggregator=fl_agg, rand_bits=rand_bits),
            pspecs,
        )
        return step, (params_abs, b_abs, batch_abs, key_abs)

    if shape.kind == "prefill":
        struct = input_specs(cfg, shape.global_batch, shape.seq_len, "prefill")
        logical = input_logical(cfg, shape.global_batch, shape.seq_len, "prefill")
        batch_abs = _abstract_tree_with_sharding(struct, logical, mesh)
        fn = lambda params, batch: prefill(params, batch, cfg)
        return fn, (params_abs, batch_abs)

    # decode
    cache_len, window = cache_plan(cfg, shape)
    struct = input_specs(cfg, shape.global_batch, shape.seq_len, "decode")
    logical = input_logical(cfg, shape.global_batch, shape.seq_len, "decode")
    batch_abs = _abstract_tree_with_sharding(struct, logical, mesh)
    cache_abs_raw = jax.eval_shape(lambda: init_cache(cfg, shape.global_batch, cache_len))
    clog = cache_logical(cfg)
    cache_abs = _abstract_tree_with_sharding(cache_abs_raw, clog, mesh)
    pos_abs = _sds((), jnp.int32, P(), mesh)

    def fn(params, cache, batch, pos):
        return serve_step(params, cache, batch, pos, cfg, window)

    return fn, (params_abs, cache_abs, batch_abs, pos_abs)


def run_case(
    arch: str,
    shape_name: str,
    multi_pod: bool,
    fl_clients: int = 16,
    indexed: bool = False,
    tag: str = "",
    fl_agg: str = "probit_plus",
    rand_bits: int = 32,
    serve_2d: bool = False,
    layer_remat: bool = False,
    remat: str = "full",
    ssm_dtype: str = "float32",
    pure_dp: bool = False,
) -> dict:
    from ..models.model import indexed_params, inner_remat, remat_policy
    from ..models.ssm import ssm_state_dtype

    shape = SHAPES[shape_name]
    report: dict = {
        "arch": arch,
        "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "variant": tag or ("indexed" if indexed else "baseline"),
    }
    if (arch, shape_name) in SKIPS:
        report["status"] = "skipped"
        report["reason"] = SKIPS[(arch, shape_name)]
        return report
    cfg = configs.get_config(arch)
    t0 = time.time()
    mesh = make_production_mesh(multi_pod=multi_pod)
    try:
        import contextlib
        if pure_dp:
            # small-model layout: no tensor parallelism at all — weights
            # replicated, clients/batch over data (+pod); the only
            # collective left is the per-client gradient all-reduce.
            rules_ctx = use_rules(
                ff=(), heads=(), kv=(), vocab=(), seq=(), experts=(),
            )
            batch_ax = ("pod", "data") if (multi_pod and shape.kind != "train") else ("data",)
            fsdp = False
        elif serve_2d and shape.kind == "decode":
            # 2D weight-stationary serving: weights sharded over BOTH axes
            # (no per-token FSDP re-gather); decode activations are tiny, so
            # resharding them between the batch-parallel attention (cache
            # stays batch@data, seq@model) and the weight-sharded matmuls
            # is cheap.
            rules_ctx = use_rules(
                ff=("model", "data"),
                vocab=("model", "data"),
                experts=("model",),
            )
            batch_ax: tuple = ("data",)
            fsdp = False
        else:
            rules_ctx = contextlib.nullcontext()
            batch_ax = ("pod", "data") if (multi_pod and shape.kind != "train") else ("data",)
            fsdp = True
        with jax.set_mesh(mesh), indexed_params(indexed), rules_ctx, \
                inner_remat(layer_remat), remat_policy(remat), ssm_state_dtype(ssm_dtype):
            with use_batch_axes(*batch_ax):
                fn, args = build_lowerable(cfg, shape, mesh, fl_clients, fl_agg, rand_bits, fsdp)
                lowered = jax.jit(fn).lower(*args)
                t_lower = time.time() - t0
                compiled = lowered.compile()
                t_compile = time.time() - t0 - t_lower
                cost = compiled.cost_analysis()
                try:
                    mem = compiled.memory_analysis()
                except Exception:
                    mem = None
                jaxpr_counts = count_fn(fn, *args)
                n_dev = mesh.size
                terms = roofline_terms(
                    cost, mem, compiled.as_text(), jaxpr_counts, n_dev
                )
        report.update(terms)
        report["status"] = "ok"
        report["t_lower_s"] = round(t_lower, 1)
        report["t_compile_s"] = round(t_compile, 1)
        report["n_params"] = cfg.n_params()
        report["n_active_params"] = cfg.n_active_params()
        if mem is not None:
            print(f"[{arch} x {shape_name} x {report['mesh']}] memory_analysis: "
                  f"args={mem.argument_size_in_bytes/2**30:.2f}GiB "
                  f"temp={mem.temp_size_in_bytes/2**30:.2f}GiB per device")
        print(f"[{arch} x {shape_name} x {report['mesh']}] cost_analysis: "
              f"flops/dev={terms['flops_per_device']:.3e} "
              f"bytes/dev={terms['bytes_per_device']:.3e} "
              f"coll={terms['collective_link_bytes']:.3e}B "
              f"bottleneck={terms['bottleneck']}")
    except Exception as e:  # a failure here is a bug in our sharding config
        report["status"] = "error"
        report["error"] = f"{type(e).__name__}: {e}"[:2000]
        report["traceback"] = traceback.format_exc()[-4000:]
    return report


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--fl-clients", type=int, default=16)
    ap.add_argument("--fl-agg", default="probit_plus", choices=["probit_plus", "fedavg_fp32"])
    ap.add_argument("--serve-2d", action="store_true", help="2D weight-stationary decode layout (perf variant)")
    ap.add_argument("--layer-remat", action="store_true", help="nested per-layer remat inside the pattern unit (perf variant)")
    ap.add_argument("--remat", default="full", choices=["full", "dots"], help="remat policy for the unit scan")
    ap.add_argument("--ssm-dtype", default="float32", choices=["float32", "bfloat16"], help="SSM chunk-state dtype (perf variant)")
    ap.add_argument("--pure-dp", action="store_true", help="no tensor parallelism: replicated weights, data/client parallelism only (small-model perf variant)")
    ap.add_argument("--rand-bits", type=int, default=32, choices=[16, 32])
    ap.add_argument("--indexed-params", action="store_true",
                    help="per-iteration param gather inside the layer scan (perf variant)")
    ap.add_argument("--tag", default="", help="variant tag for the report filename")
    ap.add_argument("--out", default=None, help="directory for JSON reports")
    args = ap.parse_args()

    cases = (
        [(a, s) for a in configs.ARCH_IDS for s in SHAPES]
        if args.all
        else [(args.arch, args.shape)]
    )
    results = []
    for arch, shape in cases:
        rep = run_case(
            arch, shape, args.multi_pod, args.fl_clients,
            indexed=args.indexed_params, tag=args.tag,
            fl_agg=args.fl_agg, rand_bits=args.rand_bits, serve_2d=args.serve_2d,
            layer_remat=args.layer_remat, remat=args.remat, ssm_dtype=args.ssm_dtype,
            pure_dp=args.pure_dp,
        )
        results.append(rep)
        if args.out:
            os.makedirs(args.out, exist_ok=True)
            suffix = f"__{args.tag}" if args.tag else ""
            tag = f"{arch}__{shape}__{'mp' if args.multi_pod else 'sp'}{suffix}.json"
            with open(os.path.join(args.out, tag), "w") as f:
                json.dump(rep, f, indent=1, default=str)
        status = rep["status"]
        print(f"== {arch} x {shape}: {status} "
              f"{'(' + rep.get('reason', rep.get('error', ''))[:120] + ')' if status != 'ok' else ''}")
    n_err = sum(r["status"] == "error" for r in results)
    print(f"\n{len(results)} cases: {n_err} errors")
    raise SystemExit(1 if n_err else 0)


if __name__ == "__main__":
    main()
