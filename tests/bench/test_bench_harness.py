"""The harness: discovery by name, the no-TPU exit, and the shape-derived
counts against the program's own loop-aware counter."""

from __future__ import annotations

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from bench import flops, harness
from conftest import BENCH, ROOT, TINY_DECODER, build_tiny_checkout, run_tiny

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())


def test_every_cell_resolves_from_its_files():
    for w in SPEC["workloads"]:
        cell = harness.find_cell(ROOT, w["name"])
        assert cell.chips == w["chips"]
        assert cell.config["name"] == w["config"]
        assert hasattr(cell.driver, "make")
        names = {m["name"] for m in cell.end_to_end + cell.per_layer}
        for name in names:
            assert callable(harness.metric_reader(name))
        assert "setup_s" in names


def test_every_config_file_matches_benchmark_json():
    for c in SPEC["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"]
        assert cfg["source"] == c["source"]
        assert cfg["reduced"] == c["reduced"]


def test_a_new_cell_config_mix_and_metric_need_only_new_files(tmp_path):
    """A throwaway cell with a configuration, a traffic mix and a per-layer
    metric of its own runs from files added next to the existing ones and
    an entry in BENCHMARK.json; no existing file changes."""
    bench = build_tiny_checkout(tmp_path, cells={})
    spec = json.loads((tmp_path / "BENCHMARK.json").read_text())
    base = json.loads((BENCH / "configs" / "paper-cnn.json").read_text())
    (bench / "configs" / "cnn-new.json").write_text(
        json.dumps(dict(base, name="cnn-new", width=2, hidden=8)))
    mix = json.loads((BENCH / "traffic" / "xdev-femnist-s10.json").read_text())
    (bench / "traffic" / "mix-new.json").write_text(json.dumps(
        dict(mix, population=16, cohort=8, samples_per_client=10, test_samples=20)))
    limits = json.loads((BENCH / "workloads" / "xdev-cnn-wire.json").read_text())["limits"]
    (bench / "workloads" / "cell-new.json").write_text(json.dumps(
        {"config": "cnn-new", "traffic": "mix-new", "chips": 1, "limits": limits}))
    (bench / "metrics" / "rounds_seen.py").write_text(
        "def read(record):\n    return float(len(record.window.work))\n")
    spec["workloads"].append({"name": "cell-new", "config": "cnn-new",
                              "traffic": "mix-new", "chips": 1, "why": "test"})
    spec["end_to_end"].append({"name": "rounds_seen", "unit": "rounds",
                               "better": "higher", "bound": 0.05,
                               "source": "host_clock", "workloads": ["cell-new"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    rc, res = run_tiny(tmp_path, bench, "cell-new", seconds=0.2)
    assert rc == 0 and res["correct"], res
    assert res["metrics"]["rounds_seen"]["value"] == res["attempted"] >= 1
    assert set(res["metrics"]) == {"rounds_seen", "setup_s"}
    assert list(res)[-1] == "checks"


def _bare_env():
    env = {k: v for k, v in os.environ.items() if not k.startswith("BENCH")}
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("PYTHONPATH", None)
    return env


def test_run_exits_nonzero_without_a_tpu():
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", SPEC["workloads"][0]["name"],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=_bare_env(), capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert not [ln for ln in proc.stdout.splitlines() if ln.startswith("{")]


def test_run_exits_nonzero_in_a_checkout_of_the_benchmark_alone(tmp_path):
    import shutil

    shutil.copytree(BENCH, tmp_path / "bench")
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", SPEC["workloads"][0]["name"],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=_bare_env(), capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode != 0
    assert not proc.stdout.strip()


@pytest.mark.parametrize("seq", [16, 32])
def test_decoder_flops_bound_the_programs_count(seq):
    """The yardstick's forward FLOPs per token against the program's
    loop-aware jaxpr count of its forward pass: the count adds the
    elementwise work and the masked half of the attention scores, so it
    lies a little above the yardstick and never below it."""
    from repro.launch.flopcount import count_fn
    from repro.models import build_specs, train_loss
    from repro.models.spec import abstract_params

    from bench.drivers.xsilo import program_model_config

    cfg = json.loads((BENCH / "configs" / "qwen2-1.5b-20L.json").read_text())
    cfg.update(TINY_DECODER)
    mc = program_model_config(cfg)
    params = abstract_params(build_specs(mc))
    toks = jax.ShapeDtypeStruct((2, seq), jnp.int32)
    counted = count_fn(
        lambda p, t: train_loss(p, {"tokens": t, "labels": t}, mc), params, toks
    )["flops_total"]
    ours = flops.decoder_forward_flops_per_token(cfg, seq) * 2 * seq
    d = cfg["hidden_size"]
    masked = 2 * 2 * d * seq * (seq - 1) / 2 * cfg["num_hidden_layers"] * 2
    assert ours <= counted
    assert counted <= 1.15 * (ours + masked)


def test_cnn_flops_and_params_match_the_program():
    from repro.launch.flopcount import count_fn
    from repro.models.vision import cnn_logits, init_cnn

    cfg = json.loads((BENCH / "configs" / "paper-cnn.json").read_text())
    params = init_cnn(jax.random.PRNGKey(0))
    assert flops.cnn_param_count(cfg) == cfg["parameters"] == sum(
        x.size for x in jax.tree.leaves(params))
    x = jax.ShapeDtypeStruct((4, 28, 28, 1), jnp.float32)
    counted = count_fn(cnn_logits, jax.eval_shape(lambda: params), x)["flops_total"]
    ours = 4 * flops.cnn_forward_flops_per_sample(cfg)
    assert ours <= counted <= 1.1 * ours


@pytest.mark.parametrize("n", [1, 1024, 206874, 13762560])
def test_kernel_bytes_count_the_calls_operands(n):
    """Bytes from shapes count what the algorithm must move (the f32
    input once, one bit or one int32 count out), never more than the
    operands of the kernels' calls as the program builds them, so that a
    roofline share cannot pass 100% from the count."""
    from repro.kernels.ops import padded_len

    rows = padded_len(n) // 1024
    f32_row = rows * 1024 * 4
    bits = -(-n // 8)
    assert flops.stoch_quant_pack_bytes(n) == 4 * n + bits
    assert flops.stoch_quant_pack_bytes(n) <= 3 * f32_row + rows * 128 + 1024 * 128 * 4
    assert flops.prox_sgd_bytes(n) == 6 * 4 * n <= 6 * f32_row
    assert flops.bit_count_bytes(512, n) == 512 * bits + 4 * n
    assert flops.bit_count_bytes(512, n) <= 512 * rows * 128 + f32_row


def test_peaks_are_keyed_by_device_kind_and_unknown_kinds_fail():
    from bench import metric_math

    peaks = json.loads((BENCH / "peaks.json").read_text())
    assert peaks["TPU v5 lite"]["flops_bf16"] == 197e12
    assert peaks["TPU v5 lite"]["hbm_bytes_per_s"] == 819e9

    class Rec:
        device_kind = "TPU v99"

    Rec.peaks = peaks
    with pytest.raises(KeyError):
        metric_math.peak(Rec, "flops_bf16")


def test_the_decoder_config_is_the_programs_qwen2_cut_in_depth_only():
    """The program's qwen2-1.5b cut in depth, with the published norm
    epsilon and tied head, which the program's config class takes as
    options; only the depth departs from the published file."""
    import dataclasses

    from repro import configs

    from bench.drivers.xsilo import program_model_config

    cfg = json.loads((BENCH / "configs" / "qwen2-1.5b-20L.json").read_text())
    want = dataclasses.replace(
        configs.with_depth(configs.get_config("qwen2-1.5b"), cfg["num_hidden_layers"]),
        norm_eps=1e-6, tie_embeddings=True,
    )
    got = program_model_config(cfg)
    assert dataclasses.replace(got, name=want.name) == want
    assert cfg["reduced"] == ["num_hidden_layers"]
    for key in cfg["reduced"]:
        assert cfg["published"][key] != cfg[key]


def test_the_window_records_each_rounds_host_spans():
    """A slow round is told apart by the host time of its spans."""
    import time

    class Drv:
        n = 0

        def round(self):
            self.n += 1
            with harness.span("batch"):
                time.sleep(0.3 if self.n == 3 else 0.02)
            return {"clients": 1}

    counter = harness.CompileCounter()
    try:
        w = harness.measure(Drv(), 0.45, counter)
    finally:
        counter.close()
    assert len(w.host) == len(w.seconds) == len(w.work) >= 4
    assert all(set(h) == {"batch", "gc"} for h in w.host)
    slow = harness.slow_rounds(w)
    assert 2 in [r[0] for r in slow]
    for i, t, host in slow:
        assert host["batch"] >= 0.9 * t
    assert dict((r[0], r[2]) for r in slow)[2]["batch"] >= 0.3
