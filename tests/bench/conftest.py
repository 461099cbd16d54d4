"""Fixtures of the benchmark's CPU tests: a throwaway checkout that holds
the benchmark's drivers and metric readers with its configurations and
mixes cut to a tiny size, so that a whole run of a cell fits a test."""

from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "bench"
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY_DECODER = dict(
    hidden_size=64, intermediate_size=128, num_attention_heads=4,
    num_key_value_heads=2, num_hidden_layers=2, vocab_size=512,
)
TINY_CNN = dict(width=4, hidden=16)
TINY_XSILO = dict(seq=16, vocab_subset=64)
TINY_XDEV = dict(population=40, cohort=20, samples_per_client=20, test_samples=50)

# tiny cell -> (real cell it is cut from, config changes, traffic changes)
TINY_CELLS = {
    "xsilo-tiny": ("xsilo-qwen2-20L", TINY_DECODER, TINY_XSILO),
    "xdev-tiny": ("xdev-cnn-wire", TINY_CNN, TINY_XDEV),
}


def _load(path: Path):
    with open(path) as f:
        return json.load(f)


def _dump(obj, path: Path) -> None:
    path.parent.mkdir(parents=True, exist_ok=True)
    with open(path, "w") as f:
        json.dump(obj, f, indent=1)


def build_tiny_checkout(root: Path, cells=TINY_CELLS) -> Path:
    """A checkout under ``root`` whose cells are the real cells cut to a
    tiny size; returns its bench directory."""
    bench = root / "bench"
    bench.mkdir(parents=True)
    for sub in ("drivers", "metrics"):
        shutil.copytree(BENCH / sub, bench / sub)
    for sub in ("configs", "traffic", "workloads"):
        (bench / sub).mkdir()
    shutil.copy(BENCH / "peaks.json", bench / "peaks.json")
    spec = _load(ROOT / "BENCHMARK.json")
    workloads = []
    renamed = {}
    for tiny, (real, cfg_change, tr_change) in cells.items():
        entry = next(w for w in spec["workloads"] if w["name"] == real)
        cell = _load(BENCH / "workloads" / f"{real}.json")
        cfg = dict(_load(BENCH / "configs" / f"{entry['config']}.json"), **cfg_change)
        tr = dict(_load(BENCH / "traffic" / f"{entry['traffic']}.json"), **tr_change)
        cfg["name"] = f"{entry['config']}-{tiny}"
        _dump(cfg, bench / "configs" / f"{cfg['name']}.json")
        _dump(tr, bench / "traffic" / f"{entry['traffic']}-{tiny}.json")
        cell = dict(cell, config=cfg["name"], traffic=f"{entry['traffic']}-{tiny}")
        _dump(cell, bench / "workloads" / f"{tiny}.json")
        workloads.append(dict(entry, name=tiny, config=cell["config"],
                              traffic=cell["traffic"]))
        renamed[real] = tiny
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [renamed[w] for w in m["workloads"] if w in renamed]
    spec["workloads"] = workloads
    _dump(spec, root / "BENCHMARK.json")
    return bench


@pytest.fixture(scope="module")
def tiny_checkout(tmp_path_factory):
    root = tmp_path_factory.mktemp("checkout")
    return root, build_tiny_checkout(root)


def run_tiny(root: Path, bench: Path, cell: str, seed: int = 3, seconds=0.5,
             trace=False, capsys=None):
    """One run of a tiny cell on the CPU; returns (rc, result dict)."""
    import io
    import time

    from bench import harness

    out = io.StringIO()
    rc = harness.run_cell(
        root, cell, seed=seed, seconds=seconds, trace=trace,
        t_start=time.monotonic(), bench_dir=bench, require_tpu=False, out=out,
    )
    lines = out.getvalue().strip().splitlines()
    return rc, json.loads(lines[-1]) if lines else None
