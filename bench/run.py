"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything a cell needs is found by name under ``bench/``: the cell in
``workloads/<cell>.json``, its configuration in ``configs/``, its traffic
mix in ``traffic/``, the driver that runs the program in ``drivers/`` and
one reader per metric in ``metrics/``. The cell's metrics are the entries
of ``BENCHMARK.json`` that list it (or list no cells at all).

The last line of standard output is one JSON object with ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` and, with ``--trace 1``,
``breakdown``; its last key, ``checks``, gives each number compared with
its limit. The run exits non-zero, with no result line, when JAX finds no
TPU or fewer chips than the cell asks for.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    src = ROOT / "src"
    if not (src / "repro").is_dir():
        print(f"bench: the program's sources are not at {src}", file=sys.stderr)
        return 2
    for p in (str(src), str(ROOT)):
        if p not in sys.path:
            sys.path.insert(0, p)
    # Compiled programs persist inside the checkout, at a fixed path, so a
    # later run of the checkout finds them again and no other shares them.
    cache = ROOT / ".jax_cache"
    cache.mkdir(exist_ok=True)  # JAX writes into it but does not make it
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(cache)
    from bench import harness

    return harness.run_cell(
        ROOT, args.workload, seed=args.seed, seconds=args.seconds,
        trace=bool(args.trace), t_start=T_START,
    )


if __name__ == "__main__":
    sys.exit(main())
