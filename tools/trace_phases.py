"""Trace a benchmark cell on the TPU and split its device time by phase
of the round (``bench.phases``).

    python tools/trace_phases.py --workload xdev-cnn-wire --seed 7 --seconds 10
    python tools/trace_phases.py --fixture out.xplane.pb --seed 7

The first form runs the cell as ``bench/run.py --trace 1`` does and
prints its two output lines, then one more JSON line: the device seconds
of self time per phase over the traced window and per round, the top
unscoped ops with their shapes, whether the trace holds the whole window,
and the host seconds each reduction took.

The second form records a small trace for the CPU tests: two rounds of
the ``xdev-cnn-wire`` cell's program (the paper's CNN on the Pallas wire)
cut to 64 clients with a cohort of 32, the second of them evaluating,
with the harness's spans, written to the given file.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import io  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

FIXTURE_CELL = "xdev-cnn-wire"
FIXTURE_TRAFFIC = {"population": 64, "cohort": 32}


def split_cell(workload: str, seed: int, seconds: float) -> int:
    from bench import harness, phases, tracing

    extra = {}
    reduce_dir = tracing.reduce_dir

    def reduce_with_phases(tdir, *, chips, kernels):
        t0 = time.monotonic()
        red = reduce_dir(tdir, chips=chips, kernels=kernels)
        t1 = time.monotonic()
        split = phases.reduce_file(tracing.find_xplane(tdir), chips=chips)
        extra.update(split.breakdown(), trace_complete=red.complete,
                     busy_s=red.busy_s, ops_busy_s=split.ops_busy_s,
                     reduce_s=t1 - t0, phases_reduce_s=time.monotonic() - t1)
        return red

    tracing.reduce_dir = reduce_with_phases
    out = io.StringIO()
    rc = harness.run_cell(ROOT, workload, seed=seed, seconds=seconds,
                          trace=True, t_start=T_START, out=out)
    print(out.getvalue(), end="", flush=True)
    lines = out.getvalue().strip().splitlines()
    rounds = json.loads(lines[-1])["attempted"] if lines else 0
    if rounds:
        extra["phase_ms_per_round"] = {
            k: 1e3 * v / rounds for k, v in extra["device_phases"]}
    print(json.dumps({"phases": extra}), flush=True)
    return rc


def record_fixture(path: str, seed: int) -> int:
    import jax

    from bench import harness, tracing

    try:
        harness.device_info(1)
    except harness.NoAccelerator as e:
        print(f"trace_phases: {e}", file=sys.stderr)
        return 1
    cell = harness.find_cell(ROOT, FIXTURE_CELL)
    cell.traffic = dict(cell.traffic, **FIXTURE_TRAFFIC)
    drv = cell.driver.make(harness.CellContext(cell=cell, seed=seed, chips=1))
    drv.setup()  # three checked rounds: the next two end with an evaluation
    tdir = tempfile.mkdtemp(prefix="trace_phases_")
    try:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 1
        with jax.profiler.trace(tdir, profiler_options=options):
            with harness.span("window"):
                drv.round()
                drv.round()
        shutil.copy(tracing.find_xplane(tdir), path)
    finally:
        shutil.rmtree(tdir, ignore_errors=True)
    print(json.dumps({"fixture": path, "bytes": os.path.getsize(path)}))
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload")
    ap.add_argument("--fixture", help="record the small test trace into this file")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    cache = ROOT / ".jax_cache"
    cache.mkdir(exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(cache)
    if args.fixture:
        return record_fixture(args.fixture, args.seed)
    if not args.workload:
        ap.error("give --workload or --fixture")
    return split_cell(args.workload, args.seed, args.seconds)


if __name__ == "__main__":
    sys.exit(main())
