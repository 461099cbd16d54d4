"""Readings that set a cell's limits: the program against the reference
on many seeds (the lower readings), and the control against the
reference (the upper readings).

    python3 bench/controls.py --workload <cell> --seeds 11,12,13 \
        [--control-seeds 21,22,23] [--fault-seeds 31,32,33]

For each of ``--seeds`` the program runs the cell's set-up (its first,
checked rounds) and the plain reference follows; for each of
``--control-seeds`` the reference computed one precision below the
configuration's (``LOWER_PRECISION`` of the cell's driver) takes the
program's place; for each of ``--fault-seeds`` so does the reference with
each of the driver's ``FAULTS`` planted in it. One JSON line per run:
``{"seed", "kind", "values"}``.
Everything runs in this one process, so the program compiles once. It
needs the chip a cell needs; the benchmark's runs never call it.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def readings(cell_name: str, seeds, control_seeds, out=None, root=ROOT,
             bench_dir=None, require_tpu=True, fault_seeds=()):
    """Yield one record per seed: the program's runs, then the control's,
    then each of the driver's ``FAULTS`` planted in the reference."""
    import gc

    from bench import harness

    cell = harness.find_cell(root, cell_name, bench_dir)
    if require_tpu:
        harness.device_info(cell.chips)
        from repro.launch.compile_cache import enable_compile_cache

        enable_compile_cache()
    runs = [("program", seeds), ("control", control_seeds)]
    runs += [(f, fault_seeds) for f in cell.driver.FAULTS]
    for kind, seed_list in runs:
        for seed in seed_list:
            t0 = time.monotonic()
            ctx = harness.CellContext(cell=cell, seed=seed, chips=cell.chips)
            drv = cell.driver.make(ctx)
            if kind == "program":
                drv.setup()
                drv.release()
                gc.collect()
                got = drv.readings
            elif kind == "control":
                drv.prepare()
                got = drv.reference_readings(drv.LOWER_PRECISION)
            else:
                drv.prepare()
                got = drv.reference_readings(fault=kind)
            values = drv.values(got, drv.reference_readings())
            rec = {"cell": cell_name, "seed": seed, "kind": kind,
                   "values": values, "seconds": time.monotonic() - t0}
            if out is not None:
                print(json.dumps(rec), file=out, flush=True)
            yield rec
            del drv
            gc.collect()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--fault-seeds", default="")
    args = ap.parse_args(argv)
    for p in (str(ROOT / "src"), str(ROOT)):
        if p not in sys.path:
            sys.path.insert(0, p)
    parse = lambda s: [int(x) for x in s.split(",") if x]  # noqa: E731
    for _ in readings(args.workload, parse(args.seeds), parse(args.control_seeds),
                      out=sys.stdout, fault_seeds=parse(args.fault_seeds)):
        pass
    return 0


if __name__ == "__main__":
    sys.exit(main())
