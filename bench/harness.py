"""The benchmark harness: find a cell's files by name, set the cell up,
measure a closed loop of rounds, read the metrics, check the result.

A cell is ``workloads/<cell>.json``; it names its configuration
(``configs/<config>.json``), its traffic mix (``traffic/<traffic>.json``),
the chips it needs and the limit of every number its check compares. The
traffic mix names the driver (``drivers/<driver>.py``) that runs the
program on it. Each metric is read by ``metrics/<metric>.py``. Adding a
cell, a configuration, a mix, a driver or a metric adds files and a
``BENCHMARK.json`` entry; nothing here changes.

A driver module exposes ``make(ctx) -> driver`` where ``ctx`` is a
:class:`CellContext`; the driver has

* ``setup()``: build the program's entry, weights and data from the seed,
  compile and warm every shape the window uses, and drive the first
  rounds whose readings the check compares;
* ``round() -> dict``: one synchronous round through the program, ending
  in ``block_until_ready`` inside ``span("sync")``; returns the work it did (``tokens``,
  ``clients``, ``samples``) and ``ok`` (False when an output is not
  finite);
* ``facts() -> dict``: shape-derived counts the metric readers need
  (FLOPs per unit of work, kernel bytes per round);
* ``release()``: drop the program's device state;
* ``check() -> list[Check]``: run the plain reference and compare.
"""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib.util
import json
import math
import shutil
import sys
import tempfile
import time
from pathlib import Path
from types import ModuleType
from typing import Any, Callable

BENCH = Path(__file__).resolve().parent


# ---------------------------------------------------------------------------
# Discovery
# ---------------------------------------------------------------------------

def load_json(path: Path) -> Any:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path) -> ModuleType:
    """Import a file by path (metric names carry dots, so no package import)."""
    name = "bench_dyn_" + "".join(c if c.isalnum() else "_" for c in str(path))
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    config_name: str
    traffic_name: str
    chips: int
    limits: dict
    config: dict
    traffic: dict
    driver: ModuleType
    end_to_end: list  # BENCHMARK.json entries this cell reports
    per_layer: list


def _lists(entry: dict, cell: str) -> bool:
    cells = entry.get("workloads")
    return cells is None or cell in cells


def find_cell(root: Path, name: str, bench_dir: Path | None = None) -> Cell:
    """Resolve a cell by name from ``BENCHMARK.json`` and the files under
    ``bench_dir`` (default: this package's directory)."""
    bench_dir = bench_dir or BENCH
    spec = load_json(root / "BENCHMARK.json")
    entries = [w for w in spec["workloads"] if w["name"] == name]
    if len(entries) != 1:
        raise KeyError(f"no workload {name!r} in {root / 'BENCHMARK.json'}")
    entry = entries[0]
    cell_file = load_json(bench_dir / "workloads" / f"{name}.json")
    for key in ("config", "traffic", "chips"):
        if cell_file[key] != entry[key]:
            raise ValueError(
                f"{name}: {key} is {cell_file[key]!r} in workloads/{name}.json "
                f"but {entry[key]!r} in BENCHMARK.json"
            )
    config = load_json(bench_dir / "configs" / f"{entry['config']}.json")
    traffic = load_json(bench_dir / "traffic" / f"{entry['traffic']}.json")
    driver = load_module(bench_dir / "drivers" / f"{traffic['driver']}.py")
    return Cell(
        name=name,
        config_name=entry["config"],
        traffic_name=entry["traffic"],
        chips=int(entry["chips"]),
        limits=cell_file.get("limits", {}),
        config=config,
        traffic=traffic,
        driver=driver,
        end_to_end=[m for m in spec["end_to_end"] if _lists(m, name)],
        per_layer=[m for m in spec["per_layer"] if _lists(m, name)],
    )


def metric_reader(name: str, bench_dir: Path | None = None) -> Callable:
    return load_module((bench_dir or BENCH) / "metrics" / f"{name}.py").read


# ---------------------------------------------------------------------------
# Spans and compile counting
# ---------------------------------------------------------------------------

SPAN_PREFIX = "bench."
# host-clock seconds per span name in the window's round under way
_round_host: dict | None = None


@contextlib.contextmanager
def span(name: str):
    """A host span in the profiler's trace (free when no trace is on); in
    the measured window its host-clock time is added to the round's
    record as well."""
    import jax

    t0 = time.monotonic()
    with jax.profiler.TraceAnnotation(SPAN_PREFIX + name):
        yield
    if _round_host is not None:
        _round_host[name] = _round_host.get(name, 0.0) + time.monotonic() - t0


class CompileCounter:
    """Counts lowerings and backend compiles while ``on``.

    Any jit cache miss lowers a program (``jaxpr_to_mlir_module``), and a
    persistent-cache miss compiles it too; inside the measured window both
    mean that a shape was not warmed up.
    """

    EVENTS = (
        "/jax/core/compile/jaxpr_to_mlir_module_duration",
        "/jax/core/compile/backend_compile_duration",
    )

    def __init__(self):
        import jax

        self.on = False
        self.lowerings = 0
        self.compiles = 0

        def listener(event, duration, **_):
            if not self.on:
                return
            if event == self.EVENTS[0]:
                self.lowerings += 1
            elif event == self.EVENTS[1]:
                self.compiles += 1

        self._listener = listener
        jax.monitoring.register_event_duration_secs_listener(listener)

    def close(self):
        import jax

        jax.monitoring.unregister_event_duration_listener(self._listener)


# ---------------------------------------------------------------------------
# Checks
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Check:
    """One number the correctness check compares, with its limit. A check
    passes when ``value <= limit``; a value that is not finite fails."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return math.isfinite(self.value) and self.value <= self.limit


def limit_of(limits: dict, name: str) -> float:
    if name not in limits:
        raise KeyError(f"the cell gives no limit for check {name!r}")
    return float(limits[name])


# ---------------------------------------------------------------------------
# Device
# ---------------------------------------------------------------------------

class NoAccelerator(RuntimeError):
    pass


def device_info(chips: int) -> dict:
    """The TPU devices the cell runs on; raises when there are too few."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoAccelerator(f"no TPU found (JAX platform is {devs[0].platform!r})")
    if len(devs) < chips:
        raise NoAccelerator(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    return {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": chips,
    }


def memory_peak_bytes(chips: int) -> int | None:
    import jax

    peaks = []
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


# ---------------------------------------------------------------------------
# The run
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class CellContext:
    """What a driver is given: the cell's files, the seed and the chips."""

    cell: Cell
    seed: int
    chips: int
    span: Callable = span


@dataclasses.dataclass
class Window:
    """The measured window: one entry per round, in order."""

    seconds: list  # host-clock duration of each round
    work: list  # per round: dict of work counts
    host: list  # per round: host seconds per span name and in "gc"
    window_s: float
    compiles: int
    lowerings: int

    def total(self, key: str) -> float:
        return float(sum(w.get(key, 0) for w in self.work))


@dataclasses.dataclass
class RunRecord:
    """Everything a metric reader may read."""

    setup_s: float
    window: Window
    facts: dict
    chips: int
    peaks: dict
    device_kind: str
    trace: Any = None  # tracing.Reduction with --trace 1


def measure(drv, seconds: float, counter: CompileCounter, span_fn=span) -> Window:
    """Closed loop: one synchronous round after another; the window ends
    with the first round that finishes after ``seconds``. Each round's
    record holds the host time of its spans and of garbage collection,
    so that a slow round can be told apart from a slow device."""
    global _round_host
    durations, work, host = [], [], []
    gc_s = [0.0, 0.0]  # start of the collection under way, seconds in collections

    def on_gc(phase, info):
        if phase == "start":
            gc_s[0] = time.monotonic()
        else:
            gc_s[1] += time.monotonic() - gc_s[0]

    gc.callbacks.append(on_gc)
    counter.lowerings = counter.compiles = 0
    counter.on = True
    t0 = time.monotonic()
    try:
        with span_fn("window"):
            while True:
                _round_host, gc_s[1] = {}, 0.0
                r0 = time.monotonic()
                w = drv.round()
                r1 = time.monotonic()
                _round_host["gc"] = gc_s[1]
                host.append(_round_host)
                durations.append(r1 - r0)
                work.append(w)
                if r1 - t0 >= seconds:
                    break
            _round_host = None
    finally:
        _round_host = None
        gc.callbacks.remove(on_gc)
    window_s = time.monotonic() - t0
    counter.on = False
    return Window(durations, work, host, window_s, counter.compiles, counter.lowerings)


def slow_rounds(window: Window, factor: float = 2.0) -> list:
    """Rounds that took over ``factor`` times the median round, with the
    host time of their spans: [index, seconds, {span: seconds}]."""
    if not window.seconds:
        return []
    med = sorted(window.seconds)[len(window.seconds) // 2]
    return [[i, t, window.host[i]] for i, t in enumerate(window.seconds)
            if t > factor * med]


def run_cell(
    root: Path,
    name: str,
    *,
    seed: int,
    seconds: float,
    trace: bool,
    t_start: float,
    bench_dir: Path | None = None,
    require_tpu: bool = True,
    out=sys.stdout,
) -> int:
    """Set up, measure, read and check one cell; print the result line.

    ``require_tpu=False`` is for the harness's own tests, which drive a
    run on the CPU at a tiny size; a benchmark run always requires a TPU.
    """
    import jax

    from bench import tracing

    bench_dir = bench_dir or BENCH
    cell = find_cell(root, name, bench_dir)
    if require_tpu:
        try:
            dev = device_info(cell.chips)
        except NoAccelerator as e:
            print(f"bench: {e}", file=sys.stderr)
            return 1
    else:
        d0 = jax.devices()[0]
        dev = {"platform": d0.platform, "kind": d0.device_kind, "count": cell.chips}

    if require_tpu:
        from repro.launch.compile_cache import enable_compile_cache

        enable_compile_cache()
        # cache every program, however quick to compile, so that a second
        # run of the cell finds all of them
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    peaks = load_json(bench_dir / "peaks.json")
    counter = CompileCounter()
    ctx = CellContext(cell=cell, seed=seed, chips=cell.chips)
    drv = cell.driver.make(ctx)
    drv.setup()
    # what set-up made is not rescanned by every collection in the window
    gc.collect()
    gc.freeze()
    setup_s = time.monotonic() - t_start

    reduction = None
    if trace:
        tdir = tempfile.mkdtemp(prefix="bench_trace_")
        try:
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0  # the harness's spans suffice
            with jax.profiler.trace(tdir, profiler_options=options):
                window = measure(drv, seconds, counter)
            reduction = tracing.reduce_dir(
                tdir, chips=cell.chips, kernels=drv.facts().get("kernels", {}),
            )
        finally:
            shutil.rmtree(tdir, ignore_errors=True)
    else:
        window = measure(drv, seconds, counter)
    counter.close()
    gc.unfreeze()
    mem_peak = memory_peak_bytes(cell.chips)
    facts = drv.facts()
    record = RunRecord(
        setup_s=setup_s, window=window, facts=facts, chips=cell.chips,
        peaks=peaks, device_kind=dev["kind"], trace=reduction,
    )
    wanted = cell.per_layer if trace else cell.end_to_end
    metrics = {}
    for m in wanted:
        value = metric_reader(m["name"], bench_dir)(record)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}

    # Host-clock rounds and the peak go on an earlier line, not the result.
    print(json.dumps({
        "rounds_s": window.seconds,
        "slow_rounds": slow_rounds(window),
        "gc_s": sum(h["gc"] for h in window.host),
        "window_s": window.window_s,
        "setup_s": setup_s,
        "memory_peak_bytes": mem_peak,
        "compiles_in_window": window.compiles,
        "lowerings_in_window": window.lowerings,
        "facts": {k: v for k, v in facts.items() if k != "kernels"},
        "trace_complete": reduction.complete if reduction else None,
        "trace_lines": reduction.lines if reduction else None,
    }), file=out, flush=True)

    # The reference runs once the program's state is freed and the peak
    # is read, so that it neither sets the peak nor shares the memory.
    drv.release()
    gc.collect()
    checks = list(drv.check())
    del drv
    checks.append(Check("window_compiles", float(window.lowerings), 0.0))
    failed = sum(1 for w in window.work if not w.get("ok", True))
    correct = all(c.ok for c in checks) and failed == 0

    device = dict(dev)
    device["memory_peak_bytes"] = mem_peak
    result = {
        "correct": correct,
        "attempted": len(window.work),
        "failed": failed,
        "metrics": metrics,
        "device": device,
    }
    if reduction is not None:
        device["busy_s"] = reduction.busy_s
        device["window_s"] = reduction.window_s
        result["breakdown"] = reduction.breakdown()
    result["checks"] = {
        c.name: {"value": float(c.value), "limit": c.limit} for c in checks
    }
    for c in checks:
        print(
            f"check {c.name}: {c.value!r} (limit {c.limit!r}) "
            f"{'ok' if c.ok else 'FAILED'}",
            file=sys.stderr, flush=True,
        )
    print(json.dumps(result), file=out, flush=True)
    return 0
