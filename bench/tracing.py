"""Reduce a profiler trace to device busy time, idle gaps by host span,
per-kernel device time and collective time.

The trace is the ``.xplane.pb`` that ``jax.profiler.trace`` writes, read
with ``jax.profiler.ProfileData``. Device planes are named
``/device:TPU:<n>``; the operations that ran on a chip are the events of
its ``XLA Ops`` line, each named by its HLO instruction text (a Pallas
kernel's custom call carries its jit's name: ``%stoch_quant_pack_2d.1 =
u8[...] custom-call(...)``). The harness's own host spans (``bench.*``
``TraceAnnotation`` events) lie on the host plane on the same clock, and
``bench.window`` marks the traced window.

Everything is reduced over the traced window only:

* busy: the union of the intervals in which the device ran an operation
  or a program (``XLA Modules``). Where the trace holds every operation
  the two agree (0.2% apart on the committed chip trace); where it holds
  a program's operations only in part, the program's interval keeps the
  device from reading idle while it ran;
* idle gaps: the complement of busy, each stretch of it named by the
  innermost host span that holds it (``bench.round`` while the host was
  in the round's call, ``bench.eval`` during an evaluation ...);
* complete: whether the trace holds the whole window. Every round ends
  in ``block_until_ready`` (the driver's ``bench.sync`` span), which
  returns once the device has finished, so a chip whose last recorded
  event ends well before the window's last ``bench.sync`` ends was not
  recorded to the end: the profiler stops recording at its event limit
  (about 4.2M operations). Metrics are not read from an incomplete trace;
* the traced window: the harness's ``bench.window`` span, whole rounds;
  in an incomplete trace, the part of it the trace covers (to the last
  recorded device event, mean over chips), over which busy and idle
  gaps are then reduced;
* kernel time: the summed durations of the operations whose name
  carries a kernel's jit name (``stoch_quant_pack_2d`` ...);
* collective time: the summed durations of all-reduce operations.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
from collections import defaultdict

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "bench.window"
SYNC_SPAN = "bench.sync"
# The host's wait returns within a few ms of the device's last operation
# (2 ms on the committed chip trace); a cut trace stops seconds earlier.
SYNC_SLACK_NS = 50e6
SPAN_PREFIX = "bench."
COLLECTIVE_MARKS = ("all-reduce",)


@dataclasses.dataclass
class Reduction:
    window_s: float  # the traced window, as far as the trace covers it
    complete: bool  # every chip recorded to the window's last sync
    busy_s: float  # mean over the chips
    busy_by_chip: list
    kernel_s: dict  # kernel jit name -> device seconds, mean over chips
    collective_s_by_chip: list
    top_ops: list  # [(label, seconds)] over all chips, mean per chip
    idle_gaps: list  # [(host span, seconds)] summed, mean per chip
    n_events: int
    lines: dict  # first chip's lines: name -> [events, first, last] in the window

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def breakdown(self) -> dict:
        return {
            "device_ops": [[n, s] for n, s in self.top_ops[:10]],
            "idle_gaps": [[n, s] for n, s in self.idle_gaps[:10]],
        }


def _union(intervals):
    """Merge (start, end) intervals; returns the sorted disjoint union."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _label(name: str) -> str:
    """A stable name for an operation: the HLO instruction's name
    (``%fusion.12 = ...`` -> ``fusion``), numbered copies merged."""
    head = name.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"(\.(\d+|clone|sunk|remat\d*))+$", "", head)


class _SpanSweep:
    """Names points in time, in increasing order, by the innermost host
    span that holds them ("untraced" where none does)."""

    def __init__(self, spans):
        self.spans = sorted(spans, key=lambda t: t[1])
        self.next = 0
        self.active = []

    def owner(self, t: float) -> str:
        while self.next < len(self.spans) and self.spans[self.next][1] <= t:
            self.active.append(self.spans[self.next])
            self.next += 1
        self.active = [sp for sp in self.active if sp[2] > t]
        if not self.active:
            return "untraced"
        return max(self.active, key=lambda sp: sp[3])[0]


def reduce_profile(pd, *, chips: int, kernels: dict) -> Reduction:
    """Reduce a ``ProfileData``. ``kernels`` maps a metric's kernel key to
    the jit name its operations carry."""
    dev_planes = sorted(
        (p for p in pd.planes if p.name.startswith(DEVICE_PREFIX)),
        key=lambda p: int("".join(c for c in p.name[len(DEVICE_PREFIX):]
                                   if c.isdigit()) or 0),
    )[:chips]
    if not dev_planes:
        raise ValueError("the trace holds no TPU device plane")
    spans = []
    window = None
    last_sync = None
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            stack = []
            for ev in line.events:
                if not ev.name.startswith(SPAN_PREFIX):
                    continue
                a = ev.start_ns
                b = a + ev.duration_ns
                while stack and stack[-1] <= a:
                    stack.pop()
                spans.append((ev.name, a, b, len(stack)))
                stack.append(b)
                if ev.name == WINDOW_SPAN:
                    window = (a, b)
                elif ev.name == SYNC_SPAN:
                    last_sync = b if last_sync is None else max(last_sync, b)
    if window is None:
        raise ValueError(f"the trace holds no {WINDOW_SPAN} span")
    lo, hi = window

    edges = sorted({t for _, a, b, _ in spans for t in (a, b)})
    busy_by_chip, coll_by_chip, covered_by_chip = [], [], []
    complete = True
    kernel_ns = defaultdict(float)
    op_ns = defaultdict(float)
    gap_ns = defaultdict(float)
    label_cache = {}
    n_events = 0
    lines_seen = {}
    for line in dev_planes[0].lines:
        starts = [ev.start_ns for ev in line.events if lo <= ev.start_ns < hi]
        if starts:
            lines_seen[line.name] = [len(starts), (min(starts) - lo) * 1e-9,
                                     (max(starts) - lo) * 1e-9]
    for plane in dev_planes:
        intervals = []
        coll = 0.0
        for line in plane.lines:
            if line.name == MODULES_LINE:
                intervals += [
                    (max(ev.start_ns, lo), min(ev.start_ns + ev.duration_ns, hi))
                    for ev in line.events
                    if ev.start_ns + ev.duration_ns > lo and ev.start_ns < hi
                ]
            if line.name != OPS_LINE:
                continue
            for ev in line.events:
                a = ev.start_ns
                b = a + ev.duration_ns
                if b <= lo or a >= hi:
                    continue
                n_events += 1
                a, b = max(a, lo), min(b, hi)
                intervals.append((a, b))
                name = ev.name
                label = label_cache.get(name)
                if label is None:
                    label = label_cache[name] = _label(name)
                op_ns[label] += b - a
                for key, jit_name in kernels.items():
                    if jit_name in name:
                        kernel_ns[key] += b - a
                if any(m in label for m in COLLECTIVE_MARKS):
                    coll += b - a
        merged = _union(intervals)
        chip_complete = (last_sync is not None and bool(merged)
                         and merged[-1][1] >= last_sync - SYNC_SLACK_NS)
        complete = complete and chip_complete
        # a cut trace is reduced over the part of the window it covers
        chip_hi = hi if chip_complete or not merged else merged[-1][1]
        covered_by_chip.append((chip_hi - lo) * 1e-9)
        busy = sum(e - s for s, e in merged)
        busy_by_chip.append(busy * 1e-9)
        coll_by_chip.append(coll * 1e-9)
        sweep = _SpanSweep(spans)
        prev = lo
        for s, e in merged + [[chip_hi, chip_hi]]:
            if s > prev:
                # split the gap where host spans begin or end
                a = prev
                i = bisect.bisect_right(edges, a)
                while a < s:
                    b = min(s, edges[i]) if i < len(edges) else s
                    gap_ns[sweep.owner(0.5 * (a + b))] += b - a
                    a, i = b, i + 1
            prev = max(prev, e)
    n = len(dev_planes)
    return Reduction(
        window_s=sum(covered_by_chip) / n,
        complete=complete,
        busy_s=sum(busy_by_chip) / n,
        busy_by_chip=busy_by_chip,
        kernel_s={k: v * 1e-9 / n for k, v in kernel_ns.items()},
        collective_s_by_chip=coll_by_chip,
        top_ops=sorted(((k, v * 1e-9 / n) for k, v in op_ns.items()),
                       key=lambda kv: -kv[1]),
        idle_gaps=sorted(((k, v * 1e-9 / n) for k, v in gap_ns.items()),
                         key=lambda kv: -kv[1]),
        n_events=n_events,
        lines=lines_seen,
    )


def find_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {trace_dir}, got {paths}")
    return paths[0]


def reduce_file(path: str, *, chips: int, kernels: dict) -> Reduction:
    from jax.profiler import ProfileData

    return reduce_profile(ProfileData.from_file(path), chips=chips, kernels=kernels)


def reduce_dir(trace_dir: str, *, chips: int, kernels: dict) -> Reduction:
    return reduce_file(find_xplane(trace_dir), chips=chips, kernels=kernels)
