"""Device self time per phase of the round, from a profiler trace's op
metadata.

The program names each phase of a round with a ``jax.named_scope``
(``fl.gather``, ``fl.train``, ``fl.attack``, ``fl.compress``,
``fl.count``, ``fl.finalize``, ``fl.update``, ``fl.writeback``). On the
TPU the scope reaches the trace as op metadata: each ``XLA Ops`` event's
name (the HLO instruction text) keys an ``XEventMetadata`` of the device
plane whose ``tf_op`` stat is the JAX name stack, e.g.
``jit(fl_round)/transpose(jvp(vmap(fl.train)))/mul:``.
``jax.profiler.ProfileData`` yields an event's timing stats only, so the
metadata is read from the ``.xplane.pb`` itself with a protobuf
wire-format reader (no dependency beyond the standard library).

The reduction, over the harness's ``bench.window``:

* self time: an op's duration (clipped to the window) less the part of
  it covered by ops nested inside it on the same line; a ``while`` op's
  event encloses the events of its body, so summed durations count the
  body twice and summed self times do not;
* phase: the innermost ``fl.<name>`` in the op's ``tf_op``, also inside
  transform wrappers (``transpose(jvp(fl.train))``). The name is taken
  from the text, so a new scope needs no change here;
* ``unscoped``: an op with no ``fl.*`` scope that runs inside a program
  execution (an ``XLA Modules`` event) holding at least one scoped op:
  compiler-made ops of the round's program, such as loops the compiler
  builds over the per-client state. ``other``: an op with no scope in a
  program with none (eager batch sampling, evaluation, set-up). No owner
  is inferred from program order.
"""

from __future__ import annotations

import bisect
import dataclasses
import re
from collections import defaultdict

from bench import tracing

SCOPE = re.compile(r"(?<![\w.])fl\.(\w+)")
UNSCOPED = "unscoped"
OTHER = "other"
TOP_UNSCOPED = 10

# Field numbers of tensorflow/tsl/profiler/protobuf/xplane.proto
_SPACE_PLANES = 1
_PLANE_NAME, _PLANE_EVENT_MD, _PLANE_STAT_MD = 2, 4, 5
_EVENT_MD_NAME, _EVENT_MD_STATS = 2, 5
_STAT_MD_ID, _STAT_MD_NAME = 1, 2
_STAT_MD_ID_REF, _STAT_STR, _STAT_REF = 1, 5, 7
_MAP_VALUE = 2


# ---------------------------------------------------------------------------
# Protobuf wire format
# ---------------------------------------------------------------------------

def _varint(buf, i: int):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf, lo: int, hi: int):
    """(field number, value) of a message in ``buf[lo:hi]``: an int for a
    varint, a (start, end) span for a length-delimited field; fixed-width
    values are skipped."""
    i = lo
    while i < hi:
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            v, i = _varint(buf, i)
            yield field, v
        elif wire == 2:
            n, i = _varint(buf, i)
            yield field, (i, i + n)
            i += n
        elif wire == 1:
            i += 8
        elif wire == 5:
            i += 4
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")


def _text(buf, span) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _map_values(buf, span):
    for field, v in _fields(buf, *span):
        if field == _MAP_VALUE:
            yield v


def read_op_metadata(path: str) -> dict:
    """``{device plane name: {event name: [{stat name: text}]}}``: the
    string stats (``tf_op``, ``shape_with_layout``, ``hlo_category`` ...)
    of each metadata entry of the TPU planes, by the name its events
    carry."""
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    out = {}
    for field, plane in _fields(buf, 0, len(buf)):
        if field != _SPACE_PLANES:
            continue
        name, event_md, stat_md = None, [], {}
        for f2, v in _fields(buf, *plane):
            if f2 == _PLANE_NAME:
                name = _text(buf, v)
            elif f2 == _PLANE_EVENT_MD:
                event_md.extend(_map_values(buf, v))
            elif f2 == _PLANE_STAT_MD:
                for md in _map_values(buf, v):
                    sid, sname = None, None
                    for f3, w in _fields(buf, *md):
                        if f3 == _STAT_MD_ID:
                            sid = w
                        elif f3 == _STAT_MD_NAME:
                            sname = _text(buf, w)
                    stat_md[sid] = sname
        if name is None or not name.startswith(tracing.DEVICE_PREFIX):
            continue
        events = out.setdefault(name, {})
        for md in event_md:
            ev_name, stats = None, {}
            for f3, w in _fields(buf, *md):
                if f3 == _EVENT_MD_NAME:
                    ev_name = _text(buf, w)
                elif f3 == _EVENT_MD_STATS:
                    sid, value = None, None
                    for f4, x in _fields(buf, *w):
                        if f4 == _STAT_MD_ID_REF:
                            sid = x
                        elif f4 == _STAT_STR:
                            value = _text(buf, x)
                        elif f4 == _STAT_REF:
                            value = stat_md.get(x)
                    if sid in stat_md and value is not None:
                        stats[stat_md[sid]] = value
            if ev_name is not None:
                events.setdefault(ev_name, []).append(stats)
    return out


# ---------------------------------------------------------------------------
# Phases and self time
# ---------------------------------------------------------------------------

def phase_of(tf_op: str | None) -> str | None:
    """The innermost ``fl.<name>`` scope of a name stack, or None."""
    names = SCOPE.findall(tf_op or "")
    return f"fl.{names[-1]}" if names else None


def _op_info(stats_list: list, event: str) -> tuple:
    """(phase, shape_with_layout) of an event name; every metadata entry
    of one name must name the same phase."""
    phases = {phase_of(s.get("tf_op")) for s in stats_list}
    if len(phases) > 1:
        raise ValueError(f"op {event[:80]!r} maps to the scopes {sorted(map(str, phases))}")
    shape = next((s["shape_with_layout"] for s in stats_list
                  if "shape_with_layout" in s), "")
    return phases.pop(), shape


def self_times(intervals: list) -> list:
    """Self time of each (start, end) interval of one line: its length
    less the part covered by the intervals nested directly inside it."""
    order = sorted(range(len(intervals)),
                   key=lambda k: (intervals[k][0], -intervals[k][1]))
    out = [b - a for a, b in intervals]
    stack = []  # indices of the enclosing intervals, innermost last
    for k in order:
        a, b = intervals[k]
        while stack and intervals[stack[-1]][1] <= a:
            stack.pop()
        if stack:
            parent = stack[-1]
            out[parent] -= min(b, intervals[parent][1]) - a
        stack.append(k)
    return out


@dataclasses.dataclass
class PhaseReduction:
    phase_s: dict  # phase -> device seconds of self time, mean over chips
    unscoped_ops: list  # [(label, shape_with_layout, seconds)], top by self time
    ops_busy_s: float  # union of the op intervals, mean over chips

    def breakdown(self) -> dict:
        return {
            "device_phases": sorted(([k, v] for k, v in self.phase_s.items()),
                                    key=lambda kv: -kv[1]),
            "unscoped_ops": [list(t) for t in self.unscoped_ops],
        }


def _window(pd) -> tuple:
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == tracing.WINDOW_SPAN:
                    return ev.start_ns, ev.start_ns + ev.duration_ns
    raise ValueError(f"the trace holds no {tracing.WINDOW_SPAN} span")


def reduce_phases(pd, metadata: dict, *, chips: int) -> PhaseReduction:
    """Reduce a ``ProfileData`` with the op metadata of its file
    (:func:`read_op_metadata`)."""
    dev_planes = sorted(
        (p for p in pd.planes if p.name.startswith(tracing.DEVICE_PREFIX)),
        key=lambda p: int("".join(c for c in p.name[len(tracing.DEVICE_PREFIX):]
                                   if c.isdigit()) or 0),
    )[:chips]
    if not dev_planes:
        raise ValueError("the trace holds no TPU device plane")
    lo, hi = _window(pd)
    phase_ns = defaultdict(float)
    unscoped_ns = defaultdict(float)
    busy = 0.0
    for plane in dev_planes:
        md = metadata.get(plane.name, {})
        info = {}
        programs, ops = [], []
        for line in plane.lines:
            if line.name not in (tracing.MODULES_LINE, tracing.OPS_LINE):
                continue
            for ev in line.events:
                a, b = ev.start_ns, ev.start_ns + ev.duration_ns
                if b <= lo or a >= hi:
                    continue
                span = (max(a, lo), min(b, hi))
                if line.name == tracing.MODULES_LINE:
                    programs.append(span)
                else:
                    if ev.name not in info:
                        info[ev.name] = _op_info(md.get(ev.name, [{}]), ev.name)
                    ops.append((span, ev.name))
        programs.sort()
        starts = [a for a, _ in programs]

        def program_of(t):
            i = bisect.bisect_right(starts, t) - 1
            return i if i >= 0 and t <= programs[i][1] else None

        scoped_programs = set()
        where = []
        for (a, _), name in ops:
            p = program_of(a)
            where.append(p)
            if info[name][0] is not None and p is not None:
                scoped_programs.add(p)
        selfs = self_times([span for span, _ in ops])
        for ((_, _), name), p, t in zip(ops, where, selfs):
            phase, shape = info[name]
            if phase is None:
                phase = UNSCOPED if p in scoped_programs else OTHER
                if phase == UNSCOPED:
                    unscoped_ns[(tracing._label(name), shape)] += t
            phase_ns[phase] += t
        busy += sum(b - a for a, b in tracing._union([span for span, _ in ops]))
    n = len(dev_planes)
    top = sorted(unscoped_ns.items(), key=lambda kv: -kv[1])[:TOP_UNSCOPED]
    return PhaseReduction(
        phase_s={k: v * 1e-9 / n for k, v in phase_ns.items()},
        unscoped_ops=[(label, shape, v * 1e-9 / n) for (label, shape), v in top],
        ops_busy_s=busy * 1e-9 / n,
    )


def reduce_file(path: str, *, chips: int) -> PhaseReduction:
    from jax.profiler import ProfileData

    return reduce_phases(ProfileData.from_file(path), read_op_metadata(path),
                         chips=chips)
