"""The trace reduction: busy union, idle gaps by host span, per-jit
kernel time and collective time."""

from __future__ import annotations

import dataclasses
from pathlib import Path

import pytest

from bench import tracing

DATA = Path(__file__).with_name("data")


@dataclasses.dataclass
class Ev:
    name: str
    start_ns: float
    duration_ns: float
    stats: tuple = ()


@dataclasses.dataclass
class Line:
    name: str
    events: list


@dataclasses.dataclass
class Plane:
    name: str
    lines: list


@dataclasses.dataclass
class Profile:
    planes: list


def _profile():
    host = Plane("/host:CPU", [Line("python", [
        Ev("bench.window", 0, 1000),
        Ev("bench.batch", 0, 100),
        Ev("bench.round", 100, 550),
        Ev("bench.sync", 650, 50),
        Ev("bench.eval", 800, 200),
    ])])
    ops = [
        Ev("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop", 100, 200),
        Ev("%fusion.2.clone = f32[8]{0} fusion(f32[8]{0} %q)", 250, 100),  # overlaps
        Ev("%stoch_quant_pack_2d.3 = u8[2,128]{1,0} custom-call(f32[2,1024]{1,0} %d),"
           ' custom_call_target="tpu_custom_call"', 500, 100),
        Ev("%all-reduce.4 = s32[8]{0} all-reduce(s32[8]{0} %c)", 650, 50),
        Ev("%fusion.5 = f32[8]{0} fusion(f32[8]{0} %r)", 1200, 100),  # after
    ]
    dev = Plane("/device:TPU:0", [
        Line("XLA Ops", ops),
        # a program whose interval ends where its last recorded op ends
        Line("XLA Modules", [Ev("jit_step", 100, 250)]),
        Line("Steps", [Ev("0", 0, 1e6)]),
    ])
    return Profile([host, dev])


def test_busy_union_gaps_kernels_and_collectives():
    red = tracing.reduce_profile(
        _profile(), chips=1, kernels={"stoch_quant": "stoch_quant_pack_2d"}
    )
    assert red.window_s == pytest.approx(1000e-9)
    assert red.complete
    # [100, 350) U [500, 600) U [650, 700)
    assert red.busy_s == pytest.approx(400e-9)
    assert red.idle_share == pytest.approx(0.6)
    assert red.kernel_s == {"stoch_quant": pytest.approx(100e-9)}
    assert red.collective_s_by_chip == [pytest.approx(50e-9)]
    gaps = dict(red.idle_gaps)
    # [0,100) batch; [350,500) + [600,650) round; [700,800) window; [800,1000) eval
    assert gaps == {
        "bench.batch": pytest.approx(100e-9),
        "bench.round": pytest.approx(200e-9),
        "bench.window": pytest.approx(100e-9),
        "bench.eval": pytest.approx(200e-9),
    }
    assert red.top_ops[0] == ("fusion", pytest.approx(300e-9))
    assert dict(red.top_ops)["stoch_quant_pack_2d"] == pytest.approx(100e-9)
    bd = red.breakdown()
    assert len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10


def test_a_trace_without_a_window_or_a_device_is_refused():
    prof = _profile()
    prof.planes[0].lines[0].events.pop(0)
    with pytest.raises(ValueError):
        tracing.reduce_profile(prof, chips=1, kernels={})
    with pytest.raises(ValueError):
        tracing.reduce_profile(Profile([_profile().planes[0]]), chips=1, kernels={})


def test_a_trace_recorded_on_the_chip():
    """Two PRoBit+ rounds of the paper's CNN (64 clients, cohort 32, the
    Pallas wire) and one evaluation, traced on one TPU v5e with the
    harness's spans: the reduction finds the window, the device's busy
    time and each of the three kernels by its jit name."""
    kernels = {"prox_sgd": "prox_sgd_2d", "stoch_quant": "stoch_quant_pack_2d",
               "bit_count": "bit_count_2d"}
    red = tracing.reduce_file(str(DATA / "cnn_rounds_v5e.xplane.pb"), chips=1,
                              kernels=kernels)
    assert red.window_s == pytest.approx(2.320731251)
    assert red.complete
    # the programs' intervals add 35 us to the operations' 16.906 ms
    assert red.busy_s == pytest.approx(0.016941312)
    assert red.n_events == 1353
    assert red.kernel_s == {
        "prox_sgd": pytest.approx(2.92712e-4),
        "stoch_quant": pytest.approx(5.40958e-4),
        "bit_count": pytest.approx(2.2532e-5),
    }
    assert red.collective_s_by_chip == [0.0]
    ops = dict(red.top_ops)
    assert set(kernels.values()) <= set(ops)
    gaps = dict(red.idle_gaps)
    # the first evaluation compiled its ops inside this trace
    assert max(gaps, key=gaps.get) == "bench.eval"
    assert sum(gaps.values()) == pytest.approx(red.window_s - red.busy_s)
    assert red.lines["XLA Ops"][0] == 1353


def test_a_trace_cut_by_the_profiler_covers_the_window_up_to_its_last_event():
    """The profiler's event limit cut the cross-silo trace after 3.44 s
    of a 31 s window while the device ran on (4,203,490 recorded ops on
    the chip). The trace then covers the window only up to its last
    event, short of the last round: it is marked incomplete, and no
    metric is read from it."""
    from bench import metric_math

    host = Plane("/host:CPU", [Line("python", [
        Ev("bench.window", 0, 10e9), Ev("bench.round", 0, 0.01e9),
        Ev("bench.sync", 0.01e9, 9.99e9),
    ])])
    ops = [Ev(f"%fusion.{i} = f32[8]{{0}} fusion()", i * 1e8, 0.9e8) for i in range(30)]
    dev = Plane("/device:TPU:0", [Line("XLA Ops", ops)])
    red = tracing.reduce_profile(Profile([host, dev]), chips=1, kernels={})
    assert not red.complete
    assert red.window_s == pytest.approx(2.99)  # the last op ends at 2.99 s
    assert red.busy_s == pytest.approx(2.7)
    assert dict(red.idle_gaps) == {"bench.sync": pytest.approx(0.29)}

    class Rec:
        trace = red

    assert metric_math.idle_pct(Rec) is None
    assert metric_math.kernel_roofline_pct(Rec, "stoch_quant") is None
    # the same trace recorded to the end of the window's last sync is read
    ops.append(Ev("%fusion.99 = f32[8]{0} fusion()", 9.9e9, 0.099e9))
    red = tracing.reduce_profile(Profile([host, dev]), chips=1, kernels={})
    assert red.complete
    assert red.window_s == pytest.approx(10.0)
    Rec.trace = red
    assert metric_math.idle_pct(Rec) == pytest.approx(100 * (1 - 2.799 / 10))
