"""Device self time per phase of the round (bench.phases): the protobuf
reader of the op metadata, self time under nesting, the innermost scope
of a name stack, and the unscoped / other split by program."""

from __future__ import annotations

import dataclasses
from pathlib import Path

import pytest

from bench import phases, tracing

DATA = Path(__file__).with_name("data")
SQ_TF_OP = ("jit(<unknown>)/jit(stoch_quant_compress_batch)/"
            "vmap(jit(stoch_quant_compress))/jit(stoch_quant_pack_2d)")


@dataclasses.dataclass
class Ev:
    name: str
    start_ns: float
    duration_ns: float


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


DEV = "/device:TPU:0"


def _profile(ops, modules, window=(0, 1000)):
    host = Plane("/host:CPU", [Line("python", [
        Ev("bench.window", window[0], window[1] - window[0])])])
    dev = Plane(DEV, [Line("XLA Ops", [Ev(*o) for o in ops]),
                      Line("XLA Modules", [Ev(*m) for m in modules])])
    return Profile([host, dev])


@pytest.mark.parametrize("tf_op, phase", [
    ("jit(fl_round)/fl.gather/gather:", "fl.gather"),
    ("jit(fl_round)/transpose(jvp(vmap(fl.train)))/mul:", "fl.train"),
    ("jit(fl_round)/fl.compress/fl.compress/jit(stoch_quant_compress_batch)/"
     "vmap(jit(stoch_quant_compress))/jit(stoch_quant_pack_2d)/pallas_call:", "fl.compress"),
    ("jit(fl_round)/jit(bit_aggregate)/fl.count/jit(bit_count_2d)/pallas_call:", "fl.count"),
    ("jit(step)/fl.update/fl.finalize/mul:", "fl.finalize"),
    # a scope a later program adds is found without a list of names
    ("jit(fl_round)/fl.merge_edges/add:", "fl.merge_edges"),
    ("jit(fl_round)/vmap()/while/body/closed_call/transpose(jvp())/reshape:", None),
    ("jit(fl_round)/self.fl.train/mul:", None),
    ("", None),
    (None, None),
])
def test_the_innermost_scope_names_the_phase(tf_op, phase):
    assert phases.phase_of(tf_op) == phase


def test_self_time_of_a_loop_leaves_out_its_body():
    # a while [0, 100) with a body of two ops, one of them a loop itself
    spans = [(0, 100), (10, 40), (50, 90), (55, 60), (60, 70), (120, 130)]
    assert phases.self_times(spans) == [30, 30, 25, 5, 10, 10]
    assert sum(phases.self_times(spans)) == sum(
        b - a for a, b in tracing._union(spans))


def test_phases_sum_self_time_and_split_unscoped_from_other():
    ops = [
        # the round's program [100, 500): a loop around training ops, a
        # compiler-made state loop with no scope, compress
        ("%while.1 = (f32[4]) while()", 100, 200),
        ("%fusion.2 = f32[4] fusion()", 110, 80),
        ("%convolution.3 = f32[4] convolution()", 200, 90),
        ("%while.4 = (f32[64,8]) while()", 320, 100),
        ("%dynamic-update-slice.5 = f32[64,8] dynamic-update-slice()", 330, 60),
        ("%stoch_quant_pack_2d.6 = u8[2,128] custom-call()", 430, 50),
        # an eager sampling program [600, 700): no scope anywhere
        ("%fusion.8 = f32[4] fusion()", 600, 40),
        ("%gather.7 = f32[4] gather()", 650, 30),
    ]
    modules = [("jit_fl_round", 100, 400), ("jit_randint", 600, 100)]
    md = {DEV: {
        "%while.1 = (f32[4]) while()": [{"tf_op": "jit(fl_round)/fl.train/while:"}],
        "%fusion.2 = f32[4] fusion()": [{"tf_op": "jit(fl_round)/fl.train/mul:"}],
        "%convolution.3 = f32[4] convolution()": [
            {"tf_op": "jit(fl_round)/transpose(jvp(vmap(fl.train)))/conv:"}],
        "%while.4 = (f32[64,8]) while()": [{"shape_with_layout": "(f32[64,8])"}],
        "%dynamic-update-slice.5 = f32[64,8] dynamic-update-slice()": [
            {"shape_with_layout": "f32[64,8]"}],
        "%stoch_quant_pack_2d.6 = u8[2,128] custom-call()": [
            {"tf_op": "jit(fl_round)/fl.compress/jit(stoch_quant_pack_2d)/pallas_call:"}],
        "%gather.7 = f32[4] gather()": [{"tf_op": "jit(randint)/gather:"}],
    }}
    red = phases.reduce_phases(_profile(ops, modules), md, chips=1)
    s = {k: v * 1e9 for k, v in red.phase_s.items()}
    # the training loop's 200 less its 80 + 90 of body, which is training
    # too; the state loop's 100 less its 60 of body, both unscoped
    assert s == pytest.approx({
        "fl.train": 30 + 80 + 90, "unscoped": 40 + 60,
        "fl.compress": 50, "other": 40 + 30})
    assert sum(s.values()) == pytest.approx(red.ops_busy_s * 1e9)
    assert [(lab, shape) for lab, shape, _ in red.unscoped_ops] == [
        ("dynamic-update-slice", "f32[64,8]"), ("while", "(f32[64,8])")]
    bd = red.breakdown()
    assert bd["device_phases"][0] == ["fl.train", pytest.approx(200e-9)]
    assert bd["unscoped_ops"][0][2] == pytest.approx(60e-9)


def test_ops_outside_the_window_or_any_program_count_as_other():
    ops = [("%a.1 = f32[4] add()", 50, 100), ("%b.2 = f32[4] mul()", 900, 200),
           ("%c.3 = f32[4] sub()", 400, 10)]
    md = {DEV: {"%a.1 = f32[4] add()": [{"tf_op": "jit(f)/fl.update/add:"}]}}
    red = phases.reduce_phases(_profile(ops, [("jit_f", 0, 200)], window=(100, 1000)),
                               md, chips=1)
    # [100, 150) of a, [900, 1000) of b; c runs in no program
    assert red.phase_s == pytest.approx({"fl.update": 50e-9, "other": 110e-9})


def test_one_name_in_two_scopes_is_refused():
    md = {DEV: {"%a.1 = f32[4] add()": [{"tf_op": "jit(f)/fl.train/add:"},
                                        {"tf_op": "jit(g)/fl.update/add:"}]}}
    with pytest.raises(ValueError, match="scopes"):
        phases.reduce_phases(
            _profile([("%a.1 = f32[4] add()", 10, 10)], [("jit_f", 0, 100)]),
            md, chips=1)
    with pytest.raises(ValueError):
        phases.reduce_phases(Profile([_profile([], []).planes[0]]), {}, chips=1)


def test_the_op_metadata_of_a_trace_recorded_on_the_chip():
    """The committed trace of two CNN rounds (before the program named its
    phases): every op's metadata is found by its event name, the Pallas
    compress op's name stack is the jit path, and self time summed over
    all ops is the union of their intervals."""
    path = str(DATA / "cnn_rounds_v5e.xplane.pb")
    md = phases.read_op_metadata(path)
    assert list(md) == ["/device:TPU:0"]
    dev = md["/device:TPU:0"]
    assert len(dev) == 539
    assert sum(len(v) for v in dev.values()) == 623
    assert sum(1 for v in dev.values() for s in v if "tf_op" in s) == 239
    sq = [v for k, v in dev.items() if k.startswith("%stoch_quant_pack_2d.1 = ")]
    assert len(sq) == 1
    assert sq[0][0]["tf_op"].startswith(SQ_TF_OP + "/")
    assert sq[0][0]["hlo_category"] == "custom-call"

    red = phases.reduce_file(path, chips=1)
    # no program of this trace holds a scope: everything is "other"
    assert set(red.phase_s) == {"other"}
    assert red.ops_busy_s == pytest.approx(0.016906209, rel=1e-6)
    assert sum(red.phase_s.values()) == pytest.approx(red.ops_busy_s, rel=1e-4)
    # the existing reduction's busy time adds the programs' intervals
    busy = tracing.reduce_file(path, chips=1, kernels={}).busy_s
    assert red.ops_busy_s == pytest.approx(busy, rel=3e-3)


def test_a_scoped_trace_recorded_on_the_chip():
    """Two rounds of the named program (64 clients, cohort 32, the Pallas
    wire; the second round evaluates), traced on one TPU v5e by
    ``tools/trace_phases.py --fixture``. The phases' self time adds up to
    the ops' busy union; the round's largest unscoped op is the loop's
    write of the (population, d) state, which no scope names; the kernels
    keep their jit names, so the existing reduction still finds them."""
    path = str(DATA / "cnn_rounds_scoped_v5e.xplane.pb")
    red = phases.reduce_file(path, chips=1)
    assert red.phase_s == pytest.approx({
        "fl.train": 0.010487193, "unscoped": 0.004171395,
        "fl.compress": 0.001330268, "other": 0.0012413,
        "fl.gather": 0.000385983, "fl.attack": 0.000152655,
        "fl.count": 6.171e-05, "fl.update": 2.3411e-05,
    }, rel=1e-6)
    assert sum(red.phase_s.values()) == pytest.approx(red.ops_busy_s, rel=1e-4)
    label, shape, seconds = red.unscoped_ops[0]
    assert (label, shape) == ("dynamic-update-slice", "f32[64,206874]{1,0:T(8,128)}")
    assert seconds == pytest.approx(0.001441429, rel=1e-6)

    sq = [v[0]["tf_op"] for k, v in phases.read_op_metadata(path)["/device:TPU:0"].items()
          if k.startswith("%stoch_quant_pack_2d.1 = ")]
    assert sq == [("jit(fl_round)/fl.compress/fl.compress/jit(stoch_quant_compress_batch)/"
                   "vmap(jit(stoch_quant_compress))/jit(stoch_quant_pack_2d)/pallas_call:")]
    kernels = {"prox_sgd": "prox_sgd_2d", "stoch_quant": "stoch_quant_pack_2d",
               "bit_count": "bit_count_2d"}
    ops = tracing.reduce_file(path, chips=1, kernels=kernels)
    assert ops.complete
    assert ops.kernel_s == {
        "prox_sgd": pytest.approx(2.92729e-4),
        "stoch_quant": pytest.approx(5.40861e-4),
        "bit_count": pytest.approx(2.2532e-5),
    }
