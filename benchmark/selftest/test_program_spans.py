"""The reduction from a trace to the program's spans and scopes
(``harness/program_spans.py``): against a hand-built trace, against a
file encoded here field by field, and against a small trace recorded on
the chip in PR 24 (``fixtures/train_steps.xplane.pb.gz``), which
``jax.profiler.ProfileData`` reads independently."""

import json
import os
import struct

import pytest

from benchmark.harness import program_spans as ps
from benchmark.harness.trace_reduce import SLICE_SPAN

US = 1e3  # ns
FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fixtures")
TRAIN = "jit_train_step"
FWD = "jvp(forward)/BiGRU/recurrence_fwd/while/body"
BWD = "transpose(jvp(forward))/BiGRU/recurrence_fwd/while/body"


def hand_built():
    """Slice 1000..2000 us, opened on a thread of its own as the training
    driver does.  The step thread runs three steps: one that straddles
    the slice's start (its ``train`` began at 900), one whole, one whose
    fold straddles the end; ``next_batch`` holds the input pipeline's two
    spans; 1900..1950 is under no span.  The device runs two train steps
    and one eager add."""
    step = [
        ("train", 900 * US, 150 * US),                # 900..1050: before
        ("train_fold", 1050 * US, 150 * US),          # 1050..1200
        ("train_next_batch", 1200 * US, 100 * US),    # 1200..1300
        ("input_compose", 1210 * US, 30 * US),        # nested
        ("input_place", 1250 * US, 40 * US),          # nested
        ("train", 1300 * US, 300 * US),               # 1300..1600
        ("train_fold", 1600 * US, 200 * US),          # 1600..1800
        ("train_next_batch", 1800 * US, 10 * US),     # 1800..1810
        ("train", 1810 * US, 90 * US),                # 1810..1900
        ("train_fold", 1950 * US, 100 * US),          # 1950..2050: after
    ]
    other = [(SLICE_SPAN, 1000 * US, 1000 * US),
             ("input_compose", 1500 * US, 20 * US)]
    ops = [
        # step one: a while with no scope of its own holds two body ops
        ("while.1", 1320 * US, 100 * US, TRAIN, ""),
        ("fusion.1", 1330 * US, 30 * US, TRAIN, FWD),
        ("fusion.2", 1370 * US, 40 * US, TRAIN, FWD + "/closed_call"),
        ("fusion.3", 1420 * US, 20 * US, TRAIN, "optimizer"),
        ("broadcast.9.clone", 1440 * US, 10 * US, TRAIN, ""),
        # step two
        ("fusion.4", 1820 * US, 50 * US, TRAIN, BWD),
        ("add.1", 1960 * US, 10 * US, "jit_add", ""),
    ]
    return {"threads": {"3:python3": step, "7:python3": other},
            "ops": {"/device:TPU:0": ps.inherit_scopes(ops)}}


def test_spans_are_counted_by_where_they_start_on_the_step_thread():
    r = ps.reduce(hand_built())
    assert r["step_thread"] == "3:python3"
    assert r["window_s"] == pytest.approx(1e-3)
    spans = r["spans"]
    # the train that began at 900 is not of the slice; its fold is
    assert spans["train"]["count"] == 2
    assert spans["train"]["mean_s"] == pytest.approx(195e-6)
    assert spans["train_fold"]["count"] == 3
    # whole durations, the straddling fold's 100 us included
    assert spans["train_fold"]["total_s"] == pytest.approx(450e-6)
    assert spans["train_next_batch"]["mean_s"] == pytest.approx(55e-6)
    assert spans["input_place"]["count"] == 1
    # the other thread's spans are reported apart, the slice span nowhere
    assert r["other_threads"] == {"7:python3": {"input_compose": {
        "count": 1, "total_s": pytest.approx(20e-6),
        "mean_s": pytest.approx(20e-6)}}}


def test_uncovered_time_is_the_step_threads_remainder_in_the_slice():
    r = ps.reduce(hand_built())
    # covered inside 1000..2000: 1000-1900 (adjacent spans) and 1950-2000
    assert r["self_s"] == pytest.approx(50e-6)
    record = {"program_spans": r}
    assert ps.loop_self_us(record) == pytest.approx(25.0)  # over 2 trains
    assert ps.span_mean_us("train")(record) == pytest.approx(195.0)
    assert ps.span_mean_us("eval")(record) is None


def test_idle_gaps_go_to_the_step_threads_innermost_program_span():
    r = ps.reduce(hand_built())
    idle = r["idle_by_span"]
    # busy: 1320-1420, 1420-1450, 1820-1870, 1960-1970
    assert r["busy_s"] == pytest.approx(190e-6)
    assert sum(idle.values()) == pytest.approx(810e-6)
    assert idle["train"] == pytest.approx(
        50e-6 + 20e-6 + 150e-6 + 10e-6 + 30e-6)  # 1000-1050, 1300-1320, ...
    assert idle["train_fold"] == pytest.approx(150e-6 + 200e-6 + 40e-6)
    assert idle["input_compose"] == pytest.approx(30e-6)  # not the other
    assert idle["input_place"] == pytest.approx(40e-6)    # thread's
    assert idle["train_next_batch"] == pytest.approx(30e-6 + 10e-6)
    assert idle[ps.NO_SPAN] == pytest.approx(50e-6)


def test_busy_time_by_program_and_scope_counts_each_instant_once():
    r = ps.reduce(hand_built())
    assert r["busy_by_module"] == {
        TRAIN: pytest.approx(180e-6), "jit_add": pytest.approx(10e-6)}
    scope = r["busy_by_scope"]
    # the while's own 30 us go where its body's operations are
    assert scope[(TRAIN, FWD, "")] == pytest.approx(30e-6 + 30e-6)
    assert scope[(TRAIN, FWD + "/closed_call", "")] == pytest.approx(40e-6)
    assert scope[(TRAIN, BWD, "")] == pytest.approx(50e-6)
    assert scope[(TRAIN, ps.NO_SCOPE, "broadcast.9.clone")] == \
        pytest.approx(10e-6)
    assert scope[("jit_add", ps.NO_SCOPE, "add.1")] == pytest.approx(10e-6)
    # (30+30+40+50) of 180 us under a recurrence_ scope
    assert ps.recurrence_dev_share({"program_spans": r}) == \
        pytest.approx(100.0 * 150 / 180)


def test_a_program_without_the_vocabulary_reads_as_nothing():
    """The parent commit: ``train`` annotations only, ``jit_step_fn``."""
    old = {"threads": {"3:python3": [("train", 1300 * US, 300 * US)],
                       "7:python3": [(SLICE_SPAN, 1000 * US, 1000 * US)]},
           "ops": {"/device:TPU:0": [
               ("fusion.1", 1330 * US, 30 * US, "jit_step_fn", "")]}}
    record = {"program_spans": ps.reduce(old)}
    assert ps.span_mean_us("train")(record) == pytest.approx(300.0)
    assert ps.span_mean_us("train_fold")(record) is None
    assert ps.span_mean_us("train_next_batch")(record) is None
    assert ps.loop_self_us(record) is None
    assert ps.recurrence_dev_share(record) is None
    # and no trace at all
    assert ps.reduce({"threads": {}, "ops": {}}) is None
    for read in (ps.loop_self_us, ps.recurrence_dev_share,
                 ps.span_mean_us("train")):
        assert read({"tracer": None}) is None


@pytest.mark.parametrize("tf_op,scope", [
    ("jit(train_step)/jvp(forward)/BiGRU/head/reduce_max:",
     "jvp(forward)/BiGRU/head"),
    ("jit(train_step)/jit(main)/optimizer/jit(_where)/select_n:",
     "optimizer/jit(_where)"),
    ("jit(step)/select_n:", ""),                 # traced under no scope
    ("state.params['linear']['kernel']", ""),    # an argument's name
    ("", ""),                                    # the compiler's own
])
def test_scope_of(tf_op, scope):
    assert ps.scope_of(tf_op) == scope


def test_scope_root_takes_the_gradients_transforms_off():
    assert ps.scope_root("transpose(jvp(forward))/BiGRU/head") == "forward"
    assert ps.scope_root("jvp(loss)") == "loss"
    assert ps.scope_root("optimizer/jit(_where)") == "optimizer"


# -- the wire format -------------------------------------------------------------

def _varint(v):
    v &= (1 << 64) - 1
    out = bytearray()
    while True:
        b = v & 0x7F
        v >>= 7
        out.append(b | (0x80 if v else 0))
        if not v:
            return bytes(out)


def _f(number, value):
    if isinstance(value, int):
        return _varint(number << 3) + _varint(value)
    if isinstance(value, float):
        return _varint(number << 3 | 1) + struct.pack("<d", value)
    if isinstance(value, str):
        value = value.encode()
    return _varint(number << 3 | 2) + _varint(len(value)) + value


def _entry(key, message):
    return _f(1, key) + _f(2, message)


def encoded_space():
    """A device plane and a host plane, encoded by hand: two operations
    of one program (one with a ``tf_op``), one module event, and a host
    line with a program span, a slice span and an event of no interest."""
    stat_meta = (_f(5, _entry(1, _f(1, 1) + _f(2, "tf_op")))
                 + _f(5, _entry(2, _f(1, 2) + _f(2, "program_id")))
                 + _f(5, _entry(3, _f(1, 3) + _f(2, "flops"))))
    pid = 15227954484073239426  # needs all 64 bits
    op1 = (_f(1, 10) + _f(2, "%fusion.1 = f32[8]{0} fusion(f32[8] %p)")
           + _f(5, _f(1, 3) + _f(4, 24))
           + _f(5, _f(1, 2) + _f(3, pid))
           + _f(5, _f(1, 1) + _f(5, "jit(train_step)/optimizer/add:")))
    op2 = (_f(1, 11) + _f(2, "%while.2 = (s32[]) while(...)")
           + _f(5, _f(1, 2) + _f(3, pid)))
    module = _f(1, 12) + _f(2, f"jit_train_step({pid})")
    device = (
        _f(2, "/device:TPU:0")
        + _f(3, _f(2, "XLA Modules") + _f(3, 5_000)
             + _f(4, _f(1, 12) + _f(2, 0) + _f(3, 9_000_000)))
        + _f(3, _f(2, "XLA Ops") + _f(3, 5_000)
             + _f(4, _f(1, 10) + _f(2, 1_000_000) + _f(3, 2_500_000)
                  + _f(4, _f(1, 3) + _f(2, 1.5)))     # an event's own stat
             + _f(4, _f(1, 11) + _f(2, 4_000_000) + _f(3, 3_000_000)))
        + _f(3, _f(2, "Steps") + _f(3, 5_000)
             + _f(4, _f(1, 12) + _f(2, 0) + _f(3, 9_000_000)))
        + _f(4, _entry(10, op1)) + _f(4, _entry(11, op2))
        + _f(4, _entry(12, module)) + stat_meta)
    host = (
        _f(2, "/host:CPU")
        + _f(3, _f(2, "tf_worker") + _f(3, 4_000)
             + _f(4, _f(1, 3) + _f(2, 0) + _f(3, 1_000_000)))
        + _f(3, _f(2, "python3") + _f(3, 4_000)
             + _f(4, _f(1, 1) + _f(2, 0) + _f(3, 12_000_000))
             + _f(4, _f(1, 2) + _f(2, 2_000_000) + _f(3, 500_000))
             + _f(4, _f(1, 3) + _f(2, 3_000_000) + _f(3, 1_000)))
        + _f(4, _entry(1, _f(1, 1) + _f(2, SLICE_SPAN)))
        + _f(4, _entry(2, _f(1, 2) + _f(2, "train_fold")))
        + _f(4, _entry(3, _f(1, 3) + _f(2, "PjitFunction(add)"))))
    return _f(1, device) + _f(1, host) + _f(4, "hostname")


def test_load_reads_the_files_wire_format(tmp_path):
    path = tmp_path / "hand.xplane.pb"
    path.write_bytes(encoded_space())
    trace = ps.load(str(path))
    # the host line that holds nothing of the program's is dropped
    assert trace["threads"] == {"1:python3": [
        (SLICE_SPAN, 4_000.0, 12_000.0), ("train_fold", 6_000.0, 500.0)]}
    assert trace["ops"] == {"/device:TPU:0": [
        ("fusion.1", 6_000.0, 2_500.0, "jit_train_step", "optimizer"),
        ("while.2", 9_000.0, 3_000.0, "jit_train_step", "")]}


# -- the trace recorded on the chip ------------------------------------------------

CHIP = os.path.join(FIXTURES, "train_steps.xplane.pb.gz")


@pytest.fixture(scope="module")
def chip():
    with open(os.path.join(FIXTURES, "train_steps.expected.json")) as fh:
        return ps.load(CHIP), json.load(fh)


def test_chip_trace_agrees_with_profile_data(chip):
    """Every span and operation this reader keeps, ``ProfileData`` shows
    at the same nanosecond under the same name."""
    import gzip

    from jax.profiler import ProfileData

    trace, _ = chip
    wanted = set(ps.PROGRAM_SPANS) | {SLICE_SPAN}
    host, ops = {}, []
    with gzip.open(CHIP, "rb") as fh:
        data = ProfileData.from_serialized_xspace(fh.read())
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for i, line in enumerate(plane.lines):
                spans = [(e.name, e.start_ns, e.duration_ns)
                         for e in line.events if e.name in wanted]
                if spans:
                    host[f"{i}:{line.name}"] = spans
        elif plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops = [(ps.short_hlo_name(e.name), e.start_ns,
                            e.duration_ns) for e in line.events]
    assert set(host) == set(trace["threads"])
    for thread, spans in host.items():
        mine = trace["threads"][thread]
        assert [s[0] for s in mine] == [s[0] for s in spans]
        for a, b in zip(mine, spans):
            assert a[1] == pytest.approx(b[1], abs=1.0)
            assert a[2] == pytest.approx(b[2], abs=1.0)
    (mine,) = trace["ops"].values()
    assert [o[0] for o in mine] == [o[0] for o in ops]
    for a, b in zip(mine, ops):
        assert a[1] == pytest.approx(b[1], abs=1.0)
        assert a[2] == pytest.approx(b[2], abs=1.0)


def test_chip_trace_counts_and_scopes(chip):
    trace, want = chip
    r = ps.reduce(trace)
    assert {n: row["count"] for n, row in r["spans"].items()} == \
        want["span_counts"]
    assert sorted(r["busy_by_module"]) == want["modules"]
    roots = {ps.scope_root(scope)
             for (module, scope, _), _ in r["busy_by_scope"].items()
             if module == "jit_train_step" and scope != ps.NO_SCOPE}
    assert roots == set(want["train_step_scope_roots"])
    parts = {part for (module, scope, _) in r["busy_by_scope"]
             if module == "jit_train_step" for part in scope.split("/")}
    assert set(want["train_step_scopes_hold"]) <= parts
    # every step's parts tile it: what the loop leaves uncovered is small
    covered = sum(row["total_s"] for name, row in r["spans"].items()
                  if not name.startswith("input_"))
    assert covered + r["self_s"] == pytest.approx(r["window_s"], rel=1e-6)
    assert sum(r["idle_by_span"].values()) + r["busy_s"] == \
        pytest.approx(r["window_s"], rel=1e-9)
    share = ps.recurrence_dev_share({"program_spans": r})
    assert want["recurrence_share_between"][0] < share < \
        want["recurrence_share_between"][1]
