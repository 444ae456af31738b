"""The reduction from an ``.xplane.pb`` to numbers, on a small trace
recorded on a TPU v5e by ``record_trace.py`` (a toy three-replica group:
3 steps and 2 bursts between the two anchors) and kept, gzipped, beside
this file.
"""

import json
import os

import pytest

from perfbench.harness import trace as tr

HERE = os.path.dirname(os.path.abspath(__file__))
TRACE = os.path.join(HERE, "small.xplane.pb.gz")


@pytest.fixture(scope="module")
def reduced():
    with open(os.path.join(HERE, "small.anchors.json")) as f:
        anchors = json.load(f)
    # host phases on the monotonic clock: one covers the window's first
    # half, nothing covers the second
    mid = (anchors[tr.OPEN] + anchors[tr.CLOSE]) / 2
    phases = [("first_half", anchors[tr.OPEN], mid)]
    return anchors, tr.reduce_trace(TRACE, anchors, phases)


def test_window_is_between_the_anchors(reduced):
    anchors, red = reduced
    want = anchors[tr.CLOSE] - anchors[tr.OPEN]
    assert red["window_s"] == pytest.approx(want, abs=2e-3)


def test_device_busy_time(reduced):
    _, red = reduced
    assert list(red["devices"]) == ["/device:TPU:0"]
    dev = red["devices"]["/device:TPU:0"]
    assert 0 < red["busy_s"] < red["window_s"]
    assert red["busy_s"] == dev["busy_s"]
    # busy is a union: never more than the sum of the operations
    assert dev["busy_s"] <= sum(b - a for a, b, _ in dev["events"]) + 1e-9
    # every event lies inside the window
    assert all(0 <= a <= b <= red["window_s"] for a, b, _ in dev["events"])
    # gaps and busy time tile the window (gaps under MIN_GAP_S are not kept)
    gap = sum(b - a for a, b in dev["gaps"])
    assert gap + dev["busy_s"] <= red["window_s"] + 1e-9
    assert gap + dev["busy_s"] >= 0.9 * red["window_s"]


def test_programs_and_operations_are_named(reduced):
    _, red = reduced
    mods = red["module_seconds"]
    assert {"jit_replica_step", "jit_burst"} <= set(mods)
    dev = red["devices"]["/device:TPU:0"]
    assert len([m for m in dev["modules"] if m[2] == "jit_replica_step"]) == 3
    assert len([m for m in dev["modules"] if m[2] == "jit_burst"]) == 2
    assert 0 < len(red["device_ops"]) <= 10
    for name, secs in red["device_ops"]:
        module, _, op = name.partition("/")
        assert module in mods and secs > 0
        assert " = " not in op and len(name) < 200     # shortened HLO text
        assert not op.startswith(tr.CONTAINERS)
    # operations run inside their programs: never more time than them
    step_ops = sum(b - a for a, b, n in dev["events"]
                   if n.startswith("jit_replica_step/"))
    assert step_ops <= mods["jit_replica_step"] * 1.001


def test_idle_gaps_are_named_by_the_phase_that_covers_them(reduced):
    _, red = reduced
    named = dict(red["idle_gaps"])
    assert set(named) <= {"first_half", "none"}
    assert named["first_half"] > 0 and named["none"] > 0
    assert sum(named.values()) == pytest.approx(
        sum(b - a for a, b in
            red["devices"]["/device:TPU:0"]["gaps"]))


class _Fake:
    def __init__(self, **kw):
        self.__dict__.update(kw)


def test_host_events_are_device_time_only_in_rehearsal():
    """A capture that lost its device planes is an error on the chip;
    the host-as-device stand-in is the CPU rehearsal's alone."""
    ev = _Fake(name="thunk", start_ns=10, duration_ns=5,
               stats=[("hlo_op", "copy.1"), ("hlo_module", "jit_f")])
    planes = [_Fake(name="/host:CPU",
                    lines=[_Fake(name="thread", events=[ev])])]
    with pytest.raises(RuntimeError, match="no /device:TPU plane"):
        tr._device_events(planes)
    found = tr._device_events(planes, rehearsal=True)
    assert list(found) == ["host-as-device"]
    assert found["host-as-device"]["events"] == [(10.0, 15.0, "jit_f/copy.1")]


def test_short_op():
    assert tr.short_op(
        "%copy.2 = s32[3,16,8]{1,2,0:T(8,128)S(1)} copy(s32[3,16,8] %x)"
    ) == ("copy.2 s32[3,16,8]", "copy")
    assert tr.short_op(
        "%while.3 = (s32[]{:T(128)}, s32[3]{0:T(8,128)}) while((s32[]) %t)"
    ) == ("while.3 (tuple)", "while")
    assert tr.short_op("not hlo") == ("not hlo", "")
