"""The trace reduction, on event lists: a synthetic one with known
answers, and a small trace recorded on the chip (``data/``)."""

import json

import pytest

from conftest import BENCH
from harness import spec, trace

KERNEL = ('%body.1 = s32[4,8,8]{2,1,0} custom-call(s32[4,8,8]{2,1,0} %a, '
          's32[8,8]{1,0} %m), custom_call_target="tpu_custom_call"')
SYN = {"device": {"/device:TPU:0": [
    ["%fusion.1 = s32[8]{0} fusion(s32[8]{0} %x), kind=kLoop", 0, 100],
    [KERNEL, 50, 100],     # overlaps: union 0..150
    ["%copy.2 = s32[8]{0} copy(s32[8]{0} %y)", 300, 50],
    [KERNEL, 400, 100],
]}, "host": [["bench.fetch", 120, 300], ["bench.dispatch", 360, 20]]}


def test_union_and_busy():
    assert trace.union_ns([(0, 10), (5, 10), (20, 5)]) == 20
    assert trace.busy_s(SYN) == pytest.approx(300e-9)


def test_gaps_named_by_host():
    gaps = dict(trace.idle_gaps(SYN))
    assert gaps["bench.fetch"] == pytest.approx(150e-9)   # 150..300
    assert gaps["bench.dispatch"] == pytest.approx(50e-9)   # 350..400


def test_readers_on_synthetic():
    ctx = {"trace": SYN, "window_s": 1000e-9}
    idle = spec.reader("device_idle.tick", BENCH).read(ctx)
    assert idle == pytest.approx(70.0)


def test_readers_silent_without_trace():
    for name in ("device_idle.tick", "device_idle.solve"):
        assert spec.reader(name, BENCH).read({"trace": None}) is None


def test_recorded_chip_trace():
    """160 op events of one wave tick (seed 1, tick 30) recorded on a
    v5e in PR 22: op names are HLO text, the replan ``while`` spans its
    children, the sweeps are ``tpu_custom_call`` ops on an (R=4, 1024,
    1024) batch against the 1024^2 mask."""
    ev = json.loads((BENCH / "tests" / "data" / "trace_small.json")
                    .read_text())
    calls = [e for _, e, p in trace.leaf_ops(ev) if p and p[2] == "custom-call"]
    assert len(calls) == 12
    # the while container is busy time; its children are not op totals
    assert trace.busy_s(ev) == pytest.approx(0.112303946)
    names = [n for n, _ in trace.top_ops(ev)]
    assert names[0] == "fusion.260 fusion"
    assert not any(n.split()[1] == "while" for n in names)
