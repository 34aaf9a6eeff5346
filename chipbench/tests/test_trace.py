"""The reduction from trace events to metrics."""

import os

import pytest

from chipbench import trace as tr

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "serve_decode_trace.json.gz")
DEV, HOST = "/device:TPU:0", "/host:CPU"


def ev(plane, line, name, start, dur):
    return tr.Event(plane, line, name, start, dur)


def small():
    """A 10 s window: two modules, overlapping ops."""
    return tr.Trace([
        ev(HOST, "python", tr.WINDOW, 0.0, 10.0),
        ev(HOST, "python", "PjitFunction(step)", 0.5, 0.4),
        ev(HOST, "python", "pipeline.py:53 batch", 4.0, 3.0),
        ev(HOST, "python", "outer", 3.5, 4.0),
        ev(DEV, tr.MODULES, "jit_step(12)", 1.0, 3.0),
        ev(DEV, tr.MODULES, "jit_step(12)", 7.0, 2.0),
        ev(DEV, tr.MODULES, "jit_other(3)", 9.5, 1.0),   # runs past the end
        ev(DEV, tr.OPS, "fusion.1", 1.0, 2.0),
        ev(DEV, tr.OPS, "fusion.2", 2.5, 1.5),            # overlaps fusion.1
        ev(DEV, tr.OPS, "fusion.3", 3.5, 1.0),
        ev(DEV, tr.OPS, "fusion.1", 7.0, 2.0),
        ev(DEV, tr.OPS, "fusion.9", 9.5, 1.0),            # clipped to 0.5
    ])


def test_busy_is_the_union_clipped_to_the_window():
    t = small()
    # [1, 4.5] + [7, 9] + [9.5, 10] = 3.5 + 2 + 0.5
    assert t.window_s == 10.0
    assert t.busy_s == pytest.approx(6.0)
    assert t.idle_share() == pytest.approx(0.4)


def test_module_durations_keep_whole_calls_inside_the_window():
    t = small()
    assert t.module_durations("jit_step") == [3.0, 2.0]
    assert t.module_durations("jit_other") == []


def test_top_ops_and_gap_owners():
    t = small()
    assert t.top_ops(2) == [["fusion.1", 4.0], ["fusion.2", 1.5]]
    # gaps: [0, 1] (PjitFunction 0.4), [4.5, 7] (batch 2.5 beats outer),
    # [9, 9.5] (none)
    gaps = dict((k, v) for k, v in t.idle_gaps(10))
    assert gaps["pipeline.py:53 batch"] == pytest.approx(2.5)
    assert gaps["PjitFunction(step)"] == pytest.approx(1.0)
    assert gaps["host idle"] == pytest.approx(0.5)


def test_interval_helpers():
    assert tr.union([(3, 4), (0, 1), (0.5, 2)]) == [(0, 2), (3, 4)]
    assert tr.subtract([(0, 10)], [(1, 2), (5, 6)]) == [(0, 1), (2, 5),
                                                        (6, 10)]
    assert tr.clip([(0, 2), (3, 5)], 1, 4) == [(1, 2), (3, 4)]


def test_recorded_chip_trace():
    """A piece of a traced serve-decode run on a TPU v5e chip."""
    t = tr.Trace(tr.read_dump(FIXTURE))
    assert t.devices == [DEV]
    steps = t.module_durations("jit_decode_step")
    assert steps and all(0.001 < d < 0.2 for d in steps)
    assert 0.0 <= t.idle_share() < 1.0
    assert t.busy_s <= t.window_s
    # inside each call of the decode step the device is busy
    ops = tr.union((e.start, e.end) for e in t.events
                   if e.plane == DEV and e.line == tr.OPS)
    for e in t.events:
        if e.plane == DEV and e.line == tr.MODULES:
            assert tr.total(tr.clip(ops, e.start, e.end)) > 0.9 * e.dur
    top = t.top_ops(10)
    assert len(top) == 10 and top[0][0] == "%while.14 while"
    gaps = dict(t.idle_gaps(10))
    # the host waits for the step's tokens while the device is idle
    assert max(gaps, key=gaps.get) == "$array.py:631 _value"
