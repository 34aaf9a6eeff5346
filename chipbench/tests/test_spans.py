"""Device idle time inside the program's host spans, and the readers of
the per-layer metrics built on it."""

import os

import pytest

from chipbench import harness, spans
from chipbench import trace as tr

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "serve_decode_spans.json.gz")
DEV, DEV1, HOST = "/device:TPU:0", "/device:TPU:1", "/host:CPU"


def ev(plane, line, name, start, dur):
    return tr.Event(plane, line, name, start, dur)


def window(*events, lo=1.0, hi=9.0):
    return tr.Trace([ev(HOST, "python3", tr.WINDOW, lo, hi - lo), *events])


def test_idle_inside_nested_spans_is_counted_once():
    # device busy [0, 3] and [6, 10]: idle [3, 6] inside the window [1, 9]
    t = window(
        ev(DEV, tr.OPS, "fusion.1", 0.0, 3.0),
        ev(DEV, tr.OPS, "fusion.2", 6.0, 4.0),
        ev(HOST, "python3", "serve.fill", 2.0, 3.0),      # [2, 5]
        ev(HOST, "python3", "serve.prefill", 2.5, 2.0),   # nested in fill
        ev(HOST, "python3", "serve.emit", 5.5, 1.0),      # [5.5, 6.5]
    )
    assert spans.idle_in_spans(t, ("serve.fill",)) == pytest.approx(2.0)
    assert spans.idle_in_spans(t, ("serve.fill", "serve.prefill")) == \
        pytest.approx(2.0)
    assert spans.idle_in_spans(t, ("serve.emit",)) == pytest.approx(0.5)
    assert spans.idle_share_in_spans(t, ("serve.fill", "serve.emit")) == \
        pytest.approx(100.0 * 2.5 / 8.0)


def test_spans_overlapping_busy_time_count_only_the_idle_part():
    # busy [2, 4]; a span [3, 7] overlaps the busy end and runs on idle
    t = window(ev(DEV, tr.OPS, "fusion.1", 2.0, 2.0),
               ev(HOST, "python3", "serve.sync", 3.0, 4.0))
    assert spans.idle_in_spans(t, ("serve.sync",)) == pytest.approx(3.0)


def test_spans_are_cut_by_the_window():
    # nothing runs on the device in [1, 9]; spans reach past both ends
    t = window(ev(DEV, tr.OPS, "fusion.1", 0.0, 0.5),
               ev(HOST, "python3", "train.input", 0.0, 2.0),   # [1, 2] in
               ev(HOST, "python3", "train.input", 8.0, 3.0),   # [8, 9] in
               ev(HOST, "python3", "train.input", 9.5, 1.0))   # outside
    assert spans.idle_in_spans(t, ("train.input",)) == pytest.approx(2.0)
    assert spans.idle_share_in_spans(t, ("train.input",)) == \
        pytest.approx(25.0)


def test_idle_is_averaged_over_devices():
    t = window(ev(DEV, tr.OPS, "fusion.1", 1.0, 8.0),      # never idle
               ev(DEV1, tr.OPS, "fusion.1", 1.0, 4.0),     # idle [5, 9]
               ev(HOST, "python3", "serve.sync", 4.0, 2.0))
    assert spans.idle_in_spans(t, ("serve.sync",)) == pytest.approx(0.5)


def test_no_reading_without_a_device_or_a_span():
    host_only = window(ev(HOST, "python3", "serve.sync", 2.0, 1.0))
    assert spans.idle_in_spans(host_only, ("serve.sync",)) is None
    # a program without the spans (and a device line) reads nothing
    no_span = window(ev(DEV, tr.OPS, "fusion.1", 2.0, 1.0),
                     ev(HOST, "python3", "$array.py:631 _value", 3.0, 1.0))
    assert spans.idle_share_in_spans(no_span, ("serve.sync",)) is None
    assert spans.idle_share_in_spans(None, ("serve.sync",)) is None


def test_intersect():
    assert spans.intersect([(0, 4), (6, 9)], [(1, 2), (3, 7), (8, 10)]) == \
        [(1, 2), (3, 4), (6, 7), (8, 9)]
    assert spans.intersect([(0, 1)], []) == []


READERS = {
    "device_idle.serve.sync": ("serve.sync",),
    "device_idle.serve.loop": ("serve.fill", "serve.dispatch", "serve.emit"),
    "device_idle.train.input": ("train.input",),
}


@pytest.mark.parametrize("metric", sorted(READERS))
def test_reader_reads_its_spans(metric):
    reader = harness.Bench(ROOT).reader(metric)
    names = READERS[metric]
    # each span 1 s long on an idle device, one more span of another name
    events = [ev(DEV, tr.OPS, "fusion.1", 1.0, 1.0),
              ev(HOST, "python3", "other.span", 8.0, 1.0)]
    events += [ev(HOST, "python3", n, 2.0 + 2 * i, 1.0)
               for i, n in enumerate(names)]
    t = window(*events)
    assert reader.read({"trace": t}) == pytest.approx(
        100.0 * len(names) / 8.0)
    # a parent without the spans, and a run with no trace: no reading
    assert reader.read({"trace": window(events[0], events[1])}) is None
    assert reader.read({}) is None


def test_recorded_chip_trace_puts_the_idle_time_on_spans():
    """A piece of a traced serve-decode run on a TPU v5e chip, with the
    server loop's spans."""
    t = tr.Trace(tr.read_dump(FIXTURE))
    assert t.devices == [DEV]
    idle = t.window_s * t.idle_share()
    sync = spans.idle_in_spans(t, ("serve.sync",))
    loop = spans.idle_in_spans(t, ("serve.fill", "serve.dispatch",
                                   "serve.emit"))
    # the two are disjoint parts of the idle time and cover nearly all
    assert sync > loop > 0
    assert 0.9 * idle <= sync + loop <= idle + 1e-9
    # one sync per decode step, inside its step
    steps = [e for e in t.events if e.name == "serve.step"]
    syncs = [e for e in t.events if e.name == "serve.sync"]
    assert len(steps) == len(syncs) == len(t.module_durations(
        "jit_decode_step")) == 4
    for st, sy in zip(steps, syncs):
        assert st.start <= sy.start and sy.end <= st.end
