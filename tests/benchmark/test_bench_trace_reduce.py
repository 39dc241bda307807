"""The trace reduction: interval arithmetic on hand-made planes, and the
small trace recorded on the chip (``tests/benchmark/data``)."""

import json
import os

import pytest

from benchmark.harness import stats, trace_reduce as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def test_union_gaps_intersect():
    merged = tr.union([(0.0, 1.0), (0.5, 2.0), (3.0, 4.0), (4.0, 4.0)])
    assert merged == [(0.0, 2.0), (3.0, 4.0)]
    assert tr.total(merged) == pytest.approx(3.0)
    assert tr.gaps(merged, -1.0, 5.0) == [(-1.0, 0.0), (2.0, 3.0), (4.0, 5.0)]
    assert tr.clip(merged, 1.5, 3.5) == [(1.5, 2.0), (3.0, 3.5)]
    assert tr.intersect([(0.0, 2.0), (3.0, 4.0)], [(1.0, 3.5)]) == [
        (1.0, 2.0), (3.0, 3.5)]


def _plane():
    ops = [("fusion.1", 0.0, 1.0), ("while", 2.0, 5.0), ("fusion.2", 2.5, 3.0),
           ("copy.3", 6.0, 6.5), ("fusion.1", 7.0, 8.0)]
    mods = [("jit_a", 0.0, 1.0), ("jit_b", 2.0, 6.5), ("jit_a", 7.0, 8.0)]
    return tr.Reduced([tr.DevicePlane("/device:TPU:0", ops, mods)], [], 0.0, 10.0)


def test_busy_ops_and_labels_on_a_hand_made_plane():
    red = _plane()
    assert tr.busy_seconds(red) == pytest.approx(1.0 + 3.0 + 0.5 + 1.0)
    tot = tr.op_totals(red)
    assert tot["fusion.1"] == pytest.approx(2.0)
    assert tot["while"] == pytest.approx(3.0)
    assert "fusion.2" not in tot  # nested in the while: counted once
    assert tr.top_ops(red, 2) == [["while", pytest.approx(3.0)],
                                  ["fusion.1", pytest.approx(2.0)]]
    idle = tr.gaps(tr.busy(red.devices[0], 0.0, 10.0), 0.0, 10.0)
    assert idle == [(1.0, 2.0), (5.0, 6.0), (6.5, 7.0), (8.0, 10.0)]
    spans = [("request", 0.0, 10.0), ("repack", 8.2, 9.9), ("decode_step", 0.9, 2.1)]
    labelled = tr.label_gaps(idle, spans, 2)
    assert labelled[0] == ["repack", pytest.approx(2.0)]
    assert labelled[1][0] == "decode_step"
    assert [n for n, _, _ in tr.modules_in(red.devices[0], 1.5, 7.0)] == ["jit_b"]


def test_percentile_and_spread():
    xs = list(range(1, 101))
    assert stats.percentile(xs, 50) == 50 and stats.percentile(xs, 95) == 95
    assert stats.percentile([7.0], 99) == 7.0
    assert stats.iqr_share([10, 10, 10, 10, 10, 10]) == 0.0


def test_recorded_chip_trace():
    """Three launches of one program with a 50 ms sleep after each."""
    path = os.path.join(DATA, "small.xplane.pb")
    with open(os.path.join(DATA, "small.summary.json")) as f:
        summary = json.load(f)
    red = tr.load(path, host_names=(tr.ANCHOR, "launch_"))
    assert len(red.devices) == 1
    plane = red.devices[0]
    assert len(plane.modules) == 3
    off = tr.to_trace_clock(red, summary["anchor_monotonic"])
    # Each launch's device work lies inside the host interval that waited
    # for it, once the host clock is moved onto the trace's.
    for (_, s, e), (h0, h1) in zip(plane.modules, summary["marks"]):
        assert h0 + off - 2e-3 <= s and e <= h1 + off + 2e-3
    busy = tr.busy(plane, red.t0, red.t1)
    assert 0 < tr.total(busy) < 0.01
    idle = tr.gaps(busy, red.t0, red.t1)
    long_gaps = [g for g in idle if g[1] - g[0] > 0.045]
    assert len(long_gaps) == 2  # the sleeps between the three launches
    launches = [(n, s, e) for n, s, e in red.host if n.startswith("launch_")]
    assert len(launches) == 3
    assert tr.label_gaps(idle, launches, 1)[0][1] > 0.045
    tot = tr.op_totals(red)
    assert sum(tot.values()) == pytest.approx(tr.total(busy), rel=0.2)
    assert tr.busy_seconds(red) == pytest.approx(tr.total(busy))
