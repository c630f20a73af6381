"""The trace reduction, on a small trace recorded on one TPU v5e chip and
on hand-made events.

The recorded trace (``data/small_trace.xplane.pb``) holds three calls of
one jitted function, each inside a ``bench:step`` annotation and followed
by a ``bench:host`` annotation around a 2 ms sleep; the harness timed the
traced window at 0.010125378999987333 s.  Its device ops, by hand from
the raw events (ns): per call a copy-start, a copy-done and a fusion of
13 + 2 + 1825, 14 + 3 + 1824 and 13 + 3 + 1825, none overlapping.
"""
import os

import pytest

from chipbench import trace as T

DATA = os.path.join(os.path.dirname(__file__), "data")
WINDOW_S = 0.010125378999987333


@pytest.fixture(scope="module")
def recorded():
    return T.reduce_dir(DATA, WINDOW_S)


def test_recorded_busy_and_window(recorded):
    busy_s, window_s, _ = recorded
    assert busy_s == pytest.approx(5522e-9, rel=1e-9)
    assert window_s == WINDOW_S
    assert 1 - busy_s / window_s == pytest.approx(0.99945463, abs=1e-8)


def test_recorded_device_ops(recorded):
    ops = dict(recorded[2]["device_ops"])
    assert len(ops) == 3
    fusion = [k for k in ops if k.startswith("%fusion")]
    assert len(fusion) == 1 and "{" not in fusion[0]
    assert ops[fusion[0]] == pytest.approx(5474e-9, rel=1e-9)
    assert sum(ops.values()) == pytest.approx(5522e-9, rel=1e-9)


def test_recorded_idle_by_host_annotation(recorded):
    idle = dict(recorded[2]["idle_gaps"])
    # the three bench:host sleeps and the three bench:step calls, by hand
    # from the annotations' durations
    assert idle["host"] == pytest.approx((2686410 + 2456860 + 2660070)
                                         * 1e-9, rel=1e-3)
    assert idle["step"] == pytest.approx((878000 + 791900 + 615870)
                                         * 1e-9, rel=1e-3)
    assert set(idle) == {"host", "step", "none"}


def test_union_and_gaps_by_hand():
    iv = [(0.0, 1.0), (0.5, 2.0), (3.0, 4.0)]
    assert T.union_length(iv) == 3.0
    assert T.gaps(iv, -1.0, 5.0) == [(-1.0, 0.0), (2.0, 3.0), (4.0, 5.0)]


def test_reduce_events_by_hand():
    events = {
        "devices": {"/device:TPU:0": [(1.0, 2.0, "a"), (4.0, 5.0, "b"),
                                      (4.5, 5.5, "a")]},
        "bench": [(0.0, 3.0, "build"), (3.0, 6.0, "pipeline")],
        "host": [(2.0, 3.0, "PjitFunction(f)")],
    }
    busy, window, br = T.reduce_events(events, 6.0)
    assert busy == 2.5                  # [1, 2] and [4, 5.5]
    assert window == 6.0
    assert dict(br["device_ops"]) == {"a": 2.0, "b": 1.0}
    assert dict(br["idle_gaps"]) == {"build": 1.0,
                                     "build/PjitFunction(f)": 1.0,
                                     "pipeline": 1.5}


def test_no_device_plane_is_an_error():
    with pytest.raises(ValueError):
        T.reduce_events({"devices": {}, "bench": [], "host": []}, 1.0)
