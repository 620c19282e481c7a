"""The trace reduction: on hand-made events, and on a small trace recorded
on a TPU v5e (``fixtures/tiny_engine.xplane.pb``, made by
``record_fixture.py``)."""
import pathlib

import pytest

from chipbench.lib import trace
from chipbench.lib.trace import Event

FIXTURE = pathlib.Path(__file__).parent / "fixtures" / "tiny_engine.xplane.pb"


def test_summary_of_hand_made_events():
    ms = 1e-3
    ops = [Event("fusion.1", 1 * ms, 3 * ms), Event("fusion.2", 2 * ms,
                                                     4 * ms),
           Event("copy.3", 6 * ms, 7 * ms), Event("fusion.1", 9 * ms,
                                                  12 * ms)]
    mods = [Event("jit__decode_segment(3)", 1 * ms, 4 * ms),
            Event("jit__prefill_one(7)", 6 * ms, 7 * ms),
            Event("jit__decode_segment(3)", 9 * ms, 12 * ms)]
    host = [Event(trace.WINDOW, 0.0, 10 * ms),
            Event("bench.step", 0.0, 10 * ms),
            Event("bench.submit", 4.2 * ms, 5.9 * ms),
            Event("device_get", 7.5 * ms, 8.9 * ms)]
    s = trace.summarize({"/device:TPU:0": {"XLA Ops": ops,
                                           "XLA Modules": mods}}, host)
    assert s.window_s == pytest.approx(10 * ms)
    # busy: [1,4] ∪ [6,7] ∪ [9,10] (clipped to the window) = 5 ms
    assert s.busy_s == pytest.approx(5 * ms)
    assert s.programs["_decode_segment"] == pytest.approx(4 * ms)
    assert s.programs["_prefill_one"] == pytest.approx(1 * ms)
    # the second segment runs past the window's end: not a whole call
    assert s.calls["_decode_segment"] == pytest.approx([1, 3 * ms])
    assert s.calls["_prefill_one"] == pytest.approx([1, 1 * ms])
    ops_ = dict(s.device_ops)
    assert ops_["fusion.1"] == pytest.approx(3 * ms)
    gaps = dict(s.idle_gaps)
    # [0,1] under bench.step only; [4,6] mid 5 in submit; [7,9] mid 8 in
    # device_get
    assert gaps["bench.step"] == pytest.approx(1 * ms)
    assert gaps["bench.submit"] == pytest.approx(2 * ms)
    assert gaps["device_get"] == pytest.approx(2 * ms)
    assert sum(gaps.values()) == pytest.approx(s.window_s - s.busy_s)


def test_no_window_is_an_error():
    with pytest.raises(ValueError):
        trace.summarize({"/device:TPU:0": {"XLA Ops": []}}, [])


def test_recorded_fixture():
    s = trace.reduce_file(FIXTURE)
    assert s.devices == 1
    assert 0 < s.busy_s < s.window_s
    assert s.programs.get("_decode_segment", 0) > 0
    # the fixture traced 12 engine steps, one decode segment each
    assert s.calls["_decode_segment"][0] == 12
    assert s.calls["_decode_segment"][1] <= s.programs["_decode_segment"]
    gaps = [v for _, v in s.idle_gaps]
    assert gaps == sorted(gaps, reverse=True)
    assert 0 < sum(gaps) <= s.window_s - s.busy_s
    assert len(s.device_ops) <= 10 and len(s.idle_gaps) <= 10
