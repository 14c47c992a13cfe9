"""The trace reduction: busy time, idle gaps named by the host, device time
per operation, on a hand-made trace with a known answer and on a small
trace recorded on a TPU v5e (tests/trace_v5e.json.gz, made by
record_trace.py)."""
from __future__ import annotations

from pathlib import Path

import pytest

from chipbench import trace as tracing

FIXTURE = Path(__file__).with_name("trace_v5e.json.gz")
MS = 1e6


def _events():
    # window [0, 100] ms; device ops 10-30 and 25-40 (overlapping) and
    # 70-80; the host was in "fetch" from 40 to 70 and in "step" all along
    return tracing.Events(
        {"/device:TPU:0": [("fusion", 10 * MS, 20 * MS),
                           ("copy", 25 * MS, 15 * MS),
                           ("fusion", 70 * MS, 10 * MS),
                           ("late", 120 * MS, 5 * MS)]},
        [(tracing.OPEN, 0.0, 1.0), (tracing.CLOSE, 100 * MS, 1.0),
         ("step", 0.0, 100 * MS), ("fetch", 40 * MS, 30 * MS)])


def test_known_answer():
    s = tracing.summarize(_events())
    assert s.window_s == pytest.approx(0.1)
    assert s.busy_s == pytest.approx(0.04)
    assert s.idle_share == pytest.approx(0.6)
    assert s.op_seconds == pytest.approx({"fusion": 0.03, "copy": 0.015})
    assert s.breakdown["idle_gaps"] == [["fetch", pytest.approx(0.03)],
                                        ["step", pytest.approx(0.02)],
                                        ["step", pytest.approx(0.01)]]
    assert s.breakdown["device_ops"][0] == ["fusion", pytest.approx(0.03)]


def test_round_trip_and_no_marks(tmp_path):
    ev = _events()
    tracing.save_events(ev, tmp_path / "t.json.gz")
    back = tracing.read_events(tmp_path / "t.json.gz")
    assert tracing.summarize(back).busy_s == tracing.summarize(ev).busy_s
    with pytest.raises(ValueError):
        tracing.summarize(tracing.Events(ev.device_ops, ev.host[2:]))


def test_recorded_v5e_trace():
    s = tracing.summarize(tracing.read_events(FIXTURE))
    assert 0 < s.busy_s < s.window_s
    # the recorder slept 0.02, 0.05 and 0.03 s between its steps: those
    # are the longest idle gaps, named by what the host was doing
    gaps = s.breakdown["idle_gaps"]
    assert [g[0] for g in gaps[:3]] == ["record.sleep1", "record.sleep2",
                                        "record.sleep0"]
    assert gaps[0][1] == pytest.approx(0.05, rel=0.2)
    assert s.breakdown["device_ops"]
