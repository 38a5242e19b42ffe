"""`convert_generic_pct` on records made from the program's own spans: planes
are converted here by `trace._convert_plane`, the spans it hands back are
laid into a run's journal as `dyno selftrace` returns them, and the reader
reads. Every tag in the toy planes is one byte, so a toy run reads 0.0; a
plane that holds a field of a two-byte tag is read by the generic path and
brings a `convert.generic` beside its `convert.decode`.
"""

import sys
from pathlib import Path

import pytest

import cells
import selftrace

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO / "tests"))

import xspace_fixture as xf  # noqa: E402

from dynolog_tpu import obs, trace  # noqa: E402

W = 1_790_000_000.0
READER = cells.load_readers()["convert_generic_pct"]


def plane(events: list) -> bytes:
    body = xf._field_varint(1, 1) + xf._field_str(2, "/device:TPU:0")
    body += xf._field_bytes(4, xf._event_metadata(1, "%op.1 = f32[] op()", ""))
    return body + xf._field_bytes(3, xf._line(1, "XLA Ops", 1000, events))


PLAIN = plane([xf._event(1, i * 2_000_000, 1_000_000) for i in range(4)])
# field 16 as a varint: its tag takes two bytes, which no fast loop knows
TWO_BYTE_TAG = plane([xf._event(1, 0, 1_000_000) + xf._field_varint(16, 1)])


def journal_of(planes: list, at_s: float = 5.0) -> list:
    """The spans of one conversion of `planes` as the daemon's journal holds
    them, the conversion begun `at_s` into the window."""
    ctx = obs.TraceContext.mint()
    obs.set_current(ctx)
    try:
        spans = [s for pid, buf in enumerate(planes, start=1)
                 for s in trace._convert_plane((pid, buf))[2]]
    finally:
        obs.set_current(None)
    shift = int((W + at_s) * 1e6) - min(s.start_us for s in spans)
    return [dict(s.chrome_event(), ts=s.start_us + shift) for s in spans]


def record(spans: list) -> dict:
    tick = {"name": "collector.tpu_monitor.tick", "ts": int((W - 20) * 1e6),
            "dur": 5000, "pid": 7, "tid": 7, "args": {}}
    found = {"spans": [tick] + spans}
    return {"window_start": W, "window_end": W + 40.0, "captures": [],
            "selftrace": found,
            "selftrace_oldest_ms": selftrace.oldest_ms(found)}


def test_a_toy_run_whose_every_tag_is_one_byte_reads_zero():
    spans = journal_of([PLAIN, PLAIN]) + journal_of([PLAIN], at_s=9.0)
    assert [s["name"] for s in spans].count("convert.decode") == 3
    assert READER.read(record(spans)) == 0.0


def test_a_plane_with_a_two_byte_tag_reads_above_zero():
    spans = journal_of([PLAIN, TWO_BYTE_TAG, PLAIN]) + journal_of([PLAIN], 9.0)
    marks = [s for s in spans if s["name"] == "convert.generic"]
    assert len(marks) == 1
    assert READER.read(record(spans)) == pytest.approx(25.0)  # one of four
    # a mark that lies beside no decode of the window counts for nothing
    marks[0]["ts"] += 1
    assert READER.read(record(spans)) == 0.0


def test_no_decode_in_the_window_or_no_journal_reads_none():
    before = journal_of([TWO_BYTE_TAG], at_s=-10.0)
    assert READER.read(record(before)) is None
    run = record(journal_of([PLAIN]))
    run["selftrace_oldest_ms"] = (W + 1) * 1e3  # the ring wrapped
    assert READER.read(run) is None
    assert READER.read({"window_start": W, "window_end": W + 40.0}) is None
