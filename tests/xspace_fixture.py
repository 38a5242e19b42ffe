"""Deterministic XSpace fixture builder.

Encodes a synthetic-but-schema-faithful serialized XSpace (the pinned
field numbers in dynolog_tpu/trace.py `_SCHEMA_PINS`) with a hand-rolled
protobuf writer — no tensorflow/protobuf dependency, bit-for-bit
reproducible (no timestamps, no randomness), so the checked-in
tests/fixtures/bench.xplane.pb can be regenerated and diffed:

    python tests/xspace_fixture.py tests/fixtures/bench.xplane.pb

The fixture is the shared workload for the converter parity test
(tests/test_trace_convert.py), the CI conversion and stream smokes
(scripts/convert_smoke.py, scripts/stream_smoke.py) and the diagnosis
tests — one artifact, so a converter regression shows up identically
in all of them.
"""

from __future__ import annotations

import struct
import sys

# Default shape: big enough that a conversion is tens-of-ms-measurable
# (≈25k events, the order of a short real capture's host planes), small
# enough to check in (~300 KB).
PLANES = 4
LINES_PER_PLANE = 3
EVENTS_PER_LINE = 2000
OPS_PER_PLANE = 16


def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        b7 = n & 0x7F
        n >>= 7
        out.append(b7 | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _field_varint(num: int, value: int) -> bytes:
    return _varint(num << 3) + _varint(value)


def _field_bytes(num: int, payload: bytes) -> bytes:
    return _varint((num << 3) | 2) + _varint(len(payload)) + payload


def _field_str(num: int, s: str) -> bytes:
    return _field_bytes(num, s.encode())


def _stat(stat_id: int, *, uint: int | None = None,
          double: float | None = None, text: str | None = None,
          raw: bytes | None = None) -> bytes:
    # XStat: metadata_id=1, then one of double_value=2 (fixed64),
    # uint64_value=3, str_value=5, bytes_value=6.
    body = _field_varint(1, stat_id)
    if uint is not None:
        body += _field_varint(3, uint)
    if double is not None:
        body += _varint((2 << 3) | 1) + struct.pack("<d", double)
    if text is not None:
        body += _field_str(5, text)
    if raw is not None:
        body += _field_bytes(6, raw)
    return body


def _stat_metadata(stat_id: int, name: str) -> bytes:
    # map<int64, XStatMetadata> entry: the embedded message carries id=1,
    # name=2.
    inner = _field_varint(1, stat_id) + _field_str(2, name)
    return _field_varint(1, stat_id) + _field_bytes(2, inner)


def _event_metadata(meta_id: int, name: str, display: str,
                    stats: tuple[bytes, ...] = ()) -> bytes:
    # map<int64, XEventMetadata> entry: key=1, value=2; the embedded
    # XEventMetadata carries id=1, name=2, display_name=4, stats=5.
    inner = (_field_varint(1, meta_id) + _field_str(2, name)
             + _field_str(4, display))
    for stat in stats:
        inner += _field_bytes(5, stat)
    return _field_varint(1, meta_id) + _field_bytes(2, inner)


def _event(meta_id: int, offset_ps: int, duration_ps: int,
           stats: tuple[bytes, ...] = ()) -> bytes:
    body = (_field_varint(1, meta_id) + _field_varint(2, offset_ps)
            + _field_varint(3, duration_ps))
    for stat in stats:
        body += _field_bytes(4, stat)
    return body


def _line(line_id: int, name: str, ts_ns: int, events: list[bytes]) -> bytes:
    body = (_field_varint(1, line_id) + _field_str(2, name)
            + _field_varint(3, ts_ns))
    for ev in events:
        body += _field_bytes(4, ev)
    return body


def build_xspace(
    planes: int = PLANES,
    lines_per_plane: int = LINES_PER_PLANE,
    events_per_line: int = EVENTS_PER_LINE,
    ops_per_plane: int = OPS_PER_PLANE,
    op_duration_scale: dict | None = None,
    op_shapes: dict | None = None,
) -> bytes:
    """One serialized XSpace: `planes` device-ish planes, each with an op
    metadata table and `lines_per_plane` lines of back-to-back complete
    events cycling through the op ids. Deterministic by construction.

    `op_duration_scale` ({meta_id: factor}) scales chosen ops' durations
    and `op_shapes` ({meta_id: "bf16[64,64]"}) overrides result shapes —
    the synthetic-regression knobs the diagnosis smoke and tests use to
    build a "current" capture that regressed vs the pristine default
    (which stays bit-identical to the checked-in fixture)."""
    scale = op_duration_scale or {}
    shapes = op_shapes or {}
    space = b""
    for p in range(planes):
        plane = _field_str(2, f"/device:TPU:{p} (synthetic)")
        for line_idx in range(lines_per_plane):
            events = []
            offset_ps = 0
            for e in range(events_per_line):
                meta_id = (e % ops_per_plane) + 1
                # Durations cycle 1-16 µs; offsets tile the line densely
                # with a 100ns gap so event order and spans are non-trivial
                # but reproducible.
                duration_ps = int(meta_id * 1_000_000 * scale.get(meta_id, 1))
                events.append(_event(meta_id, offset_ps, duration_ps))
                offset_ps += duration_ps + 100_000
            plane += _field_bytes(3, _line(
                line_id=line_idx,
                name=f"XLA Ops {line_idx}" if line_idx else "XLA Ops",
                ts_ns=1_700_000_000_000_000_000 + p * 1_000_000,
                events=events,
            ))
        for op in range(1, ops_per_plane + 1):
            shape = shapes.get(op, "bf16[128,128]")
            plane += _field_bytes(4, _event_metadata(
                op, f"%fusion.{op} = {shape}", f"fusion.{op}"))
        space += _field_bytes(1, plane)
    return space


# One step of a job with a loop on the device, as the TPU draws it on "XLA
# Ops": a `while` event that spans its trips with the body's events inside it
# on the same line. (op, microseconds) or (op, [what it holds]); a holder
# lasts as long as what it holds plus NESTED_SLACK_US of its own.
NESTED_STEP = (
    ("%fusion.9 = bf16[64,64]{1,0} fusion(%p0)", 20),
    ("%while.1 = (s32[], f32[8,8]{1,0}) while(%t)", [
        ("%fusion.2 = f32[8,8]{1,0} fusion(%a)", 10),
        ("%fusion.3 = f32[8,8]{1,0} fusion(%b)", 10),
        ("%all-reduce.4 = f32[8,8]{1,0} all-reduce(%c)", 20),
        ("%conditional.5 = f32[8,8]{1,0} conditional(%d)", [
            ("%fusion.6 = f32[8,8]{1,0} fusion(%e)", 10),
            ("%copy.7 = f32[8,8]{1,0} copy(%f)", 10),
        ]),
        ("%fusion.2 = f32[8,8]{1,0} fusion(%a)", 10),
        ("%dot.8 = f32[8,8]{1,0} dot(%g, %h)", 15),
    ]),
)
NESTED_SLACK_US = 5


def build_nested_xspace(steps: int = 3, scale: dict | None = None,
                        shuffle: bool = False) -> bytes:
    """One device plane whose "XLA Ops" line holds `steps` times NESTED_STEP.
    `scale` ({"fusion.3": 2.0}) lengthens chosen ops (and so what holds
    them); `shuffle` writes the line's events in reverse, which a reader
    has to sort before it can tell what lies inside what."""
    scale = scale or {}
    ids: dict[str, int] = {}
    events: list[bytes] = []

    def lay(spec, at_ps: int) -> int:
        for name, what in spec:
            meta_id = ids.setdefault(name, len(ids) + 1)
            if isinstance(what, list):
                slot = len(events)
                events.append(b"")
                end_ps = lay(what, at_ps) + NESTED_SLACK_US * 1_000_000
                events[slot] = _event(meta_id, at_ps, end_ps - at_ps)
            else:
                end_ps = at_ps + int(
                    what * 1_000_000 * scale.get(_shown(name), 1))
                events.append(_event(meta_id, at_ps, end_ps - at_ps))
            at_ps = end_ps
        return at_ps

    at_ps = 0
    for _ in range(steps):
        at_ps = lay(NESTED_STEP, at_ps) + 100_000
    if shuffle:
        events.reverse()
    plane = _field_str(2, "/device:TPU:0 (synthetic, nested)")
    plane += _field_bytes(3, _line(
        line_id=0, name="XLA Ops", ts_ns=1_700_000_000_000_000_000,
        events=events))
    for name, meta_id in ids.items():
        plane += _field_bytes(4, _event_metadata(meta_id, name, _shown(name)))
    return _field_bytes(1, plane)


TF_OP = 7  # the stat metadata id of `tf_op` in a scoped plane


def build_scoped_xspace(step: tuple, scope_of, steps: int = 2,
                        scale: dict | None = None) -> bytes:
    """One device plane whose ops carry their paths in `tf_op`: `step`, rows
    of (op, its path or None, microseconds or the rows it holds), `steps`
    times over; `scale` lengthens the ops under a scope (`scope_of(path)`,
    the product's reading of a path)."""
    scale = scale or {}
    ids: dict = {}
    paths: dict = {}
    events: list = []

    def lay(spec, at_ps):
        for name, path, what in spec:
            meta = ids.setdefault(name, len(ids) + 1)
            paths[meta] = path
            if isinstance(what, list):
                slot = len(events)
                events.append(b"")
                end_ps = lay(what, at_ps) + NESTED_SLACK_US * 1_000_000
                events[slot] = _event(meta, at_ps, end_ps - at_ps)
            else:
                end_ps = at_ps + int(what * 1_000_000 * scale.get(
                    scope_of(path or ""), 1))
                events.append(_event(meta, at_ps, end_ps - at_ps))
            at_ps = end_ps
        return at_ps

    at_ps = 0
    for _ in range(steps):
        at_ps = lay(step, at_ps) + 100_000
    plane = _field_str(2, "/device:TPU:0")
    plane += _field_bytes(3, _line(0, "XLA Ops", 0, events))
    for name, meta in ids.items():
        stats = () if paths[meta] is None else (
            _stat(TF_OP, text=paths[meta]),)
        plane += _field_bytes(4, _event_metadata(
            meta, name, _shown(name), stats))
    plane += _field_bytes(5, _stat_metadata(TF_OP, "tf_op"))
    return _field_bytes(1, plane)


def _shown(name: str) -> str:
    """'%fusion.3 = f32[8,8]{1,0} fusion(%b)' -> 'fusion.3'."""
    return name[1:].split(" ", 1)[0]


def main(argv: list[str]) -> int:
    out = argv[1] if len(argv) > 1 else "tests/fixtures/bench.xplane.pb"
    data = build_xspace()
    with open(out, "wb") as f:
        f.write(data)
    print(f"{out}: {len(data)} bytes")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
