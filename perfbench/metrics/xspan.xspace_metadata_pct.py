"""Of the bytes of the capture the breakdown reads, the share that is not in
a plane's `lines`, where the events are: event and stat metadata, plane
stats (the programs' HLO among them) and framing, which a session writes
once whatever its window's length. `account` is the benchmark's own count
of what each plane holds, by a wire walk of the plane's top-level fields
and `xplane.load` for names and events; the product's is the plane table of
`python -m dynolog_tpu.trace`, and a test holds the two equal."""

import os

import xplane

NAME = "xspan.xspace_metadata_pct"
UNIT = "%"
BETTER = "lower"
SOURCE = "program_counter"
LAYER = "shim capture"
MOVES = "capture_ms_p50"
CELLS = ('capture',)
# XPlane's fields by number; id and name are "other"
FIELDS = {3: "lines", 4: "event_metadata", 5: "stat_metadata", 6: "stats"}


def _varint(buf, i: int) -> tuple:
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if not byte & 0x80:
            return value, i
        shift += 7


def _fields(buf, i: int, end: int):
    """(field number, first byte of the field, first of its payload, one
    past its last) for each field of the message at buf[i:end]."""
    while i < end:
        start = i
        tag, i = _varint(buf, i)
        wire = tag & 7
        if wire == 0:
            _, i = _varint(buf, i)
            body = i
        elif wire == 2:
            size, body = _varint(buf, i)
            i = body + size
        elif wire in (1, 5):
            body, i = i, i + (8 if wire == 1 else 4)
        else:
            raise ValueError(f"wire type {wire} at byte {start}")
        yield tag >> 3, start, body, i


def account(path: str) -> list:
    """One row a plane of the artifact, in file order: `bytes` (the plane's
    payload) and its parts `<field>_bytes`, which add up to it; `lines`,
    `events` and `event_metadata` (entries) counted."""
    with open(path, "rb") as f:
        data = memoryview(f.read())
    rows = []
    planes = (f for f in _fields(data, 0, len(data)) if f[0] == 1)
    for (_, _, body, end), plane in zip(
            planes, xplane.load(path).planes, strict=True):
        row = {"name": plane.name, "bytes": end - body, "other_bytes": 0,
               "event_metadata": 0,
               **{f"{name}_bytes": 0 for name in FIELDS.values()}}
        for num, start, _, stop in _fields(data, body, end):
            row[FIELDS.get(num, "other") + "_bytes"] += stop - start
            row["event_metadata"] += (num == 4)
        lines = list(plane.lines)
        row["lines"] = len(lines)
        row["events"] = sum(len(xplane._events(line)) for line in lines)
        rows.append(row)
    return rows


def read(run: dict):
    trace = run.get("trace")
    if not trace:
        return None
    size = os.path.getsize(trace["path"])
    in_lines = sum(row["lines_bytes"] for row in account(trace["path"]))
    return 100.0 * (size - in_lines) / size if size else None
