"""The XSpace wire format: every reader of a captured .xplane.pb's bytes.

The profiler serializes an XSpace protobuf; this module decodes it with the
standard library alone (no tensorflow/protobuf dependency), each plane once,
by offsets into the one buffer the file was read into. The field numbers are
pinned here (`_SCHEMA_PINS`), verified against traces captured by this
repo's own e2e flow and against the wheel's descriptor
(`verify_schema_pins`). What a decoded plane means (the op table, the Chrome
trace, what converting it costs) is `dynolog_tpu.trace`'s, which imports
this module; this one imports nothing of the package, so a change to how a
plane is summarised or written cannot reach the decoder.
"""

from __future__ import annotations

import os
import struct
from dataclasses import dataclass, field

# Protobuf fixed64 stat values decode as little-endian doubles. Module
# level (not an inline struct.unpack format) per the dynolint
# struct-constant rule.
FLOAT64 = struct.Struct("<d")

# The XSpace schema subset the decoder reads, message -> {field name: pinned
# number}. Pinned against traces this repo's own e2e flow captures, and
# checked against the xplane FileDescriptor embedded in the installed wheel
# (verify_schema_pins() — a jax upgrade that renumbers a field fails loudly
# instead of silently mis-summarizing).
_SCHEMA_PINS = {
    "XSpace": {"planes": 1},
    "XPlane": {
        "name": 2, "lines": 3, "event_metadata": 4, "stat_metadata": 5,
        "stats": 6,
    },
    "XLine": {"id": 1, "name": 2, "timestamp_ns": 3, "events": 4},
    "XEvent": {
        "metadata_id": 1, "offset_ps": 2, "duration_ps": 3, "stats": 4,
    },
    "XEventMetadata": {"id": 1, "name": 2, "display_name": 4, "stats": 5},
    "XStat": {
        "metadata_id": 1, "double_value": 2, "uint64_value": 3,
        "int64_value": 4, "str_value": 5, "ref_value": 7,
    },
    "XStatMetadata": {"id": 1, "name": 2},
}


def _load_xplane_descriptor():
    """Loads the generated xplane_pb2 module from an installed wheel
    WITHOUT importing the heavyweight package around it (the generated
    code needs only google.protobuf; ~80ms vs ~15s for `import
    tensorflow`). Returns the module or None."""
    import importlib.util

    candidates = [
        ("tensorflow", "tsl/profiler/protobuf/xplane_pb2.py"),
        ("tensorflow", "core/profiler/protobuf/xplane_pb2.py"),
        ("tensorboard_plugin_profile", "protobuf/xplane_pb2.py"),
        ("xprof", "protobuf/xplane_pb2.py"),
    ]
    for pkg, rel in candidates:
        try:
            spec = importlib.util.find_spec(pkg)
        except (ImportError, ValueError):
            continue
        if not spec or not spec.submodule_search_locations:
            continue
        for root in spec.submodule_search_locations:
            path = os.path.join(root, rel)
            if not os.path.exists(path):
                continue
            try:
                mspec = importlib.util.spec_from_file_location(
                    "dynolog_tpu._xplane_pb2", path)
                mod = importlib.util.module_from_spec(mspec)
                mspec.loader.exec_module(mod)
                return mod
            except Exception:  # noqa: BLE001 - any wheel/protobuf
                continue  # incompatibility: try the next candidate
    return None


def verify_schema_pins() -> tuple[bool | None, list[str]]:
    """Cross-checks _SCHEMA_PINS against the embedded FileDescriptor.
    Returns (ok, mismatches); ok is None when no wheel ships a
    descriptor to check against (the pins stand as-is)."""
    mod = _load_xplane_descriptor()
    if mod is None:
        return None, []
    mismatches = []
    for msg_name, fields in _SCHEMA_PINS.items():
        msg = getattr(mod, msg_name, None)
        if msg is None:
            mismatches.append(f"{msg_name}: message missing from descriptor")
            continue
        by_name = {f.name: f.number for f in msg.DESCRIPTOR.fields}
        for fname, pinned in fields.items():
            actual = by_name.get(fname)
            if actual != pinned:
                mismatches.append(
                    f"{msg_name}.{fname}: pinned field {pinned}, "
                    f"wheel descriptor says {actual}")
    return (not mismatches), mismatches


def _read_varint(buf, i: int) -> tuple[int, int]:
    value = shift = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if not b & 0x80:
            return value, i
        shift += 7


def _fields(buf, i: int, end: int) -> list[tuple[int, int, int, int]]:
    """The fields of the message at buf[i:end], decoded in one loop over the
    one buffer the file was read into: (number, wire type, a, b). A varint's
    `a` is its value; a length-delimited or fixed field's `a` is where its
    payload starts, cut out (`buf[a:b]`) only where it is used: a name, never
    HLO bytes. `b` is where the field ends, so a message's fields tile
    [i, end). Raises ValueError on truncated or malformed input."""
    out = []
    add = out.append
    try:
        while i < end:
            tag = buf[i]
            i += 1
            if tag > 0x7F:  # a field number above 15
                tag, i = _read_varint(buf, i - 1)
            if tag < 8:
                raise ValueError("field 0")
            wt = tag & 7
            if wt == 0:
                v = buf[i]
                i += 1
                if v > 0x7F:  # inlined: offsets and durations take this path
                    v &= 0x7F
                    shift = 7
                    while True:
                        b = buf[i]
                        i += 1
                        v |= (b & 0x7F) << shift
                        if b < 0x80:
                            break
                        shift += 7
                add((tag >> 3, 0, v, i))
            elif wt == 2:
                size = buf[i]
                i += 1
                if size > 0x7F:
                    size, i = _read_varint(buf, i - 1)
                add((tag >> 3, 2, i, i + size))
                i += size
            elif wt == 1 or wt == 5:
                width = 8 if wt == 1 else 4
                add((tag >> 3, wt, i, i + width))
                i += width
            else:
                raise ValueError(f"unsupported wire type {wt}")
    except IndexError as e:
        raise ValueError("truncated message") from e
    if i != end:  # the last field runs past the message's end
        raise ValueError("truncated field")
    return out


# What a plane's bytes are made of, by the XPlane field that holds them
# (the account an operator asks "why is my trace 13 MB" of): the events
# live in `lines`; everything else is said once a plane, whatever the
# window's length.
CONTENT_FIELDS = {3: "lines", 4: "event_metadata", 5: "stat_metadata",
                  6: "stats"}


# The stats the op table reads, by XStatMetadata.name: the cost model's two
# numbers, the XProf category string, and the path the framework gave the
# op (`tf_op`: "jit(step)/transpose(jvp(moe.route))/dot_general:"), from
# which its scope reads.
COST_STATS = ("flops", "bytes_accessed", "hlo_category", "tf_op")
TEXT_STATS = ("hlo_category", "tf_op")


@dataclass
class _Plane:
    """One plane, decoded once: what its PlaneSummary and its Chrome-trace
    fragment are both made from."""

    name: str = ""
    bytes: int = 0
    content: dict = field(default_factory=dict)  # as PlaneSummary.content
    event_metadata: int = 0  # entries of the map, as they come
    names: dict = field(default_factory=dict)  # event metadata id -> name
    shown: dict = field(default_factory=dict)  # id -> display_name or name
    costs: dict = field(default_factory=dict)  # id -> {COST_STATS name: value}
    # a line: (id, name, timestamp_ns, [(metadata id, offset_ps, duration_ps,
    # the event's own costs or None)])
    lines: list = field(default_factory=list)
    # the metadata entries and events the generic path had to read
    # (`_decode_plane`): 0 wherever every tag in them is one byte
    generic: int = 0


def _map_entry(buf, a: int, b: int) -> tuple[int, list]:
    """One map<int64, message> entry: (id, the value's fields). The id may
    arrive as the entry's key (field 1) or as the embedded message's own
    field 1 (XEventMetadata.id, XStatMetadata.id): producers are free to set
    either, and the one read later stands."""
    mid, inner = 0, []
    for num, wt, x, y in _fields(buf, a, b):
        if num == 1 and wt == 0:
            mid = x
        elif num == 2 and wt == 2:
            inner = _fields(buf, x, y)
            for en, ew, ex, _ in inner:
                if en == 1 and ew == 0:
                    mid = ex
    return mid, inner


def _costs(buf, stat_spans, kinds: dict) -> dict:
    """{COST_STATS name: value} of the XStats at the spans, by the plane's
    `kinds` {stat metadata id: COST_STATS name}, through `_fields`: the
    generic reading of a stat. The callers hand over the stats worth
    opening: one whose metadata id leads it in one byte, as producers write
    it, and is none of `kinds` they step over, unread. It reads the few
    stats an event keeps (`_decode_plane`) and those of a metadata entry
    that `_read_entry` handed back (`_entry_generic`); an entry `_read_entry`
    knows has its wanted stats read where they lie, to the same answer."""
    found = {}
    for a, b in stat_spans:
        sid, value, text = 0, None, None
        for num, wt, x, y in _fields(buf, a, b):
            if num == 1 and wt == 0:
                sid = x
            elif num == 2 and wt == 1:
                value = FLOAT64.unpack_from(buf, x)[0]
            elif num in (3, 4, 7) and wt == 0:
                value = float(x)
            elif num == 5 and wt == 2:
                text = buf[x:y]
        kind = kinds.get(sid)
        if kind in TEXT_STATS:
            if text is not None:
                found[kind] = text.decode(errors="replace")
        elif kind is not None and value is not None:
            found[kind] = value
    return found


def _entry_generic(buf, a: int, b: int, kinds: dict) -> tuple:
    """`_read_entry`'s answer for any valid entry, through `_fields`: a
    list of the entry's fields, one of the value's, one for each stat
    opened. Raises ValueError on truncated or malformed input."""
    mid, inner = _map_entry(buf, a, b)
    name = disp = ""
    stat_spans = []
    for num, wt, x, y in inner:
        if wt != 2:
            continue
        if num == 2:
            name = buf[x:y].decode(errors="replace")
        elif num == 4:  # display_name (3 is `metadata`: opaque bytes)
            disp = buf[x:y].decode(errors="replace")
        elif num == 5 and kinds and (
                y - x < 2 or buf[x] != 0x08 or buf[x + 1] > 0x7F
                or buf[x + 1] in kinds):
            stat_spans.append((x, y))
    return mid, name, disp or name, _costs(buf, stat_spans, kinds)


def _read_entry(buf, i: int, end: int, kinds: dict) -> tuple | None:
    """One entry of a plane's event-metadata map, the {key, XEventMetadata}
    at buf[i:end], read in ONE pass written for its wire layout: (id, name,
    display_name or name, {COST_STATS name: value}), or None where the
    entry holds what this loop does not know.

    What it reads: the key (tag 0x08), and inside the value (0x12) the id
    (0x08), `name` (0x12), `display_name` (0x22) and each `stats` field
    (0x2A) where it lies. A stat's two leading bytes say whether it is
    worth opening, by the test the generic path makes: one that leads with
    its metadata id in one byte (0x08, id) which is none of the plane's
    `kinds` is stepped over by its length; any other (a wanted id, an id of
    two bytes, a stat that does not lead with its id) is read there and
    then: the id (0x08), `double_value` (0x11), `uint64_value`,
    `int64_value`, `ref_value` (0x18, 0x20, 0x38), `str_value` (0x2A). What
    it steps over unread: `metadata` (0x1A) and every other field of a
    one-byte tag that is a varint or length-delimited, at all three levels.
    As in `_map_entry`, the id read later stands, and a value said twice
    stands as the later one. No list of fields, no tuple a field; a length
    or an id of two bytes (a name's, most ids) is put together in line.

    What it hands back (None): a tag above 0x7F (a field number above 15),
    field 0, a fixed-width field other than `double_value`, a message whose
    fields do not end where it ends, a read past the buffer. The caller
    then reads the entry by `_entry_generic`, which gives any valid entry's
    answer and raises ValueError for truncated and malformed input."""
    mid = 0
    name = disp = ""
    found = {}
    try:
        while i < end:
            tag = buf[i]
            i += 1
            if tag == 0x12:  # the value: XEventMetadata
                size = buf[i]
                i += 1
                if size > 0x7F:
                    c = buf[i]
                    i += 1
                    if c > 0x7F:
                        size, i = _read_varint(buf, i - 2)
                    else:
                        size += (c << 7) - 0x80
                value_end = i + size
                name = disp = ""
                found = {}
                while i < value_end:
                    tag = buf[i]
                    i += 1
                    if tag == 0x2A:  # stats
                        size = buf[i]
                        i += 1
                        if size > 0x7F:
                            c = buf[i]
                            i += 1
                            if c > 0x7F:
                                size, i = _read_varint(buf, i - 2)
                            else:
                                size += (c << 7) - 0x80
                        stat_end = i + size
                        if not (kinds and (
                                size < 2 or buf[i] != 0x08
                                or buf[i + 1] > 0x7F or buf[i + 1] in kinds)):
                            i = stat_end  # not one of `kinds`: unread
                            continue
                        sid, value, text = 0, None, None
                        while i < stat_end:
                            tag = buf[i]
                            i += 1
                            if (tag == 0x08 or tag == 0x18 or tag == 0x20
                                    or tag == 0x38):
                                v = buf[i]
                                i += 1
                                if v > 0x7F:
                                    v &= 0x7F
                                    shift = 7
                                    while True:
                                        c = buf[i]
                                        i += 1
                                        v |= (c & 0x7F) << shift
                                        if c < 0x80:
                                            break
                                        shift += 7
                                if tag == 0x08:
                                    sid = v
                                else:  # uint64, int64, ref: as written
                                    value = float(v)
                            elif tag == 0x2A:  # str_value
                                size = buf[i]
                                i += 1
                                if size > 0x7F:
                                    c = buf[i]
                                    i += 1
                                    if c > 0x7F:
                                        size, i = _read_varint(buf, i - 2)
                                    else:
                                        size += (c << 7) - 0x80
                                text = buf[i:i + size]
                                i += size
                            elif tag == 0x11:  # double_value
                                value = FLOAT64.unpack_from(buf, i)[0]
                                i += 8
                            elif tag > 0x7F or tag < 8:
                                return None
                            elif tag & 7 == 0:
                                while buf[i] > 0x7F:
                                    i += 1
                                i += 1
                            elif tag & 7 == 2:
                                size, i = _read_varint(buf, i)
                                i += size
                            else:
                                return None
                        if i != stat_end:
                            return None
                        kind = kinds.get(sid)
                        if kind in TEXT_STATS:
                            if text is not None:
                                found[kind] = text.decode("utf-8", "replace")
                        elif kind is not None and value is not None:
                            found[kind] = value
                    elif tag == 0x12 or tag == 0x22:  # name, display_name
                        size = buf[i]
                        i += 1
                        if size > 0x7F:
                            c = buf[i]
                            i += 1
                            if c > 0x7F:
                                size, i = _read_varint(buf, i - 2)
                            else:
                                size += (c << 7) - 0x80
                        if tag == 0x12:
                            name = buf[i:i + size].decode("utf-8", "replace")
                        else:
                            disp = buf[i:i + size].decode("utf-8", "replace")
                        i += size
                    elif tag == 0x08:
                        mid = buf[i]
                        i += 1
                        if mid > 0x7F:
                            mid, i = _read_varint(buf, i - 1)
                    elif tag > 0x7F or tag < 8:
                        return None
                    elif tag & 7 == 2:  # `metadata` (0x1A): opaque bytes
                        size, i = _read_varint(buf, i)
                        i += size
                    elif tag & 7 == 0:
                        while buf[i] > 0x7F:
                            i += 1
                        i += 1
                    else:
                        return None
                if i != value_end:
                    return None
            elif tag == 0x08:  # the key
                mid = buf[i]
                i += 1
                if mid > 0x7F:
                    mid, i = _read_varint(buf, i - 1)
            elif tag > 0x7F or tag < 8:
                return None
            elif tag & 7 == 0:
                while buf[i] > 0x7F:
                    i += 1
                i += 1
            elif tag & 7 == 2:
                size, i = _read_varint(buf, i)
                i += size
            else:
                return None
    except (IndexError, struct.error):
        return None
    if i != end:
        return None
    return mid, name, disp or name, found


def _read_event(buf, i: int, end: int, own) -> tuple | None:
    """The event at buf[i:end] as `_decode_plane` keeps it, (metadata id,
    offset_ps, duration_ps, its own costs or None), read in one pass
    written for an XEvent's wire layout; None where the event holds what
    this loop does not know, as `_read_entry` for its message.

    The three varints `metadata_id`, `offset_ps`, `duration_ps` (0x08,
    0x10, 0x18) are read where they lie, the one read later standing. A
    `stats` field (0x22) is kept only where the line's stats are looked at
    (`own`: the plane's `kinds`, or None) and its leading bytes say it may
    be one of them, by the test `_read_entry` makes; every other is stepped
    over by its length, as is any other varint (`num_occurrences`, 0x28) or
    length-delimited field of a one-byte tag. `_costs` opens what was kept,
    for the few events that keep any."""
    meta_id = offset_ps = duration_ps = 0
    stat_spans = None
    try:
        while i < end:
            tag = buf[i]
            i += 1
            if tag == 0x22:  # stats
                size = buf[i]
                i += 1
                if size > 0x7F:
                    size, i = _read_varint(buf, i - 1)
                if own and (
                        size < 2 or buf[i] != 0x08
                        or buf[i + 1] > 0x7F or buf[i + 1] in own):
                    if stat_spans is None:
                        stat_spans = []
                    stat_spans.append((i, i + size))
                i += size
            elif tag == 0x10 or tag == 0x18 or tag == 0x08:
                v = buf[i]
                i += 1
                if v > 0x7F:  # offsets and durations take this path
                    v &= 0x7F
                    shift = 7
                    while True:
                        c = buf[i]
                        i += 1
                        v |= (c & 0x7F) << shift
                        if c < 0x80:
                            break
                        shift += 7
                if tag == 0x10:
                    offset_ps = v
                elif tag == 0x18:
                    duration_ps = v
                else:
                    meta_id = v
            elif tag > 0x7F or tag < 8:
                return None
            elif tag & 7 == 0:
                while buf[i] > 0x7F:
                    i += 1
                i += 1
            elif tag & 7 == 2:
                size, i = _read_varint(buf, i)
                i += size
            else:
                return None
    except IndexError:
        return None
    if i != end:
        return None
    return (meta_id, offset_ps, duration_ps,
            _costs(buf, stat_spans, own) if stat_spans else None)


def _event_generic(buf, a: int, b: int, own) -> tuple:
    """`_read_event`'s answer for any valid event, through `_fields`: a
    list of the event's fields and a tuple a field. Raises ValueError on
    truncated or malformed input."""
    meta_id = offset_ps = duration_ps = 0
    stat_spans = []
    for num, wt, x, y in _fields(buf, a, b):
        if wt == 0:
            if num == 1:
                meta_id = x
            elif num == 2:
                offset_ps = x
            elif num == 3:
                duration_ps = x
        elif num == 4 and wt == 2 and own and (
                y - x < 2 or buf[x] != 0x08 or buf[x + 1] > 0x7F
                or buf[x + 1] in own):
            stat_spans.append((x, y))
    return (meta_id, offset_ps, duration_ps,
            _costs(buf, stat_spans, own) if stat_spans else None)


def _decode_plane(
    buf, start: int, end: int, top: list | None = None
) -> _Plane:
    """The one decode of the plane at buf[start:end]: metadata first (the
    stats' names, then every op's names and cost model), then every line
    once and every event once. Nothing is copied but the names. `top` is
    `_fields(buf, start, end)` where the caller has walked the plane's top
    level already (to weigh it, `_plane_outline`).

    The plane's top level, its stat-metadata map and a line's top level go
    through `_fields`. The two messages a plane holds by the thousand are
    each opened once and only as far as they are read, by a loop written
    for their own wire layout: an entry of the event-metadata map by
    `_read_entry`, an event by `_read_event`. The input decides: a message
    that holds anything those loops do not know (a tag above 0x7F, a fixed
    width, fields that do not end where the message ends) is read by the
    generic path (`_entry_generic`, `_event_generic`), that message alone,
    to the same answer for any valid message and the same ValueError for a
    broken one; `_Plane.generic` counts them."""
    plane = _Plane(bytes=end - start)
    line_spans, metadata_spans = [], []
    kinds: dict[int, str] = {}  # stat metadata id -> its COST_STATS name
    at = start
    for num, wt, x, y in top or _fields(buf, start, end):
        kind = CONTENT_FIELDS.get(num, "other")
        plane.content[kind] = plane.content.get(kind, 0) + y - at
        at = y
        if wt != 2:
            continue
        if num == 2:
            plane.name = buf[x:y].decode(errors="replace")
        elif num == 3:
            line_spans.append((x, y))
        elif num == 4:
            metadata_spans.append((x, y))
        elif num == 5:
            sid, inner = _map_entry(buf, x, y)
            sname = ""
            for en, ew, ex, ey in inner:
                if en == 2 and ew == 2:
                    sname = buf[ex:ey].decode(errors="replace")
            kinds.pop(sid, None)  # an id said twice: the later entry stands
            if sname in COST_STATS:
                kinds[sid] = sname
    # Cost-model stats (flops, bytes_accessed) and the hlo_category string
    # hang off the event METADATA, one set per op instance.
    plane.event_metadata = len(metadata_spans)
    names, shown, costs = plane.names, plane.shown, plane.costs
    generic = 0
    for a, b in metadata_spans:
        entry = _read_entry(buf, a, b, kinds)
        if entry is None:
            generic += 1
            entry = _entry_generic(buf, a, b, kinds)
        mid, names[mid], shown[mid], costs[mid] = entry  # left to right
    lines = []
    for a, b in line_spans:
        lid, lname, ts_ns = 0, "", 0
        line = _fields(buf, a, b)
        for num, wt, x, y in line:
            if num == 4:
                continue  # an event: read below, once every line has a name
            if num == 1 and wt == 0:
                lid = x
            elif num == 2 and wt == 2:
                lname = buf[x:y].decode(errors="replace")
            elif num == 3 and wt == 0:
                ts_ns = x
        lines.append((lid, lname, ts_ns, line))
    has_xla_ops = any(lname == "XLA Ops" for _, lname, _, _ in lines)
    for lid, lname, ts_ns, line in lines:
        # Per-occurrence stats override the metadata's cost model where a
        # producer emits them per event; only the lines the op table reads
        # (see _plane_summary) have theirs looked at.
        own = kinds if not has_xla_ops or lname == "XLA Ops" else None
        events = []
        add = events.append
        for num, wt, x, y in line:
            if num != 4 or wt != 2:
                continue
            event = _read_event(buf, x, y, own)
            if event is None:
                generic += 1
                event = _event_generic(buf, x, y, own)
            add(event)
        plane.lines.append((lid, lname, ts_ns, events))
    plane.generic = generic
    return plane


def _plane_spans(data) -> list[tuple[int, int]]:
    return [(a, b) for num, wt, a, b in _fields(data, 0, len(data))
            if num == 1 and wt == 2]


def _plane_outline(buf, start: int, end: int) -> tuple[list, int, int, int]:
    """One walk of the top level of the plane at buf[start:end], no line
    and no metadata entry opened: (its `_fields`, which `_decode_plane`
    takes over as `top`; the bytes under `lines`; how many lines; how many
    entries of the event-metadata map). What a caller weighs a plane by
    before it decides who decodes it (`trace._plane_weight`)."""
    top = _fields(buf, start, end)
    line_bytes = lines = entries = 0
    for num, wt, x, y in top:
        if wt != 2:
            continue
        if num == 3:
            line_bytes += y - x
            lines += 1
        elif num == 4:
            entries += 1
    return top, line_bytes, lines, entries


def iter_plane_bufs(data: bytes):
    """Yields each plane's raw protobuf buffer from a serialized XSpace, a
    copy a plane: how the single-shot reference and the tests walk a file,
    on no product path. The converter converts by offsets into `data`
    (`_plane_spans`) and cuts out only the planes it sends a forked worker
    (`trace._iter_fragments`)."""
    for a, b in _plane_spans(data):
        yield data[a:b]


def plane_index(data) -> list[dict]:
    """[{"name", "bytes"}] for every plane of a serialized XSpace, in file
    order: the top level only. A plane's bytes are its payload's (the
    XSpace's framing, a tag and a length a plane, is not counted); its name
    is read from the plane's leading fields (XPlane{id=1, name=2}; the
    lines, metadata and events behind them are skipped unread), so the
    cost is a few fields a plane whatever the trace's size. Raises
    ValueError (IndexError folded in) on malformed input."""
    view = memoryview(data)
    try:
        return [{"name": _plane_name(view, a, b), "bytes": b - a}
                for num, wt, a, b in _fields(view, 0, len(view))
                if num == 1 and wt == 2]
    except IndexError as e:
        raise ValueError("truncated xspace") from e


def _plane_name(view, i: int, end: int) -> str:
    """The name of the plane at view[i:end]: its first field 2, looked for
    among the scalar fields that lead the message and no further than the
    first line or metadata entry (field >= 3)."""
    while i < end:
        tag, i = _read_varint(view, i)
        num, wt = tag >> 3, tag & 7
        if wt == 0:
            _, i = _read_varint(view, i)
        elif wt == 2:
            size, i = _read_varint(view, i)
            if num == 2:
                return bytes(view[i:i + size]).decode(errors="replace")
            if num > 2:
                break
            i += size
        else:
            break
    return ""
