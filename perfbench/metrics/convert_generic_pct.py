"""The share of the window's `convert.decode` spans that have a
`convert.generic` beside them: planes in which `trace._decode_plane` had to
read a metadata entry or an event by its generic path (`_fields`), because
the message held something the loops written for the wire layout do not
know (a tag of two bytes, a fixed-width field). The program lays the span
exactly over that plane's `convert.decode` (same trace id, process, start
and length) and records it for no other plane, so 0.0 says every decode of
the window stayed on the fast path; None where the journal holds no
`convert.decode` of the window, or does not reach back to its opening."""

import conversions
import selftrace

NAME = "convert_generic_pct"
UNIT = "%"
BETTER = "lower"
SOURCE = "program_span"
LAYER = "derive"
MOVES = "derived_ms_p50"
CELLS = ('capture',)

GENERIC = "convert.generic"


def read(run: dict):
    found = selftrace.journal(run)
    if found is None:
        return None
    lo, hi = run["window_start"] * 1e6, run["window_end"] * 1e6
    laid = {name: [(s["args"].get("trace_id"), s["pid"], s["ts"], s["dur"])
                   for s in found["spans"]
                   if s["name"] == name and lo <= s["ts"] < hi]
            for name in (conversions.DECODE, GENERIC)}
    decodes, beside = laid[conversions.DECODE], set(laid[GENERIC])
    if not decodes:
        return None
    return 100.0 * sum(d in beside for d in decodes) / len(decodes)
