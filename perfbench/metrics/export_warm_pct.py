"""Of the window's ok captures, the share whose manifest says
`"export_child": "warm"`: the derived files were begun by the export child
the shim started as that capture's window opened, which had said it was
ready before it was handed the artifact's path (`timing.export_ready_ms`
is for how long). `"cold"` is a child started at the hand-over, `"thread"`
the in-process fallback. 0.0 where no manifest holds the field (a shim
that starts its child only once the artifact is on disk), because the
captures were counted, not by default."""

NAME = "export_warm_pct"
UNIT = "%"
BETTER = "higher"
SOURCE = "program_counter"
LAYER = "derive"
MOVES = "derived_ms_p50"
CELLS = ('capture',)


def read(run: dict):
    ok = [c["manifest"] for c in run["captures"] if c["ok"]]
    if not ok:
        return None
    warm = sum(1 for m in ok if m.get("export_child") == "warm")
    return 100.0 * warm / len(ok)
