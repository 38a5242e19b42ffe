"""The most `trace.convert` spans open at one instant of the window: how
many export children convert side by side (the pile-up `ConvertBudget`'s
docstring warns of). A closed loop whose capture is shorter than a
conversion keeps more than one alive."""

import selftrace

NAME = "convert_alive_max"
UNIT = "count"
BETTER = "lower"
SOURCE = "program_span"
LAYER = "derive"
MOVES = "derived_ms_p50"
CELLS = ('capture',)


def read(run: dict):
    found = selftrace.journal(run)
    if found is None:
        return None
    lo, hi = run["window_start"] * 1e6, run["window_end"] * 1e6
    edges = []
    for s in found["spans"]:
        if s["name"] == selftrace.CONVERT and s["ts"] < hi and (
                s["ts"] + s["dur"] > lo):
            edges += [(max(s["ts"], lo), 1), (min(s["ts"] + s["dur"], hi), -1)]
    if not edges:
        return None
    alive = most = 0
    for _, step in sorted(edges):  # at one instant an end sorts before a start
        alive += step
        most = max(most, alive)
    return most
