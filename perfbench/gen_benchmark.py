#!/usr/bin/env python3
"""Regenerates BENCHMARK.json's `per_layer` table from perfbench/metrics/*.py
and appends to `end_to_end` what perfbench/end_to_end/*.py adds.

    python3 perfbench/gen_benchmark.py

A per-layer metric is one reader file; its NAME, UNIT, BETTER, SOURCE,
LAYER, MOVES and CELLS (the traffic kinds in which it finds something to
read) become its entry. A metric whose CELLS cover every traffic kind gets
no `workloads` key, so it is due in every cell, later ones too; any other
lists the cells of its kinds, less those whose configuration names it under
`no_reading`: a deployment in which the reader still finds nothing, with the
sentence that says why beside the name. A due metric that prints nothing
refuses a PR, so a reader lists only cells in which it always has a value.

An entry the table already holds keeps its place and its content; a cell
that no entry's list names yet is new, and joins the list of every reader
of its kind. Readers the table does not hold are appended after the
others, in the order of their names. An end-to-end metric added since PR 24
is a file under perfbench/end_to_end/ (NAME, UNIT, BETTER, BOUND, SOURCE,
CELLS, `read`): its entry is appended the same way, and every entry
`end_to_end` holds stays as it stands. Everything else in BENCHMARK.json is
left as it stands. A later PR adds a metric by adding a file, or a cell by
adding an entry under `workloads`, and running this.
"""

from __future__ import annotations

import json
import sys

import cells


def due_everywhere(module) -> bool:
    return set(module.CELLS) == set(cells.TRAFFIC_KINDS)


def with_workloads(entry: dict, module, kinds: dict, unread: dict) -> dict:
    """`entry` with the cells of the module's kinds, less those in which it
    has no reading, unless it is due in every cell there is or will be."""
    if not due_everywhere(module):
        entry["workloads"] = [
            w for w, kind in kinds.items()
            if kind in module.CELLS
            and module.NAME not in unread.get(w, ())]
    return entry


def entry_of(reader, kinds: dict, unread: dict) -> dict:
    """The table entry that one reader file generates; `kinds` is cell
    name -> traffic kind, in the order of `workloads`, and `unread` cell
    name -> the readers its configuration says have no reading there."""
    return with_workloads(
        {"name": reader.NAME, "unit": reader.UNIT, "better": reader.BETTER,
         "source": reader.SOURCE, "layer": reader.LAYER,
         "moves": reader.MOVES}, reader, kinds, unread)


def end_to_end_entry_of(metric, kinds: dict) -> dict:
    return with_workloads(
        {"name": metric.NAME, "unit": metric.UNIT, "better": metric.BETTER,
         "bound": metric.BOUND, "source": metric.SOURCE}, metric, kinds, {})


def end_to_end(bench: dict, metrics: dict | None = None) -> list:
    """The held entries as they stand, then the files' that it lacks."""
    metrics = cells.load_end_to_end() if metrics is None else metrics
    kinds = cell_kinds(bench)
    new = sorted(set(metrics) - {m["name"] for m in bench["end_to_end"]})
    return bench["end_to_end"] + [
        end_to_end_entry_of(metrics[name], kinds) for name in new]


def cell_kinds(bench: dict) -> dict:
    return {w["name"]: cells.load_traffic(w["traffic"])["kind"]
            for w in bench["workloads"]}


def no_reading(bench: dict, readers: dict) -> dict:
    """Cell name -> the readers its configuration names under `no_reading`.
    A name there that is no reader's, or that of a reader due in every cell
    (it has no list to leave a cell off), is an error and not silence."""
    unread = {}
    for w in bench["workloads"]:
        named = cells.load_config(w["config"]).get("no_reading", {})
        for name in named:
            if name not in readers:
                raise cells.BenchmarkError(
                    f"configs/{w['config']}.json: no_reading names {name!r}, "
                    "which is no reader in perfbench/metrics/")
            if due_everywhere(readers[name]):
                raise cells.BenchmarkError(
                    f"configs/{w['config']}.json: no_reading names {name!r}, "
                    "which is due in every cell and has no list of cells")
        unread[w["name"]] = tuple(named)
    return unread


def per_layer(bench: dict, readers: dict | None = None) -> list:
    readers = cells.load_readers() if readers is None else readers
    kinds = cell_kinds(bench)
    unread = no_reading(bench, readers)
    listed = {w for m in bench["per_layer"] for w in m.get("workloads", ())}
    table = []
    for held in bench["per_layer"]:
        if held["name"] not in readers:
            continue  # its file went; so does its entry
        entry = dict(held)
        if "workloads" in entry:
            entry["workloads"] = entry["workloads"] + [
                w for w in entry_of(readers[held["name"]], kinds, unread).get(
                    "workloads", ())
                if w not in listed]
        table.append(entry)
    new = sorted(set(readers) - {m["name"] for m in bench["per_layer"]})
    return table + [entry_of(readers[name], kinds, unread) for name in new]


def main() -> int:
    path = cells.ROOT / "BENCHMARK.json"
    bench = cells.load_benchmark()
    bench["end_to_end"] = end_to_end(bench)
    bench["per_layer"] = per_layer(bench)
    with open(path, "w") as f:
        json.dump(bench, f, indent=2)
        f.write("\n")
    print(f"{path}: {len(bench['end_to_end'])} end-to-end and "
          f"{len(bench['per_layer'])} per-layer metrics")
    return 0


if __name__ == "__main__":
    sys.exit(main())
