#!/usr/bin/env python3
"""Regenerates BENCHMARK.json's `per_layer` table from perfbench/metrics/*.py.

    python3 perfbench/gen_benchmark.py

A per-layer metric is one reader file; its NAME, UNIT, BETTER, SOURCE,
LAYER, MOVES and CELLS (the traffic kinds in which it finds something to
read) become its entry. A metric whose CELLS cover every traffic kind gets
no `workloads` key, so it is due in every cell, later ones too; any other
lists the cells of its kinds. Everything else in BENCHMARK.json is left as
it stands. A later PR adds a metric by adding a file and running this.
"""

from __future__ import annotations

import json
import sys

import cells


def per_layer(bench: dict) -> list:
    kinds = {w["name"]: cells.load_traffic(w["traffic"])["kind"]
             for w in bench["workloads"]}
    table = []
    for name, reader in cells.load_readers().items():
        entry = {"name": name, "unit": reader.UNIT, "better": reader.BETTER,
                 "source": reader.SOURCE, "layer": reader.LAYER,
                 "moves": reader.MOVES}
        if set(reader.CELLS) != set(cells.TRAFFIC_KINDS):
            entry["workloads"] = [
                w for w, kind in kinds.items() if kind in reader.CELLS]
        table.append(entry)
    return table


def main() -> int:
    path = cells.ROOT / "BENCHMARK.json"
    bench = cells.load_benchmark()
    bench["per_layer"] = per_layer(bench)
    with open(path, "w") as f:
        json.dump(bench, f, indent=2)
        f.write("\n")
    print(f"{path}: {len(bench['per_layer'])} per-layer metrics")
    return 0


if __name__ == "__main__":
    sys.exit(main())
