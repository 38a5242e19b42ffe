"""BENCHMARK.json and the data files it names, resolved by name alone.

A cell is one `workloads` entry: a configuration
(`perfbench/configs/<config>.json`) under a traffic mix
(`perfbench/traffic/<traffic>.json`). Per-layer metrics are the readers in
`perfbench/metrics/`, found by listing the directory, and so is an end-to-end
metric added since PR 24, in `perfbench/end_to_end/`. The module that makes a
job's weights and says what they should compute is the file its
configuration names under `reference`. Nothing here or in the harness
branches on a cell's, configuration's, module's or metric's name: a later PR
adds a cell by adding data files and an entry.
"""

from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
TRAFFIC_KINDS = ("steady", "capture")
CAPTURE_MODES = ("pull", "push")


class BenchmarkError(Exception):
    """The benchmark's own data is wrong or missing; nothing can be run."""


def _load_json(path: Path) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError) as e:
        raise BenchmarkError(f"cannot read {path}: {e}") from e


def load_benchmark(root: Path = ROOT) -> dict:
    return _load_json(root / "BENCHMARK.json")


@dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config_name: str
    traffic_name: str
    config: dict
    traffic: dict

    @property
    def kind(self) -> str:
        return self.traffic["kind"]

    @property
    def job(self) -> dict:
        return self.config["job"]


def load_config(name: str, here: Path = HERE) -> dict:
    config = _load_json(here / "configs" / f"{name}.json")
    for key in ("source", "assumed", "reduced", "departures", "deployment",
                "job", "reference", "daemon_flags", "shim"):
        if key not in config:
            raise BenchmarkError(f"configs/{name}.json lacks '{key}'")
    deployment = config["deployment"]
    chips = deployment.get("chips")
    if chips not in (1, 4):
        raise BenchmarkError(f"configs/{name}.json: chips {chips} is not 1 or 4")
    mesh = deployment.get("mesh") or {}
    size = 1
    for axis_size in mesh.values():
        size *= axis_size
    if (chips > 1 or mesh) and size != chips:
        raise BenchmarkError(
            f"configs/{name}.json: mesh {mesh} does not multiply to "
            f"{chips} chips")
    return config


def load_traffic(name: str, here: Path = HERE) -> dict:
    traffic = _load_json(here / "traffic" / f"{name}.json")
    if traffic.get("kind") not in TRAFFIC_KINDS:
        raise BenchmarkError(
            f"traffic/{name}.json: kind {traffic.get('kind')!r} is not one "
            f"of {TRAFFIC_KINDS}")
    if traffic["kind"] == "capture":
        for key in ("mode", "window_ms", "think_ms", "clients", "trigger"):
            if key not in traffic:
                raise BenchmarkError(f"traffic/{name}.json lacks '{key}'")
        if traffic["mode"] not in CAPTURE_MODES:
            raise BenchmarkError(
                f"traffic/{name}.json: mode {traffic['mode']!r} is not one "
                f"of {CAPTURE_MODES}")
        if traffic["clients"] != 1:
            raise BenchmarkError(
                f"traffic/{name}.json: clients {traffic['clients']}: one "
                "profiler session can be open in a process, so one client "
                "is the system's limit")
    return traffic


def load_cell(name: str, root: Path = ROOT) -> Cell:
    bench = load_benchmark(root)
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        known = ", ".join(w["name"] for w in bench["workloads"])
        raise BenchmarkError(f"no workload '{name}' in BENCHMARK.json ({known})")
    here = root / "perfbench"
    config = load_config(entry["config"], here)
    if config["deployment"]["chips"] != entry["chips"]:
        raise BenchmarkError(
            f"workload {name} asks {entry['chips']} chips, its configuration "
            f"{config['deployment']['chips']}")
    return Cell(
        name=name, chips=entry["chips"], config_name=entry["config"],
        traffic_name=entry["traffic"], config=config,
        traffic=load_traffic(entry["traffic"], here))


def metric_names(bench: dict, cell: Cell, table: str) -> list:
    """The metrics of `table` ('end_to_end' or 'per_layer') due in `cell`."""
    return [m["name"] for m in bench[table]
            if cell.name in m.get("workloads", [cell.name])]


def build_mesh(deployment: dict, devices):
    """None on one chip; else the program's own mesh over the axes the
    configuration names (`{"data": 2, "model": 2}` is the mesh PR 21 ran on
    four chips)."""
    mesh = deployment.get("mesh")
    if not mesh:
        return None
    from dynolog_tpu.parallel.sharding import MeshSpec, make_mesh

    return make_mesh(MeshSpec(**mesh), list(devices)[:deployment["chips"]])


REFERENCE_ATTRS = ("init_weights", "forward", "lower", "rel_rms",
                   "J_LOGIT_REL_RMS_LIMIT", "J_LOSS_ABS_LIMIT")
READER_ATTRS = ("NAME", "UNIT", "LAYER", "MOVES", "CELLS", "SOURCE", "BETTER",
                "read")
END_TO_END_ATTRS = ("NAME", "UNIT", "BOUND", "CELLS", "SOURCE", "BETTER",
                    "read")


def load_module(path: Path, attrs: tuple):
    """The module in the file `path`, which has to export every one of
    `attrs`. A file's name may hold dots (`step_ms_p95.capture.py`), so it
    is loaded by path."""
    if not path.is_file():
        raise BenchmarkError(f"{path} is not a file")
    spec = importlib.util.spec_from_file_location(
        f"perfbench_{path.parent.name}_" + path.stem.replace(".", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    for attr in attrs:
        if not hasattr(module, attr):
            raise BenchmarkError(f"{path} exports no {attr}")
    return module


def load_reference(config: dict, here: Path = HERE):
    """The module a configuration names under `reference`, a path under
    perfbench/: it makes the job's weights from the seed
    (`init_weights(key, job)`), says what they should compute
    (`forward(params, tokens, job, last, rounding=None)` -> the logits of
    the last positions and the loss the first step is held to; `lower`, the
    rounding of its lower-precision control; `rel_rms`) and states check J's
    two limits, which were measured for it."""
    path = (here / config["reference"]).resolve()
    if here.resolve() not in path.parents:
        raise BenchmarkError(
            f"reference {config['reference']!r} leads out of {here}: the "
            "yardstick lives with the benchmark")
    return load_module(path, REFERENCE_ATTRS)


def load_modules(folder: Path, attrs: tuple) -> dict:
    """name -> module, for every <folder>/*.py."""
    modules = {}
    for path in sorted(folder.glob("*.py")):
        module = load_module(path, attrs)
        if module.NAME != path.stem:
            raise BenchmarkError(f"{path} names its metric {module.NAME!r}")
        modules[module.NAME] = module
    return modules


def load_readers(here: Path = HERE) -> dict:
    """The per-layer metrics: one reader file each in perfbench/metrics/."""
    return load_modules(here / "metrics", READER_ATTRS)


def load_end_to_end(here: Path = HERE) -> dict:
    """The end-to-end metrics that have a file of their own in
    perfbench/end_to_end/ (those of PR 24 are `harness.end_to_end`'s)."""
    return load_modules(here / "end_to_end", END_TO_END_ATTRS)


def load_peaks(device_kind: str, here: Path = HERE) -> dict:
    peaks = _load_json(here / "peaks.json")["device_kinds"]
    if device_kind not in peaks:
        raise BenchmarkError(
            f"device kind {device_kind!r} is not in perfbench/peaks.json "
            f"({', '.join(peaks)}): an unknown device is an error, not a "
            "default")
    return peaks[device_kind]
