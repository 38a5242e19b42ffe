"""BENCHMARK.json's `per_layer` table is what `gen_benchmark.py` makes of the
reader files: every entry is its file's, accepted entries stay where they
were, new ones follow in the order of their names, and a new cell reaches
the list of every reader of its kind. No test here or elsewhere pins the
table's tail: the next reader lengthens it.
"""

import copy

import pytest

import cells
import gen_benchmark

READERS = cells.load_readers()
# The table as PR 30 left it (the ledger's accepted benchmark before PR 31),
# kept here and not asked of git: the driver's checkout is no repository.
ACCEPTED = (
    "capture_ms_max", "collect_ms", "daemon_cpu_pct", "device_idle_pct",
    "pickup_ms", "profiler_start_ms", "step_ms_p95.capture", "top_op_share",
    "write_ms", "xspace_bytes", "xspan.capture_job_cost_ms.collect",
    "xspan.capture_job_cost_ms", "xspan.capture_job_cost_ms.start",
    "xspan.capture_unaccounted_ms", "xspan.config_fetch_ms",
    "xspan.finish_ms", "xspan.idle_gap_job_excess_ms",
    "xspan.trace_clock_skew_us", "xspan.xla_collective_pct",
    "xspan.xplane_plane_skew_pct", "xspan.xspace_metadata_pct",
    "xspan.xstart_cpu_pct", "xspan.xstop_cpu_pct",
    "xspan.xstop_others_cpu_ms")


@pytest.fixture(scope="module")
def bench():
    return cells.load_benchmark()


@pytest.mark.parametrize("name", sorted(READERS))
def test_each_table_entry_is_what_its_file_generates(bench, name):
    """The cells a reader excepts included: `step_ms_p95.capture`'s entry
    was a hand edit of the table until its file said why."""
    table = {m["name"]: m for m in bench["per_layer"]}
    assert table[name] == gen_benchmark.entry_of(
        READERS[name], gen_benchmark.cell_kinds(bench))


def test_the_generator_leaves_the_accepted_prefix_where_it_was(bench):
    names = [m["name"] for m in bench["per_layer"]]
    assert tuple(names[:len(ACCEPTED)]) == ACCEPTED
    later = names[len(ACCEPTED):]
    assert later == sorted(later) and set(names) == set(READERS)
    # run again, it changes nothing
    assert gen_benchmark.per_layer(bench) == bench["per_layer"]
    # from the accepted table alone it makes the whole one, and with the
    # accepted readers alone it leaves the accepted table as it was
    before = dict(bench, per_layer=bench["per_layer"][:len(ACCEPTED)])
    assert gen_benchmark.per_layer(before) == bench["per_layer"]
    accepted = {name: READERS[name] for name in ACCEPTED}
    assert gen_benchmark.per_layer(before, accepted) == before["per_layer"]


def test_a_held_entry_is_kept_as_it_stands_and_a_new_cell_joins_its_kind(bench):
    """A cell that no list names yet is new: it joins every reader of its
    kind that does not except it, at the end of the list; nothing else of
    a held entry moves, whatever its file would generate today."""
    grown = copy.deepcopy(bench)
    grown["workloads"].append(dict(
        bench["workloads"][0], name="olmo2-1b.capture-again"))
    grown["workloads"].append(dict(
        next(w for w in bench["workloads"] if w["traffic"] == "steady"),
        name="olmo2-7b-2l.steady"))
    table = {m["name"]: m for m in gen_benchmark.per_layer(grown)}
    old = {m["name"]: m for m in bench["per_layer"]}
    for name, reader in READERS.items():
        if "workloads" not in old[name]:
            assert table[name] == old[name]  # due everywhere already
            continue
        added = [w for w, kind in (("olmo2-1b.capture-again", "capture"),
                                   ("olmo2-7b-2l.steady", "steady"))
                 if kind in reader.CELLS]
        assert table[name]["workloads"] == old[name]["workloads"] + added
    assert table["step_ms_p95.capture"]["workloads"][-1] == (
        "olmo2-1b.capture-again")
    assert "olmo2-13b-v5e4.capture" not in (
        table["step_ms_p95.capture"]["workloads"])


def test_an_excepted_cell_is_left_off_a_reader_of_every_kind():
    class Reader:
        NAME, UNIT, BETTER, SOURCE = "x_ms", "ms", "lower", "host_clock"
        LAYER, MOVES, CELLS = "device", "step_ms_p50", ('steady', 'capture')

    kinds = {"a.capture": "capture", "a.steady": "steady"}
    assert "workloads" not in gen_benchmark.entry_of(Reader, kinds)
    Reader.EXCEPT = ("a.steady",)
    assert gen_benchmark.entry_of(Reader, kinds)["workloads"] == ["a.capture"]


def test_a_reader_whose_file_went_loses_its_entry(bench):
    fewer = {n: r for n, r in READERS.items() if n != "write_ms"}
    names = [m["name"] for m in gen_benchmark.per_layer(bench, fewer)]
    assert "write_ms" not in names and len(names) == len(READERS) - 1
