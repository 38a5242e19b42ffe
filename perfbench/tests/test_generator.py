"""BENCHMARK.json's `per_layer` table is what `gen_benchmark.py` makes of the
reader files: every entry is its file's, accepted entries stay where they
were, new ones follow in the order of their names, and a new cell reaches
the list of every reader of its kind but those its configuration says it
cannot read. No test here or elsewhere pins the
table's tail: the next reader lengthens it.
"""

import copy

import pytest

import cells
import gen_benchmark

READERS = cells.load_readers()
END_TO_END = cells.load_end_to_end()
# The tables as PR 31 left them (the ledger's accepted benchmark before
# PR 32), kept here and not asked of git: the driver's checkout is no
# repository. PR 30's 24 in the order of their files, then PR 31's nine by
# name.
ACCEPTED_END_TO_END = (
    {"name": "step_ms_p50", "unit": "ms", "better": "lower", "bound": 0.01,
     "source": "host_clock"},
    {"name": "step_ms_p95", "unit": "ms", "better": "lower", "bound": 0.01,
     "source": "host_clock", "workloads": ["olmo2-1b.steady"]},
    {"name": "capture_ms_p50", "unit": "ms", "better": "lower", "bound": 0.06,
     "source": "host_clock", "workloads": [
         "olmo2-1b.capture", "olmo2-7b-2l.capture",
         "olmo2-13b-v5e4.capture"]},
    {"name": "setup_s", "unit": "s", "better": "lower", "bound": 0.25,
     "source": "host_clock"})
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
    "xspan.xstop_others_cpu_ms",
    "daemon_rss_mb", "first_capture_ms", "ipc_handoff_ms",
    "ipc_timeout_wakeup_pct", "kernel_tick_ms_p50",
    "longest_pass_tick_overlap_ms", "rpc_verb_ms", "tpu_tick_ms_max",
    "tpu_tick_ms_p50")


@pytest.fixture(scope="module")
def bench():
    return cells.load_benchmark()


@pytest.mark.parametrize("name", sorted(READERS))
def test_each_table_entry_is_what_its_file_generates(bench, name):
    """From the readers and the configurations alone, the cell a
    configuration says a reader cannot read included:
    `step_ms_p95.capture`'s entry was a hand edit of the table until PR 31,
    and the reader named the cell until PR 33."""
    table = {m["name"]: m for m in bench["per_layer"]}
    assert table[name] == gen_benchmark.entry_of(
        READERS[name], gen_benchmark.cell_kinds(bench),
        gen_benchmark.no_reading(bench, READERS))


def test_the_tables_generated_from_nothing_held_are_benchmark_json(bench):
    """Every entry of both tables, from the readers, the end-to-end files
    and the configurations: the order of a table is its history (held
    entries keep their place), so it is compared by name."""
    bare = dict(bench, per_layer=[], end_to_end=list(ACCEPTED_END_TO_END))
    made = {m["name"]: m for m in gen_benchmark.per_layer(bare)}
    assert made == {m["name"]: m for m in bench["per_layer"]}
    assert gen_benchmark.end_to_end(bare) == bench["end_to_end"]


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
    kind that its configuration does not name under `no_reading`, at the end
    of the list; nothing else of
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
    # a second cell of the configuration that cannot read it stays off too
    grown["workloads"].append(dict(
        bench["workloads"][-1], name="olmo2-13b-v5e4.capture-again"))
    table = {m["name"]: m for m in gen_benchmark.per_layer(grown)}
    assert "olmo2-13b-v5e4.capture-again" not in (
        table["step_ms_p95.capture"]["workloads"])
    assert table["collect_ms"]["workloads"][-1] == (
        "olmo2-13b-v5e4.capture-again")


def named_unread(monkeypatch, bench, names: dict) -> dict:
    """`bench`'s first configuration, naming `names` under `no_reading`."""
    first = bench["workloads"][0]["config"]
    load_config = cells.load_config
    monkeypatch.setattr(cells, "load_config", lambda name, *a: dict(
        load_config(name, *a), **({"no_reading": names}
                                  if name == first else {})))
    return gen_benchmark.no_reading(bench, READERS)


def test_a_configuration_takes_its_cells_off_the_readers_it_names(
        monkeypatch, bench):
    """Every cell of the configuration, and no cell of another."""
    unread = named_unread(monkeypatch, bench, {"collect_ms": "why not"})
    kinds = gen_benchmark.cell_kinds(bench)
    mine = [w["name"] for w in bench["workloads"]
            if w["config"] == bench["workloads"][0]["config"]]
    entry = gen_benchmark.entry_of(READERS["collect_ms"], kinds, unread)
    assert entry["workloads"] == [
        w for w, kind in kinds.items() if kind == "capture" and w not in mine]
    other = gen_benchmark.entry_of(READERS["write_ms"], kinds, unread)
    assert other["workloads"] == [
        w for w, kind in kinds.items() if kind == "capture"]


def test_a_no_reading_name_that_is_no_readers_is_an_error(monkeypatch, bench):
    with pytest.raises(cells.BenchmarkError, match="no reader"):
        named_unread(monkeypatch, bench, {"step_ms_p95.captur": "a typo"})


def test_a_reader_due_everywhere_cannot_be_named(monkeypatch, bench):
    """It has no list to leave a cell off: later cells owe it too."""
    everywhere = next(n for n, r in READERS.items()
                      if gen_benchmark.due_everywhere(r))
    assert "workloads" not in gen_benchmark.entry_of(
        READERS[everywhere], gen_benchmark.cell_kinds(bench), {})
    with pytest.raises(cells.BenchmarkError, match="due in every cell"):
        named_unread(monkeypatch, bench, {everywhere: "nothing to read"})


def test_a_reader_whose_file_went_loses_its_entry(bench):
    fewer = {n: r for n, r in READERS.items() if n != "write_ms"}
    names = [m["name"] for m in gen_benchmark.per_layer(bench, fewer)]
    assert "write_ms" not in names and len(names) == len(READERS) - 1


# ------------------------------------------------------------ end to end


def test_the_accepted_end_to_end_entries_stand_as_they_were(bench):
    held = bench["end_to_end"][:len(ACCEPTED_END_TO_END)]
    assert held == list(ACCEPTED_END_TO_END)
    # key for key in the same order: byte for byte once dumped
    assert [list(m) for m in held] == [list(m) for m in ACCEPTED_END_TO_END]


@pytest.mark.parametrize("name", sorted(END_TO_END))
def test_each_later_end_to_end_entry_is_what_its_file_generates(bench, name):
    table = {m["name"]: m for m in bench["end_to_end"]}
    metric = END_TO_END[name]
    assert table[name] == gen_benchmark.end_to_end_entry_of(
        metric, gen_benchmark.cell_kinds(bench))
    assert list(table[name])[:5] == ["name", "unit", "better", "bound", "source"]
    assert 0.01 <= metric.BOUND <= 0.25
    assert metric.SOURCE in ("host_clock", "device_trace")
    # every cell that reports it is of a kind the file reads
    kinds = gen_benchmark.cell_kinds(bench)
    assert all(kinds[w] in metric.CELLS
               for w in table[name].get("workloads", kinds))


def test_the_generator_appends_end_to_end_files_and_keeps_what_is_held(bench):
    names = [m["name"] for m in bench["end_to_end"]]
    later = names[len(ACCEPTED_END_TO_END):]
    assert later == sorted(END_TO_END) and "derived_ms_p50" in later
    assert gen_benchmark.end_to_end(bench) == bench["end_to_end"]
    before = dict(bench, end_to_end=list(ACCEPTED_END_TO_END))
    assert gen_benchmark.end_to_end(before) == bench["end_to_end"]
    assert gen_benchmark.end_to_end(before, {}) == list(ACCEPTED_END_TO_END)
    # a held entry stays as it stands, whatever its file would say today
    class Tighter:
        NAME, UNIT, BETTER, SOURCE = "derived_ms_p50", "s", "lower", "host_clock"
        BOUND, CELLS = 0.01, ("capture",)

    assert gen_benchmark.end_to_end(
        bench, {"derived_ms_p50": Tighter}) == bench["end_to_end"]


def test_every_per_layer_entry_moves_a_metric_its_cells_report(bench):
    kinds = gen_benchmark.cell_kinds(bench)
    reports = {m["name"]: set(m.get("workloads", kinds))
               for m in bench["end_to_end"]}
    for entry in bench["per_layer"]:
        assert set(entry.get("workloads", kinds)) <= reports[entry["moves"]], (
            entry["name"])


def test_the_steady_cell_reports_nothing_of_the_derive_layer(bench):
    steady = cells.load_cell("olmo2-1b.steady")
    assert "derived_ms_p50" not in cells.metric_names(
        bench, steady, "end_to_end")
    due = cells.metric_names(bench, steady, "per_layer")
    table = {m["name"]: m for m in bench["per_layer"]}
    assert not [n for n in due if table[n]["layer"] == "derive"]
