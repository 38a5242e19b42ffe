"""What decides `correct`: eight checks, none of which can race.

Every check is a count or a range test made after the window has closed
(behind a bounded wait where a series has to arrive), or a comparison of
two reductions of the same bytes. None reads a gauge's momentary value,
asks whether a gauge moved, or compares a rate with a rate.

    J   the job's numbers against the plain float32 reference
    S1  step telemetry arrived in the daemon's store and is the job's
    S2  the chip's HBM rows are the chip's
    C1  every acknowledged capture exists, whole and readable
    C2  every capture holds the steps of its window
    C3  the product's summary of a capture equals the plain reducer's
    C4  nothing is left running
    C5  what the export child wrote beside every capture is there, whole,
        and says what the bytes say

A check is {"name", "ok", "compared": [{"what", "value", "limit", "ok"}]}:
every number compared is printed beside its limit, in every run.
"""

from __future__ import annotations

import glob
import gzip
import json
import math
import os
import time
import zlib

import xplane

# Check J's form; its two limits are stated by the module that a
# configuration names under `reference`, beside the readings of that block
# they were set from.
J_POSITIONS = 256
S1_MARGIN = 0.05
# The device plane's first execution starts with the session and its last is
# cut at the stop (the chip showed 132, 132, 132, 98 ms in one window), so
# boundary steps are split across the window's edges: against the steps the
# job COMPLETED between the marks the plane read +0 or +1 in 73 captures
# (PR 24). Two either way still fails a wrong window, and a plane with no
# execution on it fails as an empty one, unless the job's own count is 0: a
# pass lasts 134-167 ms and a window 500, so only a stall of the job covers a
# whole window, and a job that ran nothing owes the device plane nothing. The
# chip showed it once in 44 captures (a 1.9 s pass over three captures whose
# planes held 2, 0 and 1 executions, the middle one no op at all; the shim,
# the daemon and the CLI went on in time). Such a capture is still held to
# C1's bytes and readability, and to no more than the tolerance on its plane.
C2_TOLERANCE = 2
CHILDREN_GONE_S = 120.0
# C5 (c): the summary rounds an op's time to a microsecond (half of one
# either way) and sums picoseconds where ProfileData hands whole nanoseconds
# rounded down (up to one nanosecond an event above the plain sum, C3's
# bound). So a sound gap less 1 ns an event lies in [-0.0005, 0.0005] ms; the
# chip's largest readings are in PERF.md section 2. A time one microsecond
# off in a row of the table fails.
C5_SUMMARY_MS_LIMIT = 0.001
DERIVED = (".summary.json", ".trace.json.gz")


def part(what: str, value, limit, ok: bool) -> dict:
    return {"what": what, "value": value, "limit": limit, "ok": bool(ok)}


def check(name: str, parts: list) -> dict:
    return {"name": name, "ok": all(p["ok"] for p in parts), "compared": parts}


# ------------------------------------------------------------------- J


def check_j(j: dict, logit_limit: float, loss_limit: float) -> dict:
    """`j` is the run's three readings; the limits are those of the module
    that made the weights and the reference (`cells.load_reference`)."""
    gap = abs(j["step_loss"] - j["ref_loss"])
    return check("J", [
        part("||job logits - reference|| / ||reference||, last "
             f"{J_POSITIONS} positions", j["logit_rel_rms"],
             f"<= {logit_limit}", j["logit_rel_rms"] <= logit_limit),
        part("|first step's loss - reference loss|", gap,
             f"<= {loss_limit}", gap <= loss_limit),
    ])


def __getattr__(name: str):
    """`tests/test_sharded_job.py` (tier-1, not a `benchmark` PR's to edit)
    reads the dense block's limits as `checks.J_*`: handed through from that
    block's module, nothing kept here. Goes with that test's next edit
    (PERF.md Open 0)."""
    if name in ("J_LOGIT_REL_RMS_LIMIT", "J_LOSS_ABS_LIMIT"):
        import cells

        return getattr(cells.load_reference({"reference": "reference.py"}),
                       name)
    raise AttributeError(f"module 'checks' has no attribute {name!r}")


# ------------------------------------------------------------------ S1


def telemetry_names(job_id: int) -> tuple:
    return (f"job{job_id}.steps_per_sec", f"job{job_id}.step_time_p50_ms")


def samples_needed(step_ends: list, start_t: float, seconds: float,
                   slot_s: float) -> int:
    """How many samples the store has to hold, from the job's own steps.

    The shim's poll thread reports at the end of a loop pass when the report
    interval has gone by AND the job stepped since its last report; a pass
    lasts as long as the capture it serves. So cut the window into slots of
    (interval + longest capture): every slot in which the job completed a
    step owes a sample. A slot in which it completed none owes nothing: a
    stalled job has no telemetry due (one run of 13 on the chip lost 8.4 s
    of its window in a few passes, PR 24). Three samples of slack: two for
    the window's edges, one for a stall's."""
    slots = max(math.floor(seconds / slot_s), 1)
    live = {int((t - start_t) / slot_s) for t in step_ends
            if start_t <= t < start_t + slots * slot_s}
    return max(len(live) - 3, 1)


def wait_for_telemetry(run) -> dict:
    """The bounded wait of S1: at most 2 x report_interval_s after the
    window, the job stepping all the while; returns the store's series."""
    rec = run.record
    interval = run.cell.config["shim"]["report_interval_s"]
    longest = max(rec["capture_ms"], default=0.0) / 1e3
    rec["s1_needed"] = samples_needed(
        [t for t, _ in run.steps], rec["window_start"], run.seconds,
        interval + longest)
    rate = telemetry_names(run.job_id)[0]
    start_ms = int(rec["window_start"] * 1e3)
    deadline = time.time() + 2 * interval
    while True:
        store = run.daemon.query(telemetry_names(run.job_id), start_ms)
        have = len(store.get(rate, {}).get("values", []))
        if have >= rec["s1_needed"] or time.time() > deadline:
            return store
        until = time.time() + 0.25
        run.step_while(lambda: time.time() < until)


def check_s1(run, store: dict) -> dict:
    """A rate is finite and positive, or it is 0 and true: the shim reports
    0 once the job has completed no step for two report intervals (a
    stalled job is what a step-rate trigger has to see), so a 0 passes
    where the job's own passes show no step completed in the report
    interval before the sample's stamp, and nowhere else. The chip stalled
    a steady job for 3.0 s once in 61 runs (PR 32) and for 2.65 and 3.56 s
    in PR 26 and 29; each failed this part for the product telling the
    truth."""
    rec = run.record
    rate, p50 = telemetry_names(run.job_id)
    series = store.get(rate, {})
    rates, stamps = series.get("values", []), series.get("timestamps", [])
    interval = run.cell.config["shim"]["report_interval_s"]
    true_zeros = [
        t for v, t in zip(rates, stamps)
        if v == 0 and not steps_between(run.steps, t / 1e3 - interval, t / 1e3)]
    good = [v for v in rates if math.isfinite(v) and v > 0]
    step_ms = [ms for _, ms in run.steps]
    lo, hi = min(step_ms) * (1 - S1_MARGIN), max(step_ms) * (1 + S1_MARGIN)
    p50s = store.get(p50, {}).get("values", [])
    outside = [v for v in p50s if not lo <= v <= hi]
    rec["telemetry_stamps_ms"] = stamps
    return check("S1", [
        part(f"samples of {rate} stamped since the window opened", len(rates),
             f">= {rec['s1_needed']}", len(rates) >= rec["s1_needed"]),
        part(f"of them finite and positive, or 0 with no step of the job in "
             f"the {interval:g} s before the stamp ({len(true_zeros)})",
             len(good) + len(true_zeros), f"== {len(rates)}",
             len(good) + len(true_zeros) == len(rates)),
        part(f"samples of {p50} outside the job's own step times "
             f"[{lo:.2f}, {hi:.2f}] ms", len(outside) if p50s else "no samples",
             "== 0", bool(p50s) and not outside),
    ])


# ------------------------------------------------------------------ S2


def check_s2(run) -> dict:
    lo_total, hi_total = run.peaks["hbm_total_bytes_range"]
    names = [f"tpu{i}.hbm_{kind}_bytes"
             for i in range(run.cell.chips) for kind in ("total", "used")]
    store = run.daemon.query(names)
    parts = []
    for i in range(run.cell.chips):
        totals = store.get(f"tpu{i}.hbm_total_bytes", {}).get("values", [])
        used = store.get(f"tpu{i}.hbm_used_bytes", {}).get("values", [])
        total = totals[-1] if totals else None
        parts.append(part(
            f"tpu{i}.hbm_total_bytes", total, f"{lo_total:g}..{hi_total:g}",
            total is not None and lo_total <= total <= hi_total))
        peak = max(used) if used else None
        floor = run.record["step_argument_bytes"]
        parts.append(part(
            f"max of tpu{i}.hbm_used_bytes ({len(used)} samples)", peak,
            f"{floor}..{total}", peak is not None and total is not None
            and floor <= peak <= total))
        if peak is not None:
            run.record["hbm_used_max"] = max(
                run.record.get("hbm_used_max", 0), peak)
    return check("S2", parts)


# ------------------------------------------------------------ C1 C2 C3


def steps_between(steps: list, start_t: float, end_t: float) -> int:
    """Steps the job completed in (start_t, end_t]."""
    return sum(1 for t, _ in steps if start_t < t <= end_t)


def capture_window(manifest: dict) -> tuple:
    """(profiler started, profiler stop called), seconds on the host clock,
    from the shim's marks: the session opens at the end of profiler_start
    and the shim sleeps the configured window before it calls stop."""
    timing = manifest["timing"]
    start = (manifest["started_ms"] + timing["profiler_start_ms"]) / 1e3
    return start, start + int(
        manifest["config"]["ACTIVITIES_DURATION_MSECS"]) / 1e3


def compare_summaries(product_ops: dict, plain_ops: dict) -> list:
    """C3's comparison. product_ops: op -> (total_ps, count) from
    dynolog_tpu.trace; plain_ops: op -> [total_ns, count] from the plain
    reducer. Counts are equal; times are equal as far as ProfileData lets
    them be: it hands each event's duration in whole nanoseconds, rounded
    down, so an op's picoseconds lie from the plain sum up to one nanosecond
    an event above it. Returns the ops that differ, with both readings."""
    differ = []
    for op in sorted(set(product_ops) | set(plain_ops)):
        ps, n = product_ops.get(op, (None, None))
        ns, m = plain_ops.get(op, (None, None))
        same = (ps is not None and ns is not None and n == m
                and 0 <= ps - ns * 1e3 < 1e3 * max(n, 1))
        if not same:
            differ.append((op, (ps, n), (ns, m)))
    return differ


def product_summary(data: bytes, plane_name: str) -> dict:
    """The system under test: dynolog_tpu.trace's per-op table of a plane."""
    from dynolog_tpu import trace as trace_mod

    for plane in trace_mod.summarize_xplane_bytes(data, group=False):
        if plane.name == plane_name:
            return {k: (v.total_ps, v.count) for k, v in plane.ops.items()}
    return {}


def check_captures(run) -> list:
    """C1 and C2 over every capture whose CLI exited 0, C3 on the last that
    holds device events."""
    c1, c2, c3 = [], [], []
    module = run.cell.job["step_module"]
    acked = [c for c in run.record["captures"] if c["cli_rc"] == 0]
    last_bytes = last_whole = summarized = None
    for cap in acked:
        label = f"capture {cap['k']}"
        if not cap["ok"]:
            c1.append(part(f"{label} manifest", cap.get("error"), "status ok",
                           False))
            continue
        manifest = cap["manifest"]
        path = xplane.find_xplane(manifest["trace_dir"])
        size = os.path.getsize(path) if path else None
        want = manifest["timing"].get("xspace_bytes")
        whole = size is not None and size == want
        c1.append(part(f"{label} .xplane.pb bytes", size, f"== {want}", whole))
        if not whole:
            continue
        with open(path, "rb") as f:
            data = f.read()
        try:
            profile = xplane.load_bytes(data)
        except Exception as e:  # noqa: BLE001 - unreadable IS the finding
            c1.append(part(f"{label} readable by ProfileData", repr(e),
                           "readable", False))
            continue
        cap["xplane_path"] = path  # whole and readable: C5 owes its files
        last_whole = (cap, profile)
        start_t, stop_t = capture_window(manifest)
        inside = steps_between(run.steps, start_t, stop_t)
        cap["steps_in_window"] = inside
        stalled = inside == 0
        floor = ">= 0 (the job completed no step in this window)" \
            if stalled else "> 0"
        for i in range(run.cell.chips):
            name = xplane.device_plane_name(i)
            plane = xplane.find_plane(profile, name)
            reduced = xplane.reduce_plane(plane)
            events = reduced.events if reduced else 0
            c1.append(part(f"{label} {name} XLA op events", events, floor,
                           events > 0 or stalled))
            if not reduced and not stalled:
                continue
            seen = xplane.count_executions(plane, module) \
                if plane is not None else 0
            cap.setdefault("executions", []).append(seen)
            if reduced:
                cap.setdefault("device_ns", []).append(
                    [reduced.busy_ns, reduced.span_ns])
            c2.append(part(
                f"{label} {name} executions of {module}", seen,
                f"{inside} +- {C2_TOLERANCE}, {floor}",
                (seen > 0 or stalled)
                and abs(seen - inside) <= C2_TOLERANCE))
        if len(cap.get("device_ns", [])) == run.cell.chips:
            last_bytes = (label, data, profile)
            summarized = last_whole
    if last_bytes is None:
        c3.append(part("a capture to summarize", None,
                       "one whole capture with device events", False))
    else:
        label, data, profile = last_bytes
        name = xplane.device_plane_name(0)
        plain = xplane.reduce_plane(xplane.find_plane(profile, name))
        plain_ops = plain.ops if plain else {}
        differ = compare_summaries(product_summary(data, name), plain_ops)
        c3.append(part(
            f"{label} ops whose (time, count) differ between "
            f"dynolog_tpu.trace and the plain reducer, of {len(plain_ops)}",
            differ[:3] if differ else 0, "== 0, of > 0",
            bool(plain_ops) and not differ))
    # where C3 had no capture (and fails), C5 reads the last whole one
    run.summarized = summarized or last_whole
    if not acked:
        c1.append(part("captures acknowledged by the CLI", 0, "> 0", False))
    return [check("C1", c1), check("C2", c2 or [
        part("captures whose window could be read", 0, "> 0", False)]),
        check("C3", c3)]


# ------------------------------------------------------------------ C5


def read_derived(cap: dict) -> None:
    """One look, after the convert children are gone, at what the export
    child wrote beside a capture's .xplane.pb: `cap["derived"]` (mtime and
    size of each file, or None; what ends in .tmp beside them) and, where
    both are there, `cap["derived_ms"]`: the later mtime - the spawn of the
    capture's `dyno gputrace`, the origin of `capture_ms`."""
    path = cap.get("xplane_path")
    if path is None and cap["ok"]:
        path = xplane.find_xplane(cap["manifest"]["trace_dir"])
    if path is None:
        return
    stem = path[:-len(".xplane.pb")]
    found = {}
    for ext in DERIVED:
        try:
            st = os.stat(stem + ext)
            found[ext] = {"path": stem + ext, "mtime": st.st_mtime,
                          "bytes": st.st_size}
        except OSError:
            found[ext] = None
    cap["derived"] = dict(found, tmp=sorted(
        os.path.basename(p)
        for p in glob.glob(os.path.join(os.path.dirname(path), "*.tmp"))))
    if cap["ok"] and all(found.values()):
        cap["derived_ms"] = (max(f["mtime"] for f in found.values())
                             - cap["spawn_t"]) * 1e3


def gunzip_to_end(path: str) -> int | str:
    """Streams the file through gzip to its end, where Python holds the
    trailer's CRC and length against what it read: the bytes it held, or
    the sentence that says where it broke."""
    total = 0
    try:
        with gzip.open(path, "rb") as f:
            while chunk := f.read(1 << 20):
                total += len(chunk)
    except (OSError, EOFError, zlib.error) as e:
        return f"{type(e).__name__}: {e}"
    return total


def chrome_events(doc: dict) -> dict:
    """(process name, thread name) -> complete events, of a Chrome trace:
    a plane is a process, a line a thread, both named by metadata events."""
    process, thread, count = {}, {}, {}
    for ev in doc["traceEvents"]:
        if ev.get("ph") == "M" and ev.get("name") == "process_name":
            process[ev["pid"]] = ev["args"]["name"]
        elif ev.get("ph") == "M" and ev.get("name") == "thread_name":
            thread[ev["pid"], ev["tid"]] = ev["args"]["name"]
        elif ev.get("ph") == "X":
            key = (ev["pid"], ev["tid"])
            count[key] = count.get(key, 0) + 1
    out: dict = {}
    for (pid, tid), n in count.items():
        key = (process.get(pid), thread.get((pid, tid)))
        out[key] = out.get(key, 0) + n
    return out


def compare_top_ops(rows: list, plain_ops: dict) -> tuple:
    """C5 (c). rows: the summary's `top_ops`; plain_ops: group ->
    [total_ns, count] from the plain reducer over the same planes. Returns
    (the groups that differ, with both readings; the largest
    |total_ms - plain| less 1 ns an event, in ms, over the groups both
    have)."""
    product = {row["op"]: (row["total_ms"], row["count"]) for row in rows}
    differ, worst = [], 0.0
    for op in sorted(set(product) | set(plain_ops)):
        ms, n = product.get(op, (None, None))
        ns, m = plain_ops.get(op, (None, None))
        if ms is None or ns is None:
            differ.append((op, (ms, n), (ns, m)))
            continue
        gap = abs(ms - ns / 1e6) - 1e-6 * m
        worst = max(worst, gap)
        if n != m or gap > C5_SUMMARY_MS_LIMIT:
            differ.append((op, (ms, n), (ns, m)))
    return differ, worst


def check_c5(run) -> dict:
    """After the convert children are gone. (a) every capture whose
    artifact C1 found whole and readable has both derived files and no
    .tmp beside them; (b) every .trace.json.gz gunzips to its end, and the
    one of the capture C3 summarized holds, for each device plane, as many
    events on "XLA Ops" as the plain reducer counts there; (c) that
    capture's .summary.json, `top_ops`, against the plain reducer."""
    owed = [c for c in run.record["captures"] if "xplane_path" in c]
    lacking, broken, gz_bytes = [], [], 0
    for cap in owed:
        derived = cap.get("derived") or {}
        missing = [ext for ext in DERIVED if not derived.get(ext)]
        if missing or derived.get("tmp"):
            lacking.append((cap["k"], missing, derived.get("tmp")))
        trace = derived.get(DERIVED[1])
        if trace:
            held = gunzip_to_end(trace["path"])
            if isinstance(held, int) and held > 0:
                gz_bytes += held
                trace["json_bytes"] = held
            else:
                broken.append((cap["k"], held))
    parts = [
        part(f"captures with a whole artifact, of {len(owed)}, that lack a "
             "derived file (.summary.json, .trace.json.gz) or hold a .tmp",
             lacking[:3] if lacking else 0, "== 0, of > 0",
             bool(owed) and not lacking),
        part(".trace.json.gz files that do not gunzip to their end (CRC, "
             f"length); the others hold {gz_bytes} bytes of JSON",
             broken[:3] if broken else 0, "== 0", not broken)]
    if run.summarized is None:
        parts.append(part("a capture whose derived files to read", None,
                          "one whole capture", False))
        return check("C5", parts)
    cap, profile = run.summarized
    label = f"capture {cap['k']}"
    derived = cap.get("derived") or {}
    # the device planes C1 found events on, each with its plain count
    planes = {}
    for i in range(run.cell.chips):
        plane = xplane.find_plane(profile, xplane.device_plane_name(i))
        reduced = xplane.reduce_plane(plane)
        if reduced is not None:
            planes[plane] = reduced.events
    try:
        with gzip.open(derived[DERIVED[1]]["path"], "rt") as f:
            events = chrome_events(json.load(f))
        for plane, want in planes.items():
            got = events.get((plane.name, xplane.XLA_OPS), 0)
            parts.append(part(
                f"{label} .trace.json.gz events of {plane.name} "
                f'"{xplane.XLA_OPS}"', got, f"== {want}", got == want))
        parts.append(part(
            f"{label} .trace.json.gz parses; complete events in all",
            sum(events.values()), "> 0", sum(events.values()) > 0))
    except Exception as e:  # noqa: BLE001 - unreadable IS the finding
        parts.append(part(f"{label} .trace.json.gz parses as a Chrome trace",
                          repr(e), "parses", False))
    try:
        with open(derived[DERIVED[0]]["path"]) as f:
            rows = json.load(f)["top_ops"]
        plain_ops = xplane.reduce_groups(profile, list(planes))
        differ, worst = compare_top_ops(rows, plain_ops)
        parts.append(part(
            f"{label} .summary.json top_ops rows whose (total_ms, count) "
            f"differ from the plain reducer's, of {len(plain_ops)} over "
            f"{len(planes) or 'all'} planes",
            differ[:3] if differ else 0, "== 0, of > 0",
            bool(plain_ops) and not differ))
        parts.append(part(
            f"{label} largest |total_ms - plain| less 1 ns an event, ms",
            worst, f"<= {C5_SUMMARY_MS_LIMIT}",
            worst <= C5_SUMMARY_MS_LIMIT))
    except Exception as e:  # noqa: BLE001 - unreadable IS the finding
        parts.append(part(f"{label} .summary.json parses and has top_ops",
                          repr(e), "parses", False))
    return check("C5", parts)


# ------------------------------------------------------------------ C4


def _alive(proc) -> bool:
    import psutil

    try:
        return proc.status() != psutil.STATUS_ZOMBIE
    except psutil.Error:
        return False


def wait_children_gone(skip: set, timeout_s: float = CHILDREN_GONE_S) -> float | None:
    """Seconds until this process has no child but those in `skip` (the
    shim's convert children run detached at nice 19); None on timeout."""
    import psutil

    me = psutil.Process()
    t0 = time.time()
    while time.time() - t0 < timeout_s:
        left = [c for c in me.children(recursive=True)
                if c.pid not in skip and _alive(c)]
        if not left:
            return time.time() - t0
        time.sleep(0.2)
    return None


def check_c4(children_gone_s, daemon_clean: bool) -> dict:
    return check("C4", [
        part("seconds until the shim's convert children were gone",
             children_gone_s, f"<= {CHILDREN_GONE_S:g}",
             children_gone_s is not None),
        part("dynologd exited on SIGTERM within 10 s", daemon_clean, "True",
             daemon_clean),
    ])
