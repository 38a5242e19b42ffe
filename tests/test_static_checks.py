"""Tier-1 gate for tools/dynolint: green on the real tree, and every pass
fails closed on the defect class it exists for.

Mutation tests copy the minimal file set into a temp root, perturb one
thing (reorder a wire field, widen an i32, drop a lock, sleep on a hot
path, ...), and assert the corresponding pass produces a diagnostic with
the precise file and line. A checker that stays green on its own mutation
is a broken gate — this file is what keeps the suite honest.

No jax, no C++ build: pure-Python, runs in the default tier-1 lane and in
the CI dynolint job (with --noconftest).
"""

from __future__ import annotations

import json
import pathlib
import shutil
import subprocess
import sys
import time

REPO = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))  # for --noconftest runs

from tools.dynolint import (  # noqa: E402
    callgraph,
    compat,
    concurrency,
    contract,
    durability,
    flags,
    lockgraph,
    py_hotpath,
    reach,
    wire_schema,
)

WIRE_FILES = [
    "src/tracing/IPCMonitor.h",
    "src/ipc/FabricManager.h",
    "dynolog_tpu/client/ipc.py",
    "dynolog_tpu/client/shim.py",
]


def _copy_subtree(tmp: pathlib.Path, rels: list[str]) -> pathlib.Path:
    for rel in rels:
        dst = tmp / rel
        dst.parent.mkdir(parents=True, exist_ok=True)
        shutil.copy(REPO / rel, dst)
    return tmp


def _mutate(root: pathlib.Path, rel: str, old: str, new: str) -> int:
    """Replace old->new (must occur exactly once); returns the 1-based
    line where the replacement landed."""
    path = root / rel
    text = path.read_text()
    assert text.count(old) == 1, f"mutation anchor not unique in {rel}"
    pos = text.index(old)
    path.write_text(text.replace(old, new))
    return text.count("\n", 0, pos) + 1


def _findings(mod, root: pathlib.Path):
    return mod.run(root)


def _assert_flagged(findings, rule: str, file: str, line: int | None = None):
    hits = [f for f in findings if f.rule == rule and f.file == file]
    assert hits, (
        f"expected a [{rule}] diagnostic in {file}; got: "
        + "; ".join(f"{f.location()} [{f.rule}]" for f in findings))
    if line is not None:
        assert any(f.line == line for f in hits), (
            f"expected [{rule}] at {file}:{line}; got lines "
            f"{[f.line for f in hits]}")
    # Every diagnostic must carry a real location.
    for f in hits:
        assert f.line >= 1 and f.file


# -- green on the real tree ---------------------------------------------


def test_wire_schema_green_on_tree():
    assert _findings(wire_schema, REPO) == []


def test_cpp_concurrency_green_on_tree():
    assert _findings(concurrency, REPO) == []


def test_py_hotpath_green_on_tree():
    assert _findings(py_hotpath, REPO) == []


def test_cli_exits_zero_on_tree():
    proc = subprocess.run(
        [sys.executable, "-m", "tools.dynolint", "--format=json"],
        cwd=REPO, capture_output=True, text=True)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    doc = json.loads(proc.stdout)
    assert doc["findings"] == []


# -- pass 1: wire-schema mutations --------------------------------------


def test_wire_reordered_fields_flagged(tmp_path):
    root = _copy_subtree(tmp_path, WIRE_FILES)
    # Swap ClientSubscribe's (pid, reserved) i32 pair after jobId: the C
    # layout shifts every offset while the Python format stands still.
    line = _mutate(
        root, "src/tracing/IPCMonitor.h",
        "struct ClientSubscribe {\n  int32_t pid;\n"
        "  int32_t reserved; // must be 0 on the wire (future version/flags)\n"
        "  int64_t jobId;",
        "struct ClientSubscribe {\n  int64_t jobId;\n  int32_t pid;\n"
        "  int32_t reserved; // must be 0 on the wire (future version/flags)")
    findings = _findings(wire_schema, root)
    _assert_flagged(findings, "field-offset", "dynolog_tpu/client/ipc.py")
    # The C side of the message names the struct and each field's OWN
    # header line: jobId (now first, line+1) mismatches the 'i' code by
    # size; pid (line+2) lands at a drifted offset.
    assert any("ClientSubscribe.jobId" in f.message and
               f"IPCMonitor.h:{line + 1}" in f.message
               for f in findings if f.rule == "field-size"), findings
    assert any("ClientSubscribe.pid" in f.message and
               f"IPCMonitor.h:{line + 2}" in f.message
               for f in findings if f.rule == "field-offset"), findings


def test_wire_widened_i32_flagged(tmp_path):
    root = _copy_subtree(tmp_path, WIRE_FILES)
    line = _mutate(root, "src/tracing/IPCMonitor.h",
                   "  int32_t configType;", "  int64_t configType;")
    findings = _findings(wire_schema, root)
    _assert_flagged(findings, "field-size", "dynolog_tpu/client/ipc.py")
    assert any("ClientRequest.configType" in f.message and
               f"IPCMonitor.h:{line}" in f.message
               for f in findings if f.rule == "field-size"), findings
    # The header's static_assert pin trips too, at its own line.
    _assert_flagged(findings, "static-assert", "src/tracing/IPCMonitor.h")


def test_wire_endianness_flagged(tmp_path):
    root = _copy_subtree(tmp_path, WIRE_FILES)
    line = _mutate(root, "dynolog_tpu/client/ipc.py",
                   'CONTEXT = struct.Struct("<iiq")',
                   'CONTEXT = struct.Struct(">iiq")')
    findings = _findings(wire_schema, root)
    _assert_flagged(findings, "endianness", "dynolog_tpu/client/ipc.py", line)


def test_wire_reserved_must_pack_zero(tmp_path):
    root = _copy_subtree(tmp_path, WIRE_FILES)
    line = _mutate(root, "dynolog_tpu/client/ipc.py",
                   "payload = SUBSCRIBE.pack(pid or os.getpid(), 0, job_id)",
                   "payload = SUBSCRIBE.pack(pid or os.getpid(), 1, job_id)")
    findings = _findings(wire_schema, root)
    _assert_flagged(findings, "reserved-nonzero",
                    "dynolog_tpu/client/ipc.py", line)


def test_wire_pack_arity_flagged(tmp_path):
    root = _copy_subtree(tmp_path, WIRE_FILES)
    line = _mutate(root, "dynolog_tpu/client/ipc.py",
                   "payload = CONTEXT.pack(device, pid or os.getpid(), job_id)",
                   "payload = CONTEXT.pack(device, job_id)")
    findings = _findings(wire_schema, root)
    _assert_flagged(findings, "pack-arity", "dynolog_tpu/client/ipc.py", line)


# -- pass 2: concurrency mutations --------------------------------------


def test_cpp_dropped_guarded_by_flagged(tmp_path):
    # The per-shard guarded member (the sharded MetricStore's Shard.frame)
    # must carry its annotation like any other guarded member.
    root = _copy_subtree(tmp_path, ["src/metrics/MetricStore.h"])
    line = _mutate(root, "src/metrics/MetricStore.h",
                   "MetricFrameMap frame; // guarded_by(mutex)",
                   "MetricFrameMap frame;")
    findings = _findings(concurrency, root)
    _assert_flagged(findings, "guarded-decl", "src/metrics/MetricStore.h",
                    line)


def test_cpp_guarded_by_unknown_mutex_flagged(tmp_path):
    root = _copy_subtree(tmp_path, ["src/metrics/MetricStore.h"])
    line = _mutate(root, "src/metrics/MetricStore.h",
                   "MetricFrameMap frame; // guarded_by(mutex)",
                   "MetricFrameMap frame; // guarded_by(nonexistent_)")
    findings = _findings(concurrency, root)
    _assert_flagged(findings, "guarded-decl", "src/metrics/MetricStore.h",
                    line)


def test_cpp_missing_shard_lock_flagged(tmp_path):
    # Sharded-lock form of guarded-use: strip every per-shard lock from
    # MetricStore.cpp — every `shard.frame` touch in the store's methods
    # must light up, with the owning function named.
    root = _copy_subtree(
        tmp_path, ["src/metrics/MetricStore.h", "src/metrics/MetricStore.cpp"])
    path = root / "src/metrics/MetricStore.cpp"
    text = path.read_text()
    assert "std::lock_guard<std::mutex> lock(shard.mutex);" in text
    path.write_text(
        text.replace("std::lock_guard<std::mutex> lock(shard.mutex);", ""))
    findings = _findings(concurrency, root)
    hits = [f for f in findings
            if f.rule == "guarded-use" and f.file.endswith("MetricStore.cpp")]
    assert hits and all("shard.frame" in f.message for f in hits), findings
    # addSamples/query/listMetrics/latest all touch shard.frame lock-free
    # now.
    assert {m for f in hits
            for m in ["addSamples", "query", "listMetrics", "latest"]
            if m in f.message} == {
                "addSamples", "query", "listMetrics", "latest"}


def test_cpp_missing_table_lock_flagged(tmp_path):
    # Classic same-class guarded-use, now anchored on the interner: drop
    # MetricNameTable::intern's lock and its ids_/names_ touches flag.
    root = _copy_subtree(tmp_path, ["src/metrics/MetricStore.h"])
    path = root / "src/metrics/MetricStore.h"
    text = path.read_text()
    anchor = ("  uint32_t intern(std::string_view name) {\n"
              "    std::lock_guard<std::mutex> lock(mutex_);\n")
    assert text.count(anchor) == 1
    path.write_text(text.replace(
        anchor, "  uint32_t intern(std::string_view name) {\n"))
    findings = _findings(concurrency, root)
    hits = [f for f in findings
            if f.rule == "guarded-use" and "intern" in f.message]
    assert hits, findings
    assert any("ids_" in f.message for f in hits), findings


def test_cpp_sharded_pattern_synthetic(tmp_path):
    # The sharded idiom end to end on a synthetic pair: locked access is
    # green; the same access without the per-instance lock (or locking
    # the WRONG instance's mutex) is flagged.
    hdr = tmp_path / "src" / "Pool.h"
    hdr.parent.mkdir(parents=True)
    hdr.write_text(
        "#include <mutex>\n"
        "struct Stripe {\n"
        "  std::mutex mutex;\n"
        "  int rows = 0; // guarded_by(mutex)\n"
        "};\n"
        "class Pool {\n"
        " public:\n"
        "  void good(Stripe& stripe) {\n"
        "    std::lock_guard<std::mutex> lock(stripe.mutex);\n"
        "    stripe.rows++;\n"
        "  }\n"
        "};\n")
    assert _findings(concurrency, tmp_path) == []
    hdr.write_text(
        "#include <mutex>\n"
        "struct Stripe {\n"
        "  std::mutex mutex;\n"
        "  int rows = 0; // guarded_by(mutex)\n"
        "};\n"
        "class Pool {\n"
        " public:\n"
        "  void unlocked(Stripe& stripe) {\n"
        "    stripe.rows++;\n"
        "  }\n"
        "  void wrongInstance(Stripe& a, Stripe& b) {\n"
        "    std::lock_guard<std::mutex> lock(a.mutex);\n"
        "    b.rows++;\n"
        "  }\n"
        "};\n")
    findings = _findings(concurrency, tmp_path)
    _assert_flagged(findings, "guarded-use", "src/Pool.h", 9)
    _assert_flagged(findings, "guarded-use", "src/Pool.h", 13)
    assert any("unlocked" in f.message and "stripe.rows" in f.message
               for f in findings), findings
    assert any("wrongInstance" in f.message and "b.rows" in f.message
               for f in findings), findings


def test_cpp_sleep_in_hot_path_flagged(tmp_path):
    root = _copy_subtree(tmp_path, ["src/common/Failpoints.h"])
    line = _mutate(
        root, "src/common/Failpoints.h",
        "    return armedCount_.load(std::memory_order_relaxed) > 0;\n",
        "    std::this_thread::sleep_for(std::chrono::milliseconds(1));\n"
        "    return armedCount_.load(std::memory_order_relaxed) > 0;\n")
    findings = _findings(concurrency, root)
    _assert_flagged(findings, "hot-path", "src/common/Failpoints.h", line)
    assert any("anyArmed" in f.message for f in findings), findings


def test_cpp_lock_in_signal_handler_flagged(tmp_path):
    root = _copy_subtree(tmp_path, ["src/daemon/Main.cpp"])
    line = _mutate(
        root, "src/daemon/Main.cpp",
        "  gStop.store(true);\n}",
        "  std::lock_guard<std::mutex> lock(gStopMutex);\n"
        "  gStop.store(true);\n}")
    findings = _findings(concurrency, root)
    _assert_flagged(findings, "signal-handler", "src/daemon/Main.cpp", line)
    assert any("handleSignal" in f.message for f in findings), findings


def test_cpp_adjacent_annotation_not_inherited(tmp_path):
    # Regression: a member added directly below an annotated one must NOT
    # inherit the previous line's trailing guarded_by comment.
    hdr = tmp_path / "src" / "Probe.h"
    hdr.parent.mkdir(parents=True)
    hdr.write_text(
        "#include <mutex>\n"
        "class Probe {\n"
        " private:\n"
        "  std::mutex mutex_;\n"
        "  int annotated_ = 0; // guarded_by(mutex_)\n"
        "  int forgotten_ = 0;\n"
        "};\n")
    findings = _findings(concurrency, tmp_path)
    _assert_flagged(findings, "guarded-decl", "src/Probe.h", 6)
    assert any("forgotten_" in f.message for f in findings), findings
    assert not any("annotated_" in f.message for f in findings), findings


def test_cpp_hot_path_annotation_spans_doc_comment(tmp_path):
    # A `hot-path` marker anywhere in the function's contiguous doc
    # comment applies, however long the comment block is.
    hdr = tmp_path / "src" / "Probe.h"
    hdr.parent.mkdir(parents=True)
    hdr.write_text(
        "// hot-path: line one of a long doc comment.\n"
        "// line two.\n"
        "// line three.\n"
        "// line four.\n"
        "// line five.\n"
        "inline void spin() {\n"
        "  usleep(100);\n"
        "}\n")
    findings = _findings(concurrency, tmp_path)
    _assert_flagged(findings, "hot-path", "src/Probe.h", 7)


def test_cpp_brace_initialized_member_flagged(tmp_path):
    # Regression: `T member_{init};` must not be mistaken for an inline
    # function body and silently skipped by the annotation rules.
    hdr = tmp_path / "src" / "Probe.h"
    hdr.parent.mkdir(parents=True)
    hdr.write_text(
        "#include <mutex>\n"
        "class Probe {\n"
        " private:\n"
        "  std::mutex mutex_;\n"
        "  int braceInit_{0};\n"
        "};\n")
    findings = _findings(concurrency, tmp_path)
    _assert_flagged(findings, "guarded-decl", "src/Probe.h", 5)
    assert any("braceInit_" in f.message for f in findings), findings


def test_cpp_blocking_read_on_event_loop_flagged(tmp_path):
    # The epoll thread reads through the non-blocking state machine; a
    # netio::recvAll (blocking, loops until the full count arrives) on an
    # `// event-loop` function reinstates head-of-line blocking.
    root = _copy_subtree(
        tmp_path, ["src/rpc/EventLoopServer.h", "src/rpc/EventLoopServer.cpp"])
    line = _mutate(
        root, "src/rpc/EventLoopServer.cpp",
        "    ssize_t r = ::recv(fd, buf, sizeof(buf), 0);",
        "    netio::recvAll(fd, buf, sizeof(buf));\n"
        "    ssize_t r = ::recv(fd, buf, sizeof(buf), 0);")
    findings = _findings(concurrency, root)
    _assert_flagged(findings, "event-loop", "src/rpc/EventLoopServer.cpp",
                    line)
    assert any("onReadable" in f.message and "recvAll" in f.message
               for f in findings), findings


def test_cpp_verb_dispatch_on_event_loop_flagged(tmp_path):
    # Verb bodies belong on the worker pool: a direct handleRequest()
    # call from the parse path would run heavy verbs (gputrace trigger,
    # large queries) on the epoll thread.
    root = _copy_subtree(
        tmp_path, ["src/rpc/EventLoopServer.h", "src/rpc/EventLoopServer.cpp"])
    line = _mutate(
        root, "src/rpc/EventLoopServer.cpp",
        "  conn.state = ConnState::kProcessing;",
        "  handleRequest(request, &fatal);\n"
        "  conn.state = ConnState::kProcessing;")
    findings = _findings(concurrency, root)
    _assert_flagged(findings, "event-loop", "src/rpc/EventLoopServer.cpp",
                    line)
    assert any("tryParse" in f.message and "handleRequest" in f.message
               for f in findings), findings


def test_cpp_event_loop_synthetic_bans(tmp_path):
    # The rule end to end on a synthetic pair: a non-blocking event-loop
    # function is green; sleeps, condition waits, blocking sends and
    # processor_ dispatch each light up at their own line.
    hdr = tmp_path / "src" / "Loop.h"
    hdr.parent.mkdir(parents=True)
    hdr.write_text(
        "// event-loop: dispatch only.\n"
        "inline void onEvent(int fd) {\n"
        "  ::recv(fd, nullptr, 0, 0);\n"
        "}\n")
    assert _findings(concurrency, tmp_path) == []
    hdr.write_text(
        "#include <thread>\n"
        "// event-loop: dispatch only.\n"
        "inline void onEvent(int fd) {\n"
        "  std::this_thread::sleep_for(std::chrono::milliseconds(1));\n"
        "  cv_.wait_for(lock, std::chrono::milliseconds(1));\n"
        "  netio::sendAll(fd, buf, 4);\n"
        "  processor_(request);\n"
        "}\n")
    findings = _findings(concurrency, tmp_path)
    for line in (4, 5, 6, 7):
        _assert_flagged(findings, "event-loop", "src/Loop.h", line)
    # An identical function WITHOUT the annotation stays exempt (the rule
    # keys on the marker, not the name).
    hdr.write_text(
        "#include <thread>\n"
        "inline void onEvent(int fd) {\n"
        "  std::this_thread::sleep_for(std::chrono::milliseconds(1));\n"
        "}\n")
    assert _findings(concurrency, tmp_path) == []


def test_cpp_unsupervised_thread_waiver_stripped_flagged(tmp_path):
    # Strip the epoll-loop thread's waiver: the construction must light up
    # as an unsupervised entrypoint.
    root = _copy_subtree(
        tmp_path, ["src/rpc/EventLoopServer.h", "src/rpc/EventLoopServer.cpp"])
    path = root / "src/rpc/EventLoopServer.cpp"
    text = path.read_text()
    anchor = ("  // unsupervised-thread: the epoll loop is the transport — "
              "it cannot be\n"
              "  // restarted without dropping every connection; loop() "
              "exits only on\n"
              "  // stop() and a transport fault there is fatal by design.\n")
    assert text.count(anchor) == 1
    path.write_text(text.replace(anchor, ""))
    findings = _findings(concurrency, root)
    hits = [f for f in findings if f.rule == "unsupervised-thread"]
    assert len(hits) == 1, findings
    assert hits[0].file == "src/rpc/EventLoopServer.cpp"
    assert "std::thread construction" in hits[0].message


def test_cpp_rogue_thread_in_main_flagged(tmp_path):
    # A bare thread added to the daemon alongside the supervised ones is
    # exactly what the rule exists for.
    root = _copy_subtree(tmp_path, ["src/daemon/Main.cpp"])
    line = _mutate(
        root, "src/daemon/Main.cpp",
        "  std::vector<std::thread> threads;",
        "  std::vector<std::thread> threads;\n"
        "  std::thread rogue([] { wildLoop(); });")
    findings = _findings(concurrency, root)
    _assert_flagged(findings, "unsupervised-thread", "src/daemon/Main.cpp",
                    line + 1)


def test_cpp_unsupervised_thread_synthetic(tmp_path):
    hdr = tmp_path / "src" / "Spawn.h"
    hdr.parent.mkdir(parents=True)
    # Supervised entrypoint, an explicit waiver with a reason, and a bare
    # declaration: all green.
    hdr.write_text(
        "#include <thread>\n"
        "#include <vector>\n"
        "inline void good(Supervisor& supervisor) {\n"
        "  std::thread t([&] { supervisor.run(); });\n"
        "  // unsupervised-thread: joined before return; body cannot "
        "throw.\n"
        "  std::thread w([] { waived(); });\n"
        "  std::thread declaredOnly;\n"
        "  t.join(); w.join();\n"
        "}\n")
    assert _findings(concurrency, tmp_path) == []
    # Unsupervised construction, a reasonless waiver, and a vector
    # emplace each light up at their own line.
    hdr.write_text(
        "#include <thread>\n"
        "#include <vector>\n"
        "inline void bad() {\n"
        "  std::thread t([] { naked(); });\n"
        "  // unsupervised-thread:\n"
        "  std::thread w([] { reasonless(); });\n"
        "  std::vector<std::thread> pool;\n"
        "  pool.emplace_back([] { pooled(); });\n"
        "  std::thread b{[] { braceInit(); }};\n"
        "  t.join(); w.join(); b.join(); pool[0].join();\n"
        "}\n")
    findings = _findings(concurrency, tmp_path)
    for line in (4, 6, 8, 9):
        _assert_flagged(findings, "unsupervised-thread", "src/Spawn.h", line)
    assert any("std::vector<std::thread> pool" in f.message
               for f in findings), findings


def test_cpp_thread_vector_in_sibling_header_flagged(tmp_path):
    # workers_-style members: the vector is declared in the header, the
    # spawn happens in the .cpp — the rule must connect the two.
    hdr = tmp_path / "src" / "Pool.h"
    hdr.parent.mkdir(parents=True)
    hdr.write_text(
        "#include <thread>\n"
        "#include <vector>\n"
        "class Pool {\n"
        "  std::vector<std::thread> workers_; "
        "// unguarded(run/stop handshake)\n"
        "};\n")
    (tmp_path / "src" / "Pool.cpp").write_text(
        "#include \"src/Pool.h\"\n"
        "void Pool::run() {\n"
        "  workers_.emplace_back([] { work(); });\n"
        "}\n")
    findings = _findings(concurrency, tmp_path)
    _assert_flagged(findings, "unsupervised-thread", "src/Pool.cpp", 3)


def test_wire_span_struct_drift_flagged(tmp_path):
    # The self-trace span wire pair (ClientSpan <-> SPAN): widening the
    # pid field shifts reserved/name and trips the pin.
    root = _copy_subtree(tmp_path, WIRE_FILES)
    line = _mutate(
        root, "src/tracing/IPCMonitor.h",
        "  int64_t durUs;\n  int32_t pid;\n"
        "  int32_t reserved; // must be 0 on the wire (future version/flags)\n"
        "  char name[48]; // NUL-padded ASCII (truncated client-side)",
        "  int64_t durUs;\n  int64_t pid;\n"
        "  int32_t reserved; // must be 0 on the wire (future version/flags)\n"
        "  char name[48]; // NUL-padded ASCII (truncated client-side)")
    findings = _findings(wire_schema, root)
    assert any("ClientSpan.pid" in f.message and
               f"IPCMonitor.h:{line + 1}" in f.message
               for f in findings if f.rule == "field-size"), findings
    _assert_flagged(findings, "static-assert", "src/tracing/IPCMonitor.h")


def test_wire_span_reserved_must_pack_zero(tmp_path):
    root = _copy_subtree(tmp_path, WIRE_FILES)
    _mutate(root, "dynolog_tpu/client/ipc.py",
            "            span.pid,\n            0,",
            "            span.pid,\n            1,")
    findings = _findings(wire_schema, root)
    # The diagnostic anchors on the SPAN.pack() call expression, naming
    # the reserved argument position.
    _assert_flagged(findings, "reserved-nonzero", "dynolog_tpu/client/ipc.py")
    assert any("SPAN.pack() argument 7" in f.message
               for f in findings if f.rule == "reserved-nonzero"), findings


# -- unspanned (span-coverage) mutations ---------------------------------


SPAN_FILES = [
    "src/rpc/ServiceHandler.h",
    "src/rpc/ServiceHandler.cpp",
    "src/rpc/JsonRpcServer.h",
    "src/rpc/JsonRpcServer.cpp",
    "src/rpc/EventLoopServer.h",
]


def test_cpp_verb_dispatch_without_span_flagged(tmp_path):
    # Strip the verb span from ServiceHandler::processRequest: the verb
    # dispatcher (it reads request.at("fn")) must light up as unspanned.
    root = _copy_subtree(tmp_path, SPAN_FILES)
    path = root / "src/rpc/ServiceHandler.cpp"
    text = path.read_text()
    anchor = ("  SpanScope verbSpan(\n"
              "      \"rpc.\" + fn,\n"
              "      wireCtx ? wireCtx->traceId : 0,\n"
              "      wireCtx ? wireCtx->spanId : 0);\n")
    assert text.count(anchor) == 1
    # The config-injection path references verbSpan; neutralize it so the
    # mutant stays a pure span-removal (the lint is textual, not a build).
    text = text.replace(anchor, "")
    text = text.replace("verbSpan.childContext()", "TraceContext{0, 0}")
    path.write_text(text)
    findings = _findings(concurrency, root)
    hits = [f for f in findings if f.rule == "unspanned"]
    assert hits, findings
    assert any("processRequest" in f.message and
               f.file == "src/rpc/ServiceHandler.cpp" for f in hits), findings


def test_cpp_handoff_waiver_stripped_flagged(tmp_path):
    # JsonRpcServer::handleRequest carries an // unspanned: waiver (verb
    # spans live in the processor body); stripping it must flag the
    # worker handoff.
    root = _copy_subtree(tmp_path, SPAN_FILES)
    path = root / "src/rpc/JsonRpcServer.cpp"
    text = path.read_text()
    anchor = ("// unspanned: per-verb rpc.<fn> spans (with the request's "
              "trace_ctx) are\n// recorded inside "
              "ServiceHandler::processRequest — the processor_ body;\n"
              "// a second transport-level span here would double-count "
              "every request.\n")
    assert text.count(anchor) == 1
    path.write_text(text.replace(anchor, ""))
    findings = _findings(concurrency, root)
    hits = [f for f in findings if f.rule == "unspanned"]
    assert len(hits) == 1, findings
    assert hits[0].file == "src/rpc/JsonRpcServer.cpp"
    assert "handleRequest" in hits[0].message
    assert "worker handoff" in hits[0].message


def test_cpp_unspanned_synthetic(tmp_path):
    # The rule end to end on synthetic sources: a spanned handoff, a
    # waived one, and an unrelated function are green; a bare handoff and
    # a bare dispatcher each light up at their own line.
    hdr = tmp_path / "src" / "Serve.h"
    hdr.parent.mkdir(parents=True)
    hdr.write_text(
        "inline std::string handleRequest(const std::string& r) {\n"
        "  SpanScope span(\"scrape.render\", 0, 0);\n"
        "  return r;\n"
        "}\n"
        "// unspanned: spans recorded one level down in the verb bodies.\n"
        "inline std::string handleRequest(const std::string& r2) {\n"
        "  return r2;\n"
        "}\n"
        "inline void unrelated() {}\n")
    assert _findings(concurrency, tmp_path) == []
    hdr.write_text(
        "inline std::string handleRequest(const std::string& r) {\n"
        "  return r;\n"
        "}\n"
        "inline std::string dispatch(const json::Value& request) {\n"
        "  const std::string fn = request.at(\"fn\").asString();\n"
        "  return fn;\n"
        "}\n")
    findings = _findings(concurrency, tmp_path)
    _assert_flagged(findings, "unspanned", "src/Serve.h", 1)
    _assert_flagged(findings, "unspanned", "src/Serve.h", 4)
    assert any("worker handoff" in f.message for f in findings), findings
    assert any("verb dispatcher" in f.message for f in findings), findings


# -- unspanned: diagnose.* extension mutations ----------------------------


DIAG_FILES = [
    "src/tracing/Diagnoser.h",
    "src/tracing/Diagnoser.cpp",
]


def test_cpp_diagnose_capture_span_stripped_flagged(tmp_path):
    # Strip the enqueue span from Diagnoser::diagnoseCapture: a
    # diagnose-verb body with no diagnose.* span must light up.
    root = _copy_subtree(tmp_path, DIAG_FILES)
    path = root / "src/tracing/Diagnoser.cpp"
    text = path.read_text()
    anchor = ('  SpanScope enqueueSpan("diagnose.enqueue", ctx.traceId, '
              "ctx.spanId);\n")
    assert text.count(anchor) == 1
    text = text.replace(anchor, "")
    # Keep the mutant self-consistent (textual lint, not a build).
    text = text.replace("enqueueSpan.childContext()",
                        "TraceContext{ctx.traceId, ctx.spanId}")
    # The async worker's wait span lives in the same body — strip it too
    # so the mutant models a diagnoseCapture with NO diagnose.* span.
    assert text.count('"diagnose.capture_wait"') == 1
    text = text.replace('"diagnose.capture_wait"', '"wait"')
    path.write_text(text)
    findings = _findings(concurrency, root)
    hits = [f for f in findings if f.rule == "unspanned"]
    assert hits, findings
    assert any("diagnoseCapture" in f.message and "diagnose.*" in f.message
               for f in hits), findings


def test_cpp_diagnose_span_renamed_out_of_namespace_flagged(tmp_path):
    # A span that exists but leaves the diagnose.* namespace breaks the
    # one-trace-id join just the same — the rule requires the literal.
    root = _copy_subtree(tmp_path, DIAG_FILES)
    line = _mutate(
        root, "src/tracing/Diagnoser.cpp",
        'SpanScope enqueueSpan("diagnose.enqueue"',
        'SpanScope enqueueSpan("misc.enqueue"')
    _mutate(
        root, "src/tracing/Diagnoser.cpp",
        '"diagnose.capture_wait"', '"misc.capture_wait"')
    findings = _findings(concurrency, root)
    hits = [f for f in findings if f.rule == "unspanned"
            and f.file == "src/tracing/Diagnoser.cpp"]
    assert hits, (findings, line)


def test_cpp_diagnose_rule_green_on_tree_and_scoped(tmp_path):
    # Green on the real tree, and name-anchored: bookkeeping named
    # *Diagnosis*, `diagnoser_` members and Diagnose-classed ctors are
    # NOT verb bodies; a waived verb body is green; a bare one flags.
    assert [f for f in _findings(concurrency, REPO / "src" / "tracing")
            if f.rule == "unspanned"] == []
    hdr = tmp_path / "src" / "Diag.h"
    hdr.parent.mkdir(parents=True)
    hdr.write_text(
        "inline void diagnoseNow() {\n"
        "  SpanScope span(\"diagnose.run\", 0, 0);\n"
        "}\n"
        "// unspanned: report registry read, spans live in runEngine.\n"
        "inline void diagnoseList() {}\n"
        "inline void bumpDiagnosis(bool ok) {}\n"
        "class Diagnoser {\n"
        " public:\n"
        "  Diagnoser() {}\n"
        "  ~Diagnoser() {}\n"
        "};\n")
    assert _findings(concurrency, tmp_path) == []
    hdr.write_text(
        "inline void diagnoseNow() {\n"
        "  int x = 0;\n"
        "}\n")
    findings = _findings(concurrency, tmp_path)
    _assert_flagged(findings, "unspanned", "src/Diag.h", 1)


# -- pass 3: python hot-path mutations ----------------------------------


def _py_case(tmp_path, body: str) -> pathlib.Path:
    root = tmp_path
    mod = root / "dynolog_tpu" / "client" / "mutant.py"
    mod.parent.mkdir(parents=True, exist_ok=True)
    mod.write_text(body)
    return root


def test_py_select_without_timeout_flagged(tmp_path):
    root = _py_case(tmp_path, (
        "import select\n\n\n"
        "def wait(sock):\n"
        "    return select.select([sock], [], [])\n"))
    findings = _findings(py_hotpath, root)
    _assert_flagged(findings, "select-timeout",
                    "dynolog_tpu/client/mutant.py", 5)


def test_py_select_none_timeout_flagged(tmp_path):
    root = _py_case(tmp_path, (
        "import select\n\n\n"
        "def wait(sock):\n"
        "    return select.select([sock], [], [], None)\n"))
    findings = _findings(py_hotpath, root)
    _assert_flagged(findings, "select-timeout",
                    "dynolog_tpu/client/mutant.py", 5)


def test_py_inline_struct_pack_flagged(tmp_path):
    root = _py_case(tmp_path, (
        "import struct\n\n\n"
        "def encode(job_id):\n"
        "    return struct.pack('<q', job_id)\n"))
    findings = _findings(py_hotpath, root)
    _assert_flagged(findings, "struct-constant",
                    "dynolog_tpu/client/mutant.py", 5)


def test_py_blocking_socket_flagged(tmp_path):
    root = _py_case(tmp_path, (
        "import socket\n\n\n"
        "def make():\n"
        "    s = socket.socket(socket.AF_UNIX, socket.SOCK_DGRAM)\n"
        "    return s\n"))
    findings = _findings(py_hotpath, root)
    _assert_flagged(findings, "blocking-socket",
                    "dynolog_tpu/client/mutant.py", 5)


def test_py_unguarded_recv_flagged(tmp_path):
    root = _py_case(tmp_path, (
        "def read(sock):\n"
        "    return sock.recvfrom(4096)\n"))
    findings = _findings(py_hotpath, root)
    _assert_flagged(findings, "unguarded-recv",
                    "dynolog_tpu/client/mutant.py", 2)


# -- machine-readable output + baseline contract -------------------------


def test_json_format_and_baseline_suppression(tmp_path):
    # A mutant tree with one known finding...
    root = _py_case(tmp_path, (
        "import struct\n\n\n"
        "def encode(job_id):\n"
        "    return struct.pack('<q', job_id)\n"))
    cmd = [sys.executable, "-m", "tools.dynolint", "--root", str(root),
           "--pass", "py", "--format=json", "--no-baseline"]
    proc = subprocess.run(cmd, cwd=REPO, capture_output=True, text=True)
    assert proc.returncode == 1, proc.stdout + proc.stderr
    doc = json.loads(proc.stdout)
    assert len(doc["findings"]) == 1
    finding = doc["findings"][0]
    assert finding["rule"] == "struct-constant"
    assert finding["file"] == "dynolog_tpu/client/mutant.py"
    assert finding["line"] == 5
    assert finding["key"]

    # ...baselined, the same run exits 0 and reports it suppressed: the
    # zero-NEW-findings contract future PRs assert against.
    baseline = tmp_path / "baseline.json"
    baseline.write_text(json.dumps({"version": 1, "findings": [finding]}))
    proc2 = subprocess.run(
        cmd[:-1] + ["--baseline", str(baseline)],
        cwd=REPO, capture_output=True, text=True)
    assert proc2.returncode == 0, proc2.stdout + proc2.stderr
    doc2 = json.loads(proc2.stdout)
    assert doc2["findings"] == [] and doc2["suppressed"] == 1

    # A second, new finding is NOT suppressed by the stale baseline.
    (root / "dynolog_tpu" / "client" / "mutant2.py").write_text(
        "import select\n\n\ndef wait(s):\n"
        "    return select.select([s], [], [])\n")
    proc3 = subprocess.run(
        cmd[:-1] + ["--baseline", str(baseline)],
        cwd=REPO, capture_output=True, text=True)
    assert proc3.returncode == 1
    doc3 = json.loads(proc3.stdout)
    assert [f["rule"] for f in doc3["findings"]] == ["select-timeout"]
    assert doc3["suppressed"] == 1


def test_checked_in_baseline_is_empty():
    # The shipped baseline carries no suppressed debt; if a future PR adds
    # entries, this test makes the act explicit and reviewable.
    doc = json.loads((REPO / "tools/dynolint/baseline.json").read_text())
    assert doc["findings"] == []


# ========================================================================
# Graph tier (dynolint v2): call graph + lock/reach/contract/flags passes
# ========================================================================

FIXTURE = REPO / "tests" / "fixtures" / "callgraph"


# -- green on the real tree ----------------------------------------------


def test_lockgraph_green_on_tree():
    assert _findings(lockgraph, REPO) == []


def test_reach_green_on_tree():
    assert _findings(reach, REPO) == []


def test_contract_green_on_tree():
    assert _findings(contract, REPO) == []


def test_flags_green_on_tree():
    assert _findings(flags, REPO) == []


def test_cli_runs_all_nine_passes():
    proc = subprocess.run(
        [sys.executable, "-m", "tools.dynolint", "--format=json",
         "--no-cache"],
        cwd=REPO, capture_output=True, text=True)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    doc = json.loads(proc.stdout)
    assert sorted(doc["passes"]) == sorted(
        ["wire", "cpp", "py", "durability", "lock", "reach", "contract",
         "flags", "compat"])
    for name, stats in doc["passes"].items():
        assert stats["findings"] == 0, (name, stats)
        assert stats["runtime_ms"] >= 0


# -- call-graph core on the checked-in fixture tree ----------------------


def test_callgraph_resolves_across_files():
    g = callgraph.analyze(FIXTURE)
    on_event = next(n for n in g.nodes.values() if n.fd.name == "onEvent")
    step_call = next(c for c in on_event.calls if c.name == "stepOne")
    targets = g.resolve(on_event, step_call)
    assert [t.rel for t in targets] == ["src/util/Util.h"]
    # Transitive walk reaches the sink two hops down, with the chain.
    reached = {(n.fd.name, depth) for n, depth, _ in g.walk(on_event)}
    assert ("stepOne", 1) in reached
    assert ("stepTwo", 2) in reached
    # Defined-but-uncalled functions are not "reachable".
    assert not any(name == "islandSleep" for name, _ in reached)


def test_callgraph_virtual_override_edges():
    # Server::drive calls its own virtual handleOne; the bodies live in
    # derived .cpps the base never includes — the edges must exist anyway.
    g = callgraph.analyze(FIXTURE)
    drive = next(n for n in g.nodes.values() if n.fd.name == "drive")
    call = next(c for c in drive.calls if c.name == "handleOne")
    classes = sorted(t.fd.cls for t in g.resolve(drive, call))
    assert classes == ["JsonServer", "MetricsServer"]


def test_callgraph_file_scope_bounds_resolution(tmp_path):
    # Same function name in an unrelated, un-included file must NOT
    # resolve — file-scope resolution is what keeps name matching sane.
    (tmp_path / "src").mkdir(parents=True)
    (tmp_path / "src" / "A.h").write_text(
        "inline void caller() {\n  helper();\n}\n")
    (tmp_path / "src" / "Elsewhere.h").write_text(
        "inline void helper() {\n  usleep(1);\n}\n")
    g = callgraph.analyze(tmp_path)
    caller = next(n for n in g.nodes.values() if n.fd.name == "caller")
    call = next(c for c in caller.calls if c.name == "helper")
    assert g.resolve(caller, call) == []


def test_callgraph_stl_member_names_not_resolved(tmp_path):
    # `ids_.size()` must not resolve to our own size() method — that
    # wiring produced phantom lock self-cycles before the skip list.
    (tmp_path / "src").mkdir(parents=True)
    (tmp_path / "src" / "T.h").write_text(
        "#include <vector>\n"
        "#include <mutex>\n"
        "class Table {\n"
        " public:\n"
        "  size_t size() {\n"
        "    std::lock_guard<std::mutex> lock(mutex_);\n"
        "    return ids_.size();\n"
        "  }\n"
        "  std::mutex mutex_;\n"
        "  std::vector<int> ids_; // guarded_by(mutex_)\n"
        "};\n")
    assert _findings(lockgraph, tmp_path) == []


def test_fixture_green_under_lexical_passes():
    # The fixture's defects are graph-tier by construction: the lexical
    # concurrency pass must see nothing (each direct body is clean).
    assert _findings(concurrency, FIXTURE) == []


# -- reach: interprocedural blocking reachability ------------------------


def test_reach_two_hops_below_event_loop_flagged():
    findings = _findings(reach, FIXTURE)
    hits = [f for f in findings if f.rule == "event-loop-reach"]
    assert len(hits) == 1, findings
    f = hits[0]
    assert f.file == "src/loop/Loop.h"
    assert f.symbol == "onEvent"
    assert "onEvent -> stepOne -> stepTwo" in f.message
    assert "src/util/Deep.h:" in f.message
    # The waived twin and the unannotated sibling stay clean.
    assert not any("onEventWaived" in f.message or "offLoop" in f.message
                   for f in findings)


def test_reach_mutated_real_tree_two_hops(tmp_path):
    # Real-tree mutation: give JsonRpcServer::parseRequest (the virtual
    # the event-loop's tryParse dispatches to) a helper that does a
    # blocking recvAll — two hops below the `// event-loop` annotation.
    root = _copy_subtree(tmp_path, [
        "src/rpc/EventLoopServer.h", "src/rpc/EventLoopServer.cpp",
        "src/rpc/JsonRpcServer.h", "src/rpc/JsonRpcServer.cpp"])
    _mutate(
        root, "src/rpc/JsonRpcServer.cpp",
        "size_t JsonRpcServer::parseRequest(",
        "static size_t slowPeek(int fd) {\n"
        "  char b[4];\n"
        "  netio::recvAll(fd, b, sizeof(b));\n"
        "  return 0;\n"
        "}\n"
        "size_t JsonRpcServer::parseRequest(")
    path = root / "src/rpc/JsonRpcServer.cpp"
    text = path.read_text()
    # First statement of parseRequest's body calls the helper.
    anchor = "  if (buf.size() < sizeof(int32_t)) {"
    assert text.count(anchor) == 1
    path.write_text(text.replace(anchor, "  slowPeek(0);\n" + anchor, 1))
    findings = _findings(reach, root)
    hits = [f for f in findings if f.rule == "event-loop-reach"
            and "tryParse" in f.symbol]
    assert hits, findings
    assert any("parseRequest" in f.message and "slowPeek" in f.message
               and "recvAll" in f.message for f in hits), findings


def test_reach_signal_handler_registered_cross_file_direct_body(tmp_path):
    # A handler DEFINED in one file but REGISTERED from another escapes
    # the lexical direct-body rule (it only sees same-file handlers);
    # the reach pass must own the direct body in that case.
    (tmp_path / "src").mkdir(parents=True)
    (tmp_path / "src" / "Main.cpp").write_text(
        '#include "src/Handlers.h"\n'
        "#include <csignal>\n"
        "void install() {\n"
        "  std::signal(SIGTERM, onSig);\n"
        "}\n")
    (tmp_path / "src" / "Handlers.h").write_text(
        "#include <mutex>\n"
        "inline void onSig(int) {\n"
        "  std::lock_guard<std::mutex> lock(gM);\n"
        "}\n")
    assert _findings(concurrency, tmp_path) == []  # lexical tier blind
    findings = _findings(reach, tmp_path)
    hits = [f for f in findings if f.rule == "signal-handler-reach"]
    assert hits, findings
    assert hits[0].file == "src/Handlers.h"
    assert "RAII lock" in hits[0].message


def test_reach_signal_handler_cross_file(tmp_path):
    # A handler whose unsafe work hides one call away, in another file.
    (tmp_path / "src").mkdir(parents=True)
    (tmp_path / "src" / "Sig.cpp").write_text(
        '#include "src/Helper.h"\n'
        "#include <csignal>\n"
        "void onSig(int) {\n"
        "  notifyStop();\n"
        "}\n"
        "void install() {\n"
        "  std::signal(SIGTERM, onSig);\n"
        "}\n")
    (tmp_path / "src" / "Helper.h").write_text(
        "#include <mutex>\n"
        "inline void notifyStop() {\n"
        "  std::lock_guard<std::mutex> lock(gM);\n"
        "}\n")
    findings = _findings(reach, tmp_path)
    hits = [f for f in findings if f.rule == "signal-handler-reach"]
    assert hits, findings
    assert any("onSig -> notifyStop" in f.message for f in hits), findings


def test_reach_waiver_requires_reason(tmp_path):
    # `// blocking-ok:` with no reason is NOT a waiver — fail closed.
    (tmp_path / "src").mkdir(parents=True)
    (tmp_path / "src" / "L.h").write_text(
        "#include <thread>\n"
        "inline void helper() {\n"
        "  std::this_thread::sleep_for(std::chrono::milliseconds(1));\n"
        "}\n"
        "// event-loop: dispatch.\n"
        "inline void onEvt() {\n"
        "  // blocking-ok:\n"
        "  helper();\n"
        "}\n")
    findings = _findings(reach, tmp_path)
    assert any(f.rule == "event-loop-reach" for f in findings), findings


# -- lockgraph: cycles and blocking-under-lock ---------------------------


def test_lock_fixture_ab_cycle_flagged():
    findings = _findings(lockgraph, FIXTURE)
    cycles = [f for f in findings if f.rule == "lock-cycle"]
    assert cycles, findings
    assert any("A::mutex_" in f.message and "B::mutex_" in f.message
               for f in cycles), findings


def test_lock_cycle_introduced_by_mutation(tmp_path):
    # Start from a one-directional (acyclic) pair: green. Introduce the
    # reverse acquisition: the cycle must light up.
    src = tmp_path / "src"
    src.mkdir(parents=True)
    base = (
        "#include <mutex>\n"
        "class B;\n"
        "class A {\n"
        " public:\n"
        "  void aThenB(B& b);\n"
        "  std::mutex mutex_;\n"
        "};\n"
        "class B {\n"
        " public:\n"
        "  void bOnly() {\n"
        "    std::lock_guard<std::mutex> lock(mutex_);\n"
        "  }\n"
        "  void bThenA(A& a);\n"
        "  std::mutex mutex_;\n"
        "};\n"
        "inline void A_impl(A& a, B& b) {}\n")
    cpp_green = (
        '#include "src/AB.h"\n'
        "void A::aThenB(B& b) {\n"
        "  std::lock_guard<std::mutex> lock(mutex_);\n"
        "  b.bOnly();\n"
        "}\n"
        "void B::bThenA(A& a) {\n"
        "  a.aThenB(*this);\n"
        "}\n")
    (src / "AB.h").write_text(base)
    (src / "AB.cpp").write_text(cpp_green)
    assert [f for f in _findings(lockgraph, tmp_path)
            if f.rule == "lock-cycle"] == []
    # Mutation: bThenA now holds B::mutex_ across the call into A.
    (src / "AB.cpp").write_text(cpp_green.replace(
        "void B::bThenA(A& a) {\n",
        "void B::bThenA(A& a) {\n"
        "  std::lock_guard<std::mutex> lock(mutex_);\n"))
    findings = _findings(lockgraph, tmp_path)
    cycles = [f for f in findings if f.rule == "lock-cycle"]
    assert cycles, findings
    assert any("A::mutex_" in f.message and "B::mutex_" in f.message
               for f in cycles), findings


def test_lock_blocking_direct_and_own_cv_exempt(tmp_path):
    (tmp_path / "src").mkdir(parents=True)
    (tmp_path / "src" / "W.h").write_text(
        "#include <condition_variable>\n"
        "#include <mutex>\n"
        "class W {\n"
        " public:\n"
        "  void waitOk() {\n"
        "    std::unique_lock<std::mutex> lock(mutex_);\n"
        "    cv_.wait_for(lock, std::chrono::milliseconds(1));\n"
        "  }\n"
        "  std::mutex mutex_;\n"
        "  std::condition_variable cv_;\n"
        "};\n")
    # The idiomatic own-lock cv wait is exempt...
    assert _findings(lockgraph, tmp_path) == []
    # ...but file I/O under the same lock is not.
    (tmp_path / "src" / "W.h").write_text(
        "#include <fstream>\n"
        "#include <mutex>\n"
        "class W {\n"
        " public:\n"
        "  void flush() {\n"
        "    std::lock_guard<std::mutex> lock(mutex_);\n"
        "    std::ofstream out(\"/tmp/x\");\n"
        "  }\n"
        "  std::mutex mutex_;\n"
        "};\n")
    findings = _findings(lockgraph, tmp_path)
    hits = [f for f in findings if f.rule == "lock-blocking"]
    assert len(hits) == 1, findings
    assert "W::flush" in hits[0].message
    assert "fstream" in hits[0].message


def test_lock_blocking_transitive_cv_wait_under_foreign_lock(tmp_path):
    # A callee's own-lock cv wait releases only the CALLEE's lock: a
    # caller holding a DIFFERENT lock across the call still stalls on
    # it, so the own-lock exemption must not apply transitively.
    (tmp_path / "src").mkdir(parents=True)
    (tmp_path / "src" / "Outer.h").write_text(
        '#include "src/Helper.h"\n'
        "#include <mutex>\n"
        "class Outer {\n"
        " public:\n"
        "  void run(Helper& h) {\n"
        "    std::lock_guard<std::mutex> lock(mutex_);\n"
        "    h.waitDone();\n"
        "  }\n"
        "  std::mutex mutex_;\n"
        "};\n")
    (tmp_path / "src" / "Helper.h").write_text(
        "#include <condition_variable>\n"
        "#include <mutex>\n"
        "class Helper {\n"
        " public:\n"
        "  void waitDone() {\n"
        "    std::unique_lock<std::mutex> lk(m_);\n"
        "    cv_.wait(lk);\n"
        "  }\n"
        "  std::mutex m_;\n"
        "  std::condition_variable cv_;\n"
        "};\n")
    findings = _findings(lockgraph, tmp_path)
    hits = [f for f in findings if f.rule == "lock-blocking"
            and "Outer::run" in f.message]
    assert hits, findings
    assert any("condition-variable wait" in f.message for f in hits), findings


def test_callgraph_commented_include_creates_no_edge(tmp_path):
    # A dead `// #include "src/..."` must not open a visibility edge.
    (tmp_path / "src").mkdir(parents=True)
    (tmp_path / "src" / "A.h").write_text(
        '// #include "src/Elsewhere.h"\n'
        "inline void caller() {\n  helper();\n}\n")
    (tmp_path / "src" / "Elsewhere.h").write_text(
        "inline void helper() {\n  usleep(1);\n}\n")
    g = callgraph.analyze(tmp_path)
    caller = next(n for n in g.nodes.values() if n.fd.name == "caller")
    call = next(c for c in caller.calls if c.name == "helper")
    assert g.resolve(caller, call) == []


def test_lock_blocking_transitive_under_lock(tmp_path):
    # The sink-path shape: a lock held across a call whose callee
    # (another file) does a deadline-less connect.
    (tmp_path / "src").mkdir(parents=True)
    (tmp_path / "src" / "Sink.h").write_text(
        '#include "src/Net.h"\n'
        "#include <mutex>\n"
        "class Sink {\n"
        " public:\n"
        "  void push() {\n"
        "    std::lock_guard<std::mutex> lock(mutex_);\n"
        "    dial();\n"
        "  }\n"
        "  std::mutex mutex_;\n"
        "};\n")
    (tmp_path / "src" / "Net.h").write_text(
        "inline int dial() {\n"
        "  return ::connect(3, nullptr, 0);\n"
        "}\n")
    findings = _findings(lockgraph, tmp_path)
    hits = [f for f in findings if f.rule == "lock-blocking"]
    assert hits, findings
    assert any("Sink::push -> dial" in f.message and "connect" in f.message
               for f in hits), findings


def test_lock_blocking_ok_waiver_prunes_edge(tmp_path):
    (tmp_path / "src").mkdir(parents=True)
    (tmp_path / "src" / "S.h").write_text(
        "#include <mutex>\n"
        "#include <thread>\n"
        "class S {\n"
        " public:\n"
        "  void reap() {\n"
        "    std::lock_guard<std::mutex> lock(mutex_);\n"
        "    // blocking-ok: worker already finished; join is instant.\n"
        "    t_.join();\n"
        "  }\n"
        "  std::mutex mutex_;\n"
        "  std::thread t_; // unguarded(lifecycle)\n"
        "};\n")
    assert [f for f in _findings(lockgraph, tmp_path)
            if f.rule == "lock-blocking"] == []


# -- contract: cross-language verb drift ---------------------------------


CONTRACT_FILES = [
    "src/rpc/ServiceHandler.cpp",
    "src/cli/dyno.cpp",
    "docs/CONTROL_SURFACE.md",
    "dynolog_tpu/cluster/unitrace.py",
    "dynolog_tpu/cluster/rpc.py",
]


def test_contract_green_on_copied_surface(tmp_path):
    root = _copy_subtree(tmp_path, CONTRACT_FILES)
    assert _findings(contract, root) == []


def test_contract_new_cpp_verb_without_docs_flagged(tmp_path):
    # A verb added to the dispatcher but nowhere else: fails closed.
    root = _copy_subtree(tmp_path, CONTRACT_FILES)
    line = _mutate(
        root, "src/rpc/ServiceHandler.cpp",
        '  } else if (fn == "health") {',
        '  } else if (fn == "frobnicate") {\n'
        "    response = processor_->getStatus();\n"
        '  } else if (fn == "health") {')
    findings = _findings(contract, root)
    _assert_flagged(findings, "verb-undocumented",
                    "src/rpc/ServiceHandler.cpp", line)
    assert any(f.symbol == "frobnicate" for f in findings), findings


def test_contract_ghost_docs_row_flagged(tmp_path):
    root = _copy_subtree(tmp_path, CONTRACT_FILES)
    _mutate(
        root, "docs/CONTROL_SURFACE.md",
        "| `health` | `health` | — |",
        "| `olde_verb` | `health` | — | Removed years ago. |\n"
        "| `health` | `health` | — |")
    findings = _findings(contract, root)
    hits = [f for f in findings if f.rule == "verb-ghost"]
    assert hits and hits[0].symbol == "olde_verb", findings


def test_contract_cli_subcommand_undocumented_flagged(tmp_path):
    root = _copy_subtree(tmp_path, CONTRACT_FILES)
    line = _mutate(
        root, "src/cli/dyno.cpp",
        '  if (verb == "status") {',
        '  if (verb == "newsub") {\n'
        "    return 0;\n"
        "  }\n"
        '  if (verb == "status") {')
    findings = _findings(contract, root)
    _assert_flagged(findings, "cli-undocumented", "src/cli/dyno.cpp", line)


def test_contract_unknown_client_verb_flagged(tmp_path):
    # A Python call site inventing a verb the daemon never dispatches.
    root = _copy_subtree(tmp_path, CONTRACT_FILES)
    mod = root / "dynolog_tpu" / "probe.py"
    mod.write_text('REQ = {"fn": "nonsenseVerb", "job_id": 1}\n')
    findings = _findings(contract, root)
    hits = [f for f in findings if f.rule == "verb-unknown"]
    assert hits, findings
    assert hits[0].file == "dynolog_tpu/probe.py"
    assert hits[0].symbol == "nonsenseVerb"


def test_contract_python_drift_both_directions(tmp_path):
    root = _copy_subtree(tmp_path, CONTRACT_FILES)
    # Direction 1: the table claims a Python caller that does not exist.
    _mutate(
        root, "docs/CONTROL_SURFACE.md",
        "| `health` | `health` | — |",
        "| `health` | `health` | `unitrace` |")
    findings = _findings(contract, root)
    assert any(f.rule == "python-drift" and f.symbol == "health"
               for f in findings), findings
    # Direction 2: Python calls a verb whose row denies a Python caller.
    root2 = _copy_subtree(tmp_path / "two", CONTRACT_FILES)
    _mutate(
        root2, "docs/CONTROL_SURFACE.md",
        "| `queryMetrics` | `query` `watch` `top` `jobs` | `unitrace` |",
        "| `queryMetrics` | `query` `watch` `top` `jobs` | — |")
    findings2 = _findings(contract, root2)
    assert any(f.rule == "python-drift" and f.symbol == "queryMetrics"
               for f in findings2), findings2


# -- flags: DEFINE_* vs docs table ----------------------------------------


def _flag_tree(tmp_path, defines: str, rows: str) -> pathlib.Path:
    (tmp_path / "src").mkdir(parents=True, exist_ok=True)
    (tmp_path / "docs").mkdir(parents=True, exist_ok=True)
    (tmp_path / "src" / "Thing.cpp").write_text(defines)
    (tmp_path / "docs" / "FLAGS.md").write_text(
        "# Flags\n\n| Flag | Type | Default | Description |\n"
        "|---|---|---|---|\n" + rows)
    return tmp_path


def test_flags_green_when_in_sync(tmp_path):
    root = _flag_tree(
        tmp_path,
        'DYN_DEFINE_int32(foo_interval_s, 60, "Interval");\n',
        "| `--foo_interval_s` | int32 | `60` | Interval |\n")
    assert _findings(flags, root) == []


def test_flags_undocumented_define_flagged(tmp_path):
    root = _flag_tree(
        tmp_path,
        'DYN_DEFINE_int32(foo_interval_s, 60, "Interval");\n'
        'DYN_DEFINE_bool(stealth_mode, false, "Undocumented");\n',
        "| `--foo_interval_s` | int32 | `60` | Interval |\n")
    findings = _findings(flags, root)
    hits = [f for f in findings if f.rule == "flag-undocumented"]
    assert len(hits) == 1, findings
    assert hits[0].symbol == "stealth_mode"
    assert hits[0].file == "src/Thing.cpp" and hits[0].line == 2


def test_flags_ghost_row_flagged(tmp_path):
    root = _flag_tree(
        tmp_path,
        'DYN_DEFINE_int32(foo_interval_s, 60, "Interval");\n',
        "| `--foo_interval_s` | int32 | `60` | Interval |\n"
        "| `--gone_flag` | bool | `false` | Renamed away |\n")
    findings = _findings(flags, root)
    hits = [f for f in findings if f.rule == "flag-ghost"]
    assert len(hits) == 1 and hits[0].symbol == "gone_flag", findings


def test_flags_duplicate_in_same_binary_flagged(tmp_path):
    root = _flag_tree(
        tmp_path,
        'DYN_DEFINE_int32(foo_interval_s, 60, "Interval");\n'
        'DYN_DEFINE_int32(foo_interval_s, 30, "Duplicate");\n',
        "| `--foo_interval_s` | int32 | `60` | Interval |\n")
    findings = _findings(flags, root)
    assert any(f.rule == "flag-duplicate" for f in findings), findings


def test_flags_commented_out_define_ignored(tmp_path):
    # A DYN_DEFINE_* in a comment ("old default, kept for reference") is
    # neither a duplicate nor a live definition.
    root = _flag_tree(
        tmp_path,
        'DYN_DEFINE_int32(foo_interval_s, 60, "Interval");\n'
        '// DYN_DEFINE_int32(foo_interval_s, 30, "old default");\n'
        '// DYN_DEFINE_bool(retired_flag, false, "removed in r7");\n',
        "| `--foo_interval_s` | int32 | `60` | Interval |\n")
    assert _findings(flags, root) == []


def test_contract_commented_out_dispatch_not_served(tmp_path):
    # A dispatch branch left behind as a comment must not count as a
    # served verb — otherwise stale docs rows and dead client literals
    # both fail open.
    root = _copy_subtree(tmp_path, CONTRACT_FILES)
    _mutate(
        root, "src/rpc/ServiceHandler.cpp",
        '  } else if (fn == "health") {',
        '  // } else if (fn == "oldVerb") { // removed verb, kept as doc\n'
        '  } else if (fn == "health") {')
    mod = root / "dynolog_tpu" / "probe.py"
    mod.write_text('REQ = {"fn": "oldVerb"}\n')
    findings = _findings(contract, root)
    assert any(f.rule == "verb-unknown" and f.symbol == "oldVerb"
               for f in findings), findings


def test_flags_same_name_across_binaries_allowed(tmp_path):
    # --port exists in both the daemon and the CLI: separate registries.
    root = _flag_tree(
        tmp_path,
        'DYN_DEFINE_int32(port, 1778, "Daemon port");\n',
        "| `--port` | int32 | `1778` | Port |\n")
    (root / "src" / "cli").mkdir()
    (root / "src" / "cli" / "dyno.cpp").write_text(
        'DYN_DEFINE_int32(port, 1778, "CLI port");\n')
    assert [f for f in _findings(flags, root)
            if f.rule == "flag-duplicate"] == []


# -- content-anchored baseline keys ---------------------------------------


def test_baseline_key_survives_line_shift(tmp_path):
    # The whole point of content anchoring: an unrelated edit ABOVE a
    # baselined finding must not churn its key (old keys embedded line
    # numbers via message text; see docs/STATIC_ANALYSIS.md migration
    # note).
    root = _py_case(tmp_path, (
        "import struct\n\n\n"
        "def encode(job_id):\n"
        "    return struct.pack('<q', job_id)\n"))
    cmd = [sys.executable, "-m", "tools.dynolint", "--root", str(root),
           "--pass", "py", "--format=json", "--no-baseline", "--no-cache"]
    proc = subprocess.run(cmd, cwd=REPO, capture_output=True, text=True)
    first = json.loads(proc.stdout)["findings"][0]
    mod = root / "dynolog_tpu" / "client" / "mutant.py"
    mod.write_text("# a comment\n# another\n\n" + mod.read_text())
    proc2 = subprocess.run(cmd, cwd=REPO, capture_output=True, text=True)
    second = json.loads(proc2.stdout)["findings"][0]
    assert second["line"] == first["line"] + 3  # the finding moved...
    assert second["key"] == first["key"]  # ...its key did not
    parts = first["key"].split("|")
    assert len(parts) == 5  # pass|rule|file|symbol|snippet-hash
    assert parts[0] == "py" and parts[1] == "struct-constant"
    assert parts[3] == "encode"  # symbol = enclosing function


# -- incremental cache + runtime budget -----------------------------------


def test_cache_invalidates_on_content_change(tmp_path):
    # Cached lex/parse results are content-hash keyed: mutating a file
    # after a cached run must surface the new finding, not stale green.
    root = _copy_subtree(tmp_path, ["src/metrics/MetricStore.h"])
    cmd = [sys.executable, "-m", "tools.dynolint", "--root", str(root),
           "--pass", "cpp", "--format=json", "--no-baseline"]
    proc = subprocess.run(cmd, cwd=REPO, capture_output=True, text=True)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert (root / "build" / "dynolint-cache.pkl").exists()
    _mutate(root, "src/metrics/MetricStore.h",
            "MetricFrameMap frame; // guarded_by(mutex)",
            "MetricFrameMap frame;")
    proc2 = subprocess.run(cmd, cwd=REPO, capture_output=True, text=True)
    assert proc2.returncode == 1, proc2.stdout + proc2.stderr
    doc = json.loads(proc2.stdout)
    assert any(f["rule"] == "guarded-decl" for f in doc["findings"])


def test_full_suite_under_budget():
    # The hard tier-1 budget: all 8 passes in under 10 seconds. The
    # first run warms build/dynolint-cache.pkl; the timed run is the
    # steady state every later invocation (tier-1, CI, pre-commit) sees.
    subprocess.run(
        [sys.executable, "-m", "tools.dynolint", "--format=json"],
        cwd=REPO, capture_output=True, text=True)
    t0 = time.monotonic()
    proc = subprocess.run(
        [sys.executable, "-m", "tools.dynolint", "--format=json"],
        cwd=REPO, capture_output=True, text=True)
    elapsed = time.monotonic() - t0
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert elapsed < 10.0, f"dynolint took {elapsed:.1f}s (budget: 10s)"


# -- durability pass (PR 9): fsync-before-publish discipline ---------------

DUR_FILES = ["src/core/SinkWal.cpp", "src/core/SinkWal.h"]


def test_durability_green_on_tree():
    assert _findings(durability, REPO) == []


def test_durability_ack_without_fsync_flagged(tmp_path):
    # Remove the fsync from the ack-watermark persist helper: both the
    # tmp+rename publish AND every ack() that calls the helper lose their
    # barrier.
    root = _copy_subtree(tmp_path, DUR_FILES)
    _mutate(root, "src/core/SinkWal.cpp",
            "  ok = ::fsync(fd) == 0 && ok;\n", "")
    found = _findings(durability, root)
    _assert_flagged(found, "rename-unsynced", "src/core/SinkWal.cpp")
    _assert_flagged(found, "ack-unsynced", "src/core/SinkWal.cpp")


def test_durability_ack_reordered_before_persist_flagged(tmp_path):
    # Advance the watermark BEFORE persisting it: a crash between the
    # two re-loses acked records. The mutation swaps the statement order.
    root = _copy_subtree(tmp_path, DUR_FILES)
    line = _mutate(
        root, "src/core/SinkWal.cpp",
        """  std::string error;
  if (!persistAckLocked(upToSeq, &error)) {
    DLOG_ERROR << "SinkWal: " << error;
    return false;
  }
  const uint64_t previousAcked = ackedSeq_;
  ackedSeq_ = upToSeq;""",
        """  const uint64_t previousAcked = ackedSeq_;
  ackedSeq_ = upToSeq;
  std::string error;
  if (!persistAckLocked(upToSeq, &error)) {
    DLOG_ERROR << "SinkWal: " << error;
    return false;
  }""")
    # The watermark assignment is the REPLACEMENT's second line (the
    # skip-cache re-key snapshot precedes it), hence line + 1.
    _assert_flagged(
        _findings(durability, root), "ack-unsynced",
        "src/core/SinkWal.cpp", line + 1)


def test_durability_naked_rename_flagged(tmp_path):
    root = _copy_subtree(tmp_path, DUR_FILES)
    line = _mutate(
        root, "src/core/SinkWal.cpp",
        "WalRegistry& WalRegistry::instance() {",
        """static void publishUnsynced(const std::string& a,
                            const std::string& b) {
  ::rename(a.c_str(), b.c_str());
}

WalRegistry& WalRegistry::instance() {""") + 2
    _assert_flagged(
        _findings(durability, root), "rename-unsynced",
        "src/core/SinkWal.cpp", line)


def test_durability_reasonless_waiver_fails_closed(tmp_path):
    # Stripping the reason from an existing waiver must NOT keep it
    # waived — an unexplained exemption is a finding, not an audit.
    root = _copy_subtree(tmp_path, DUR_FILES)
    text = (root / "src/core/SinkWal.cpp").read_text()
    old = ("    // durability-ok: restoring the ALREADY-persisted "
           "watermark at\n"
           "    // recovery — nothing is being acknowledged, so no new "
           "fsync is due.\n")
    assert text.count(old) == 1
    (root / "src/core/SinkWal.cpp").write_text(
        text.replace(old, "    // durability-ok\n"))
    found = _findings(durability, root)
    _assert_flagged(found, "ack-unsynced", "src/core/SinkWal.cpp")
    assert any("reasonless" in f.message for f in found)


def test_durability_unchecked_write_flagged(tmp_path):
    # PR 13 rule: discard the checked result of a persistence-path
    # write() — a short write or ENOSPC would then pass silently into
    # the fsync+rename that publishes the epoch file.
    root = _copy_subtree(tmp_path, DUR_FILES)
    line = _mutate(
        root, "src/core/SinkWal.cpp",
        """    ok = ::write(efd, text.data(), text.size()) ==
        static_cast<ssize_t>(text.size());
""",
        """    ::write(efd, text.data(), text.size());
""")
    _assert_flagged(
        _findings(durability, root), "write-unchecked",
        "src/core/SinkWal.cpp", line)


def test_durability_unchecked_write_waivable_with_reason(tmp_path):
    # The waiver grammar applies to the new rule too — WITH a reason; a
    # reasonless marker fails closed like every durability waiver.
    root = _copy_subtree(tmp_path, DUR_FILES)
    _mutate(
        root, "src/core/SinkWal.cpp",
        """    ok = ::write(efd, text.data(), text.size()) ==
        static_cast<ssize_t>(text.size());
""",
        """    // durability-ok: mutation-test waiver — deliberate discard.
    ::write(efd, text.data(), text.size());
""")
    found = _findings(durability, root)
    assert not any(f.rule == "write-unchecked" for f in found), found
    # Strip the reason: the same site is a finding again, with the
    # reasonless-marker hint.
    root2 = _copy_subtree(tmp_path / "r2", DUR_FILES)
    _mutate(
        root2, "src/core/SinkWal.cpp",
        """    ok = ::write(efd, text.data(), text.size()) ==
        static_cast<ssize_t>(text.size());
""",
        """    // durability-ok
    ::write(efd, text.data(), text.size());
""")
    found = _findings(durability, root2)
    _assert_flagged(found, "write-unchecked", "src/core/SinkWal.cpp")
    assert any("reasonless" in f.message for f in found
               if f.rule == "write-unchecked")


def test_durability_method_write_calls_not_flagged(tmp_path):
    # stream.write() / obj->write() are a different idiom (checked via
    # stream state): the syscall rule must not fire on them.
    root = _copy_subtree(tmp_path, DUR_FILES)
    _mutate(
        root, "src/core/SinkWal.cpp",
        "WalRegistry& WalRegistry::instance() {",
        """static void methodWriteIdiom(std::ostream& out,
                             const std::string& data) {
  out.write(data.data(), 1);
  ::rename("a", "b"); // durability-ok: mutation fixture, not durable
}

WalRegistry& WalRegistry::instance() {""")
    found = _findings(durability, root)
    assert not any(f.rule == "write-unchecked" for f in found), found


def test_durability_callee_fsync_counts_as_barrier(tmp_path):
    # The one-level interprocedural rule: sealActiveLocked's direct
    # fsync and ack()'s persistAckLocked barrier keep the REAL tree
    # green — pin that the pass resolves same-file helpers rather than
    # demanding a literal fsync in every function.
    root = _copy_subtree(tmp_path, DUR_FILES)
    assert _findings(durability, root) == []


# -- compat pass (PR 15): the schema version table cannot drift ------------


def test_compat_green_on_tree():
    assert _findings(compat, REPO) == []


def _compat_tree(tmp_path, *, version_h=None, supervise=None,
                 doc=None) -> pathlib.Path:
    """A minimal tree carrying every file the compat registry tracks,
    copied from the real repo then selectively mutated."""
    for name, rel, _ in compat.SOURCES:
        src = REPO / rel
        dst = tmp_path / rel
        dst.parent.mkdir(parents=True, exist_ok=True)
        if not dst.exists():
            dst.write_text(src.read_text())
    (tmp_path / "docs").mkdir(exist_ok=True)
    (tmp_path / compat.DOC).write_text(
        doc if doc is not None else (REPO / compat.DOC).read_text())
    if version_h is not None:
        (tmp_path / "src/common/Version.h").write_text(version_h)
    if supervise is not None:
        (tmp_path / "dynolog_tpu/supervise.py").write_text(supervise)
    return tmp_path


def test_compat_green_when_in_sync(tmp_path):
    assert _findings(compat, _compat_tree(tmp_path)) == []


def test_compat_bumped_constant_without_table_is_drift(tmp_path):
    text = (REPO / "src/common/Version.h").read_text().replace(
        "constexpr int64_t kWalRecordVersion = 1",
        "constexpr int64_t kWalRecordVersion = 2")
    findings = _findings(compat, _compat_tree(tmp_path, version_h=text))
    drift = [f for f in findings if f.rule == "version-drift"]
    assert drift and drift[0].symbol == "kWalRecordVersion", findings
    # The bump also skews against the Python mirror.
    assert any(f.rule == "version-skew" for f in findings), findings


def test_compat_undocumented_constant_flagged(tmp_path):
    doc = (REPO / compat.DOC).read_text()
    # Delete the kWalRecordVersion row from the table.
    doc = "\n".join(
        ln for ln in doc.split("\n") if "| `kWalRecordVersion` |" not in ln)
    findings = _findings(compat, _compat_tree(tmp_path, doc=doc))
    hits = [f for f in findings if f.rule == "version-undocumented"]
    assert hits and hits[0].symbol == "kWalRecordVersion", findings


def test_compat_ghost_row_flagged(tmp_path):
    doc = (REPO / compat.DOC).read_text().replace(
        "| `kWalRecordVersion` | `1` |",
        "| `kWalRecordVersion` | `1` |\n| `kRetiredVersion` | `3` |",
        1)
    findings = _findings(compat, _compat_tree(tmp_path, doc=doc))
    hits = [f for f in findings if f.rule == "version-ghost"]
    assert hits and hits[0].symbol == "kRetiredVersion", findings
    # The retired-row finding must not suppress the real rows.
    assert not any(f.rule == "version-drift" for f in findings), findings


def test_compat_mirror_skew_flagged(tmp_path):
    text = (REPO / "dynolog_tpu/supervise.py").read_text().replace(
        "\nPROTO_VERSION = 1", "\nPROTO_VERSION = 2", 1)
    findings = _findings(compat, _compat_tree(tmp_path, supervise=text))
    skew = [f for f in findings if f.rule == "version-skew"]
    assert skew and skew[0].symbol == "PROTO_VERSION", findings


def test_compat_renamed_constant_fails_closed(tmp_path):
    text = (REPO / "src/common/Version.h").read_text().replace(
        "kWalRecordVersion", "kWalFrameGeneration")
    findings = _findings(compat, _compat_tree(tmp_path, version_h=text))
    missing = [f for f in findings if f.rule == "version-missing"]
    assert missing and missing[0].symbol == "kWalRecordVersion", findings


def test_compat_missing_doc_fails_closed(tmp_path):
    root = _compat_tree(tmp_path)
    (root / compat.DOC).unlink()
    findings = _findings(compat, root)
    assert any(f.rule == "missing-file" for f in findings), findings
