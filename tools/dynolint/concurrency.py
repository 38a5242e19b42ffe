"""Pass 2: house concurrency rules over src/ (AST-lite C++).

Rules (conventions documented in docs/STATIC_ANALYSIS.md):

- guarded-decl: every mutable data member of a class that owns a
  std::mutex must carry a `// guarded_by(<mutex>)` annotation naming a
  mutex member of the same class, or an explicit `// unguarded(<reason>)`
  waiver. const members, atomics, and the sync primitives themselves
  (mutex/condition_variable) are exempt.
- guarded-use: a guarded member may only be touched in a scope that holds
  a lock_guard/unique_lock/scoped_lock on its mutex. Methods whose names
  end in `Locked` (house convention: the caller holds the lock),
  constructors, and destructors are exempt. Lock scopes are lexical —
  a lambda captured under a lock and run later is not caught; TSAN covers
  that class at runtime (scripts/tsan.supp, CI tsan job).
- guarded-use, sharded form: a guarded member reached through an instance
  expression (`shard.frame`, `s->frame` — the lock-striped shard pattern,
  MetricStore.h) requires a RAII lock on the SAME instance's mutex
  (`lock_guard lock(shard.mutex)`) in scope. Applies to any function in a
  file (plus its sibling header) that defines the mutex-owning class; the
  instance base must match textually, so hold the canonical
  `auto& shard = ...;` alias before locking.
- hot-path: a function annotated `// hot-path` (comment on or just above
  its signature) must not directly call blocking primitives: sleeps,
  file I/O opens, system/popen, or the fabric's blocking send/recv
  helpers. Direct body only — annotate the callee too if it is hot.
- event-loop: a function annotated `// event-loop` runs on the epoll
  dispatch thread (src/rpc/EventLoopServer) — one stall there reinstates
  the head-of-line blocking the transport exists to kill. Everything the
  hot-path rule bans is banned, plus: the blocking framed-IO helpers
  (netio::recvAll/sendAll — socket IO on the loop goes through the
  non-blocking O_NONBLOCK read/write state machines), condition-variable
  waits, and verb dispatch (processor_()/handleRequest() bodies belong
  on the worker pool, never the loop).
- signal-handler: a function registered via std::signal/sigaction must
  not acquire locks, notify condition variables, allocate, or log
  (DLOG_* takes a mutex) in its direct body. The transitive callee set
  is covered cross-file by the graph-tier reach pass.
- unsupervised-thread: every std::thread entrypoint in src/ (direct
  construction with a callable, or emplace/push into a
  std::vector<std::thread>) must run under the fault-containment
  Supervisor (src/daemon/Supervisor.h — detected as the statement
  mentioning Supervisor/supervise*), or carry an explicit
  `// unsupervised-thread: <reason>` waiver (trailing, or in the comment
  block above). One throw escaping a bare thread entrypoint is a
  std::terminate for the whole daemon — the class of outage the
  supervision layer exists to kill. src/tests/ is exempt.
- unspanned: span-coverage for the control-plane self-tracing layer
  (src/core/SpanJournal.h, docs/OBSERVABILITY.md). A span-required
  function — an event-loop worker handoff (a `handleRequest` or
  `streamRequest` override, the body EventLoopServer dispatches to the
  worker pool) or an RPC
  verb dispatcher (a body reading `request.at("fn")`) — must record a
  span (a SpanScope, or a direct SpanJournal record), or carry an
  explicit `// unspanned: <reason>` waiver in its doc-comment block.
  Control-plane work that records no span is invisible to
  `dyno selftrace`, which is exactly the blindness the layer exists to
  kill. Mirrors the unsupervised-thread rule's fail-closed posture.
  Diagnosis extension: diagnosis-named functions (the closed loop's
  daemon half, src/tracing/Diagnoser.h) must record a span in the
  diagnose.* namespace specifically — a generic span would keep the
  daemon's leg of breach -> capture -> diff -> report out of the one
  trace-id the loop is joined under. Same waiver syntax.
"""

from __future__ import annotations

import pathlib
import re

from . import Finding, cache
from .cpp_lex import (
    FunctionDef,
    LexedFile,
    class_statements,
    find_classes,
)

PASS = "cpp"

CPP_GLOBS = ("src/**/*.h", "src/**/*.cpp")
# Test scaffolding is exempt from daemon house rules (tests sleep, block
# and fork on purpose); the suite still compiles under TSAN in CI.
EXEMPT_DIRS = ("src/tests/",)

_GUARDED_RE = re.compile(r"guarded_by\(\s*([A-Za-z_]\w*)\s*\)")
_UNGUARDED_RE = re.compile(r"unguarded\(\s*([^)]+)\)")
_HOT_PATH_RE = re.compile(r"\bhot-path\b")
_EVENT_LOOP_RE = re.compile(r"\bevent-loop\b")

_SYNC_TYPES = re.compile(
    r"\b(?:std::)?(?:mutex|recursive_mutex|shared_mutex|condition_variable"
    r"(?:_any)?)\b")
_ATOMIC_TYPE = re.compile(r"\b(?:std::)?atomic\b")
_MUTEX_DECL = re.compile(
    r"\b(?:std::)?(?:recursive_|shared_)?mutex\s+([A-Za-z_]\w*)\s*;?$")

# The lock argument may be a bare member (`mutex_`), `this->mutex_`, or an
# instance-qualified expression (`shard.mutex`, `s->mutex`) — the sharded
# lock pattern. Whitespace inside the expression is normalized away.
_LOCK_ACQ = re.compile(
    r"\b(?:std::)?(?:lock_guard|unique_lock|scoped_lock)\s*"
    r"(?:<[^>]*>)?\s+(?:[A-Za-z_]\w*)\s*[({]\s*"
    r"([A-Za-z_]\w*(?:\s*(?:\.|->)\s*[A-Za-z_]\w*)*)")

# Blocking primitives banned from // hot-path function bodies.
_BLOCKING = [
    (re.compile(r"\bsleep_for\b"), "std::this_thread::sleep_for"),
    (re.compile(r"\bsleep_until\b"), "std::this_thread::sleep_until"),
    (re.compile(r"\b(?:u|nano)?sleep\s*\("), "sleep()"),
    (re.compile(r"\b[io]?fstream\b"), "fstream file I/O"),
    (re.compile(r"\bfopen\s*\("), "fopen()"),
    (re.compile(r"\bopendir\s*\("), "opendir()"),
    (re.compile(r"\bsystem\s*\("), "system()"),
    (re.compile(r"\bpopen\s*\("), "popen()"),
    (re.compile(r"\bpoll_recv\s*\("), "FabricManager::poll_recv (blocking)"),
    (re.compile(r"\bsync_send\s*\("), "sync_send (sleeps between retries)"),
    (re.compile(r"\.join\s*\(\)"), "thread join"),
]

# Additionally banned from `// event-loop` functions (the epoll dispatch
# thread), on top of everything in _BLOCKING: blocking framed-IO helpers,
# condition waits, and verb dispatch — one stall on the loop reinstates
# the serial transport's head-of-line blocking.
_EVENT_LOOP_BANNED = [
    (re.compile(r"\brecvAll\s*\("),
     "netio::recvAll (blocking read; use the non-blocking state machine)"),
    (re.compile(r"\bsendAll\s*\("),
     "netio::sendAll (blocking write; use the non-blocking state machine)"),
    (re.compile(r"\.\s*wait(?:_for|_until)?\s*\("),
     "condition-variable wait"),
    (re.compile(r"\bprocessor_\s*\("),
     "verb dispatch (processor_) — request bodies run on the worker pool"),
    (re.compile(r"\bhandleRequest\s*\("),
     "handleRequest() — request bodies run on the worker pool"),
]

# Not async-signal-safe: banned from signal handlers and their callees.
_SIGNAL_UNSAFE = [
    (re.compile(r"\b(?:lock_guard|unique_lock|scoped_lock)\b"), "RAII lock"),
    (re.compile(r"\.lock\s*\(\)"), "mutex lock()"),
    (re.compile(r"\bnotify_(?:one|all)\s*\(\)"), "condition_variable notify"),
    (re.compile(r"\bDLOG_?\w*\b"), "DLOG_* logging (takes a mutex)"),
    (re.compile(r"\bnew\b"), "heap allocation"),
    (re.compile(r"\bmalloc\s*\("), "malloc"),
    (re.compile(r"\bprintf\s*\("), "stdio"),
    (re.compile(r"\bc(?:out|err)\b"), "iostream"),
]

# Thread entrypoints: a std::thread constructed WITH a callable (bare
# declarations like `std::thread worker_;` carry no entrypoint), or an
# emplace/push into a std::vector<std::thread>. Known limit: a function
# DECLARATION returning std::thread (`std::thread make(...);`) would
# false-positive — no such signature exists in this tree; if one ever
# does, waive it with the annotation or return by out-param.
_THREAD_CTOR = re.compile(
    r"\bstd::thread\s+[A-Za-z_]\w*\s*[({]|\bstd::thread\s*[({]")
_THREAD_VEC_DECL = re.compile(
    r"\bstd::vector<\s*std::thread\s*>\s+([A-Za-z_]\w*)")
_SUPERVISED = re.compile(r"supervis", re.IGNORECASE)
_UNSUPERVISED_WAIVER = re.compile(r"unsupervised-thread\s*:\s*(\S.*)")

# Span-coverage (unspanned rule): tokens that count as "records a span",
# the marker identifying a verb-dispatch body, and the waiver.
_SPAN_TOKEN = re.compile(
    r"\bSpanScope\b|SpanJournal::instance\(\)\s*\.\s*record\s*\(|"
    r"\brecordSpan\s*\(")
_VERB_DISPATCH = re.compile(r'\.\s*at\(\s*"fn"\s*\)')
_UNSPANNED_WAIVER = re.compile(r"unspanned\s*:\s*(\S.*)")
_SPAN_REQUIRED_NAMES = ("handleRequest", "streamRequest")
# Diagnosis-span extension of the unspanned rule: a diagnose-verb
# function — name `diagnose` or `diagnoseXxx`/`diagnose_xxx` (the closed
# loop's daemon entry points: ServiceHandler::diagnose,
# Diagnoser::diagnoseCapture) — must record a span whose name literal is
# in the diagnose.* namespace, so every leg of breach -> capture ->
# diff -> report stays visible to `dyno selftrace`. Deliberately
# name-anchored: `diagnoser_` members, `Diagnoser` ctors and
# `bumpDiagnosis`-style bookkeeping are not verb bodies. The literal
# lives in the ORIGINAL text (lex() blanks strings in .code).
_DIAG_FN_NAME = re.compile(r"^[Dd]iagnose(?:$|[A-Z_])")
_DIAG_SPAN_LITERAL = re.compile(r'"diagnose\.')

_SIGNAL_REG = re.compile(
    r"\b(?:std::)?signal\s*\(\s*SIG\w+\s*,\s*([A-Za-z_]\w*)\s*\)")
_SIGACTION_HANDLER = re.compile(
    r"\.\s*sa_(?:handler|sigaction)\s*=\s*&?\s*([A-Za-z_]\w*)")

_MEMBER_DECL = re.compile(
    r"^(?:mutable\s+|volatile\s+)*"
    r"(?P<type>[A-Za-z_][\w:<>,\s*&]*?[\w:<>*&])\s+"
    r"(?P<name>[A-Za-z_]\w*)\s*(?:\[[^\]]*\])?\s*(?:=[^;]*|\{[^;]*\})?$"
)
_NON_MEMBER = re.compile(
    r"^(?:public|private|protected)\s*$|"
    r"^(?:using|typedef|friend|static|enum|class|struct|template|explicit|"
    r"virtual|operator)\b")


class ClassInfo:
    def __init__(self, name: str, rel: str):
        self.name = name
        self.rel = rel
        self.mutexes: list[str] = []
        # member -> (mutex, line)
        self.guarded: dict[str, tuple[str, int]] = {}


def _collect_annotation(lx: LexedFile, start_line: int,
                        end_line: int) -> str:
    """Annotation text for a declaration: trailing comments on any of its
    lines (declarations may wrap), plus the line immediately above — but
    only when that line is a pure comment. A code-bearing previous line is
    another declaration, whose trailing annotation must never be inherited
    by this one (that would make the rule fail open for a member added
    right below an annotated one)."""
    parts = [lx.comments.get(ln, "")
             for ln in range(start_line, end_line + 1)]
    if not lx.line_has_code(start_line - 1):
        parts.insert(0, lx.comments.get(start_line - 1, ""))
    return " ".join(p for p in parts if p).strip()


def _scan_class_members(lx: LexedFile, rel: str,
                        findings: list[Finding]) -> dict[str, ClassInfo]:
    infos: dict[str, ClassInfo] = {}
    for cls in find_classes(lx):
        stmts = class_statements(lx, cls)
        members: list[tuple[str, str, int, str]] = []  # name,type,line,annot
        mutexes: list[str] = []
        for st in stmts:
            text = " ".join(st.text.split())
            # Access labels don't end statements (':' not ';'): strip them.
            text = re.sub(r"^(?:(?:public|private|protected)\s*:\s*)+", "",
                          text)
            if _NON_MEMBER.match(text):
                continue
            if re.search(r"\boperator\b|=\s*(?:delete|default)\b", text):
                continue  # special member functions, not data
            m = _MEMBER_DECL.match(text)
            if not m:
                continue
            mtype, name = m.group("type"), m.group("name")
            line = lx.line_of(st.start)
            if _MUTEX_DECL.search(mtype + " " + name + ";") or (
                    _SYNC_TYPES.search(mtype)
                    and not _ATOMIC_TYPE.search(mtype)):
                if "mutex" in mtype:
                    mutexes.append(name)
                continue  # sync primitives need no annotation
            members.append((
                name, mtype, line,
                _collect_annotation(lx, line, lx.line_of(st.end))))
        if not mutexes:
            continue
        info = ClassInfo(cls.name, rel)
        info.mutexes = mutexes
        for name, mtype, line, annot in members:
            if mtype.split()[0] == "const" or _ATOMIC_TYPE.search(mtype):
                continue
            g = _GUARDED_RE.search(annot)
            if g:
                if g.group(1) not in mutexes:
                    findings.append(Finding(
                        PASS, "guarded-decl", rel, line,
                        f"{cls.name}.{name}: guarded_by({g.group(1)}) names "
                        f"no mutex member of {cls.name} "
                        f"(has: {', '.join(mutexes)})",
                        symbol=f"{cls.name}.{name}"))
                else:
                    info.guarded[name] = (g.group(1), line)
                continue
            u = _UNGUARDED_RE.search(annot)
            if u:
                if not u.group(1).strip():
                    findings.append(Finding(
                        PASS, "guarded-decl", rel, line,
                        f"{cls.name}.{name}: unguarded() waiver requires a "
                        "reason", symbol=f"{cls.name}.{name}"))
                continue
            findings.append(Finding(
                PASS, "guarded-decl", rel, line,
                f"{cls.name}.{name}: mutable member of mutex-owning class "
                f"lacks a // guarded_by(<mutex>) or // unguarded(<reason>) "
                "annotation", symbol=f"{cls.name}.{name}"))
        infos[cls.name] = info
    return infos


def _lock_spans(lx: LexedFile, fn: FunctionDef) -> list[tuple[str, int, int]]:
    """[(lock_expr, start, end)]: positions in the body where a RAII lock
    on `lock_expr` is held (from acquisition to the close of its brace
    scope). lock_expr is whitespace-normalized (`shard . mutex` ->
    `shard.mutex`)."""
    code = lx.code
    spans = []
    for m in _LOCK_ACQ.finditer(code, fn.body_start, fn.body_end):
        # Scope end: walk from the acquisition to the '}' that drops the
        # depth below the acquisition point's level.
        depth = 0
        end = fn.body_end
        for i in range(m.start(), fn.body_end):
            c = code[i]
            if c == "{":
                depth += 1
            elif c == "}":
                depth -= 1
                if depth < 0:
                    end = i
                    break
        spans.append((re.sub(r"\s+", "", m.group(1)), m.end(), end))
    return spans


_WORD = r"(?<![\w.])%s(?!\w)"


def _check_guarded_use(lx: LexedFile, rel: str, fn: FunctionDef,
                       info: ClassInfo, findings: list[Finding]) -> None:
    if (fn.name.endswith("Locked") or fn.name == info.name
            or fn.name == "~" + info.name):
        return
    spans = _lock_spans(lx, fn)
    code = lx.code
    for member, (mutex, _decl_line) in info.guarded.items():
        for m in re.finditer(_WORD % re.escape(member),
                             code[fn.body_start:fn.body_end]):
            pos = fn.body_start + m.start()
            # `this->member` and bare `member` both match; `other.member`
            # is excluded by the lookbehind on '.'.
            if code[max(0, pos - 2):pos] == "->" and \
                    code[max(0, pos - 6):pos] != "this->":
                continue  # someone else's field via pointer
            held = any(
                s[0] in (mutex, "this->" + mutex) and s[1] <= pos < s[2]
                for s in spans)
            if not held:
                findings.append(Finding(
                    PASS, "guarded-use", rel, lx.line_of(pos),
                    f"{info.name}::{fn.name}: touches '{member}' "
                    f"(guarded_by {mutex}) without holding a "
                    f"lock_guard/unique_lock on {mutex} in scope",
                    symbol=f"{info.name}::{fn.name}"))


def _check_sharded_use(lx: LexedFile, rel: str, fn: FunctionDef,
                       infos: dict[str, "ClassInfo"],
                       findings: list[Finding]) -> None:
    """Sharded-lock pattern: a guarded member of a mutex-owning class
    reached through an instance expression (`shard.frame`, `s->frame`)
    requires a RAII lock on the same instance's mutex (`shard.mutex`)
    covering the use. Checked for every function in the file — the users
    of a shard struct are its OWNER's methods, not the struct's own.
    Same exemptions as the classic form: `*Locked` methods (caller holds
    the lock by convention), constructors and destructors."""
    if fn.name.endswith("Locked") or (
            fn.cls and fn.name in (fn.cls, "~" + fn.cls)):
        return
    targets = [info for info in infos.values()
               if info.name != fn.cls and info.guarded]
    if not targets:
        return  # nothing foreign to guard: skip the lock-span scan
    spans = _lock_spans(lx, fn)
    code = lx.code
    for info in targets:
        for member, (mutex, _decl_line) in info.guarded.items():
            pat = re.compile(
                r"([A-Za-z_]\w*)\s*(?:\.|->)\s*" + re.escape(member)
                + r"(?!\w)")
            for m in pat.finditer(code, fn.body_start, fn.body_end):
                base = m.group(1)
                if base == "this":
                    continue
                pos = m.start()
                want = (f"{base}.{mutex}", f"{base}->{mutex}")
                held = any(
                    s[0] in want and s[1] <= pos < s[2] for s in spans)
                if not held:
                    findings.append(Finding(
                        PASS, "guarded-use", rel, lx.line_of(pos),
                        f"{(fn.cls + '::') if fn.cls else ''}{fn.name}: "
                        f"touches '{base}.{member}' ({info.name} member "
                        f"guarded_by {mutex}) without holding a "
                        f"lock_guard/unique_lock on {base}.{mutex} in "
                        "scope",
                        symbol=f"{(fn.cls + '::') if fn.cls else ''}"
                               f"{fn.name}"))


def _annotated_with(lx: LexedFile, fn: FunctionDef,
                    marker: re.Pattern) -> bool:
    # Marker on the signature line or anywhere in the contiguous
    # pure-comment block directly above it (the function's doc comment).
    if marker.search(lx.comments.get(fn.line, "")):
        return True
    ln = fn.line - 1
    while ln >= 1 and not lx.line_has_code(ln) and ln in lx.comments:
        if marker.search(lx.comments[ln]):
            return True
        ln -= 1
    return False


def _annotated_hot_path(lx: LexedFile, fn: FunctionDef) -> bool:
    return _annotated_with(lx, fn, _HOT_PATH_RE)


def _annotated_event_loop(lx: LexedFile, fn: FunctionDef) -> bool:
    return _annotated_with(lx, fn, _EVENT_LOOP_RE)


def _check_hot_path(lx: LexedFile, rel: str, fn: FunctionDef,
                    findings: list[Finding]) -> None:
    body = lx.code[fn.body_start:fn.body_end]
    for pat, what in _BLOCKING:
        for m in pat.finditer(body):
            findings.append(Finding(
                PASS, "hot-path", rel, lx.line_of(fn.body_start + m.start()),
                f"{fn.name}: blocking call ({what}) inside a function "
                "marked // hot-path", symbol=fn.name))


def _check_event_loop(lx: LexedFile, rel: str, fn: FunctionDef,
                      findings: list[Finding]) -> None:
    body = lx.code[fn.body_start:fn.body_end]
    for pat, what in list(_BLOCKING) + _EVENT_LOOP_BANNED:
        for m in pat.finditer(body):
            findings.append(Finding(
                PASS, "event-loop", rel,
                lx.line_of(fn.body_start + m.start()),
                f"{fn.name}: blocking call ({what}) inside a function "
                "marked // event-loop (the epoll dispatch thread; one "
                "stall here delays every connection)", symbol=fn.name))


def _check_span_coverage(lx: LexedFile, rel: str, fn: FunctionDef,
                         findings: list[Finding]) -> None:
    """unspanned rule: see module docstring. Span-required = an
    event-loop worker handoff (handleRequest override) or a verb
    dispatcher (reads request.at("fn"))."""
    body = lx.code[fn.body_start:fn.body_end]
    is_handoff = fn.name in _SPAN_REQUIRED_NAMES
    # The dispatch marker lives inside a string literal ('"fn"'), which
    # lex() blanks in .code — match the original text (same offsets).
    is_dispatch = bool(
        _VERB_DISPATCH.search(lx.text[fn.body_start:fn.body_end]))
    if not (is_handoff or is_dispatch):
        return
    if _SPAN_TOKEN.search(body):
        return
    if _annotated_with(lx, fn, _UNSPANNED_WAIVER):
        return
    what = ("event-loop worker handoff (handleRequest/streamRequest "
            "override)"
            if is_handoff
            else 'RPC verb dispatcher (reads request.at("fn"))')
    findings.append(Finding(
        PASS, "unspanned", rel, fn.line,
        f"{(fn.cls + '::') if fn.cls else ''}{fn.name}: {what} records "
        "no span (SpanScope / SpanJournal::instance().record) and "
        "carries no // unspanned: <reason> waiver — control-plane work "
        "here is invisible to `dyno selftrace`"))


def _check_diagnose_spans(lx: LexedFile, rel: str, fn: FunctionDef,
                          findings: list[Finding]) -> None:
    """Diagnosis-verb extension of the unspanned rule (see the module
    docstring): a diagnosis-named function must record a diagnose.*
    span, or carry the same `// unspanned: <reason>` waiver."""
    if not _DIAG_FN_NAME.search(fn.name):
        return
    if fn.cls and fn.name in (fn.cls, "~" + fn.cls):
        return  # a Diagnose-named class's ctor/dtor is not a verb body
    body = lx.code[fn.body_start:fn.body_end]
    original = lx.text[fn.body_start:fn.body_end]
    if _SPAN_TOKEN.search(body) and _DIAG_SPAN_LITERAL.search(original):
        return
    if _annotated_with(lx, fn, _UNSPANNED_WAIVER):
        return
    findings.append(Finding(
        PASS, "unspanned", rel, fn.line,
        f"{(fn.cls + '::') if fn.cls else ''}{fn.name}: diagnosis "
        "function records no diagnose.* span (SpanScope with a "
        '"diagnose.<stage>" name) and carries no // unspanned: <reason> '
        "waiver — a diagnosis leg that records no span breaks the "
        "breach -> capture -> diff -> report trace `dyno selftrace` "
        "reconstructs"))


def _check_signal_handlers(lx: LexedFile, rel: str,
                           fns: list[FunctionDef],
                           findings: list[Finding]) -> None:
    handlers = set()
    for pat in (_SIGNAL_REG, _SIGACTION_HANDLER):
        for m in pat.finditer(lx.code):
            name = m.group(1)
            if name not in ("SIG_IGN", "SIG_DFL"):
                handlers.add(name)
    if not handlers:
        return
    by_name = {f.name: f for f in fns}

    # Direct handler bodies only — the reach pass (graph tier) follows
    # the transitive callee set cross-file with full call chains.
    for h in sorted(handlers):
        fn = by_name.get(h)
        if fn is None:
            continue
        body = lx.code[fn.body_start:fn.body_end]
        for pat, what in _SIGNAL_UNSAFE:
            for m in pat.finditer(body):
                findings.append(Finding(
                    PASS, "signal-handler", rel,
                    lx.line_of(fn.body_start + m.start()),
                    f"{h}: {what} in a signal handler body "
                    "(not async-signal-safe)",
                    symbol=h))


def _statement_end(code: str, start: int) -> int:
    """Position just past the ';' terminating the statement containing
    `start` (bracket-depth aware, so lambda bodies with their own ';'s
    stay inside). Falls back to end of code."""
    depth = 0
    for i in range(start, len(code)):
        c = code[i]
        if c in "({[":
            depth += 1
        elif c in ")}]":
            depth -= 1
        elif c == ";" and depth <= 0:
            return i + 1
    return len(code)


def _comment_block_text(lx: LexedFile, first_line: int,
                        last_line: int) -> str:
    """Waiver-annotation text for a statement: trailing comments on any of
    its lines plus the contiguous pure-comment block directly above."""
    parts = [lx.comments.get(ln, "")
             for ln in range(first_line, last_line + 1)]
    ln = first_line - 1
    above: list[str] = []
    while ln >= 1 and not lx.line_has_code(ln) and ln in lx.comments:
        above.append(lx.comments[ln])
        ln -= 1
    return " ".join(reversed(above)) + " " + " ".join(p for p in parts if p)


def _thread_vector_names(lx: LexedFile) -> set[str]:
    return {m.group(1) for m in _THREAD_VEC_DECL.finditer(lx.code)}


def _check_thread_entrypoints(lx: LexedFile, rel: str, extra_vectors: set[str],
                              findings: list[Finding]) -> None:
    """unsupervised-thread rule: see module docstring."""
    code = lx.code
    vectors = _thread_vector_names(lx) | extra_vectors
    sites: list[tuple[int, str]] = []  # (pos, what)
    for m in _THREAD_CTOR.finditer(code):
        # `std::thread t;` never matches (no bracket); an empty ctor call
        # `std::thread()` / `std::thread{}` carries no entrypoint either.
        # Both alternatives end with the opening bracket.
        open_pos = m.end() - 1
        closer = ")" if code[open_pos] == "(" else "}"
        rest = code[open_pos + 1:open_pos + 64].lstrip()
        if rest.startswith(closer):
            continue
        sites.append((m.start(), "std::thread construction"))
    if vectors:
        vec_pat = re.compile(
            r"\b(" + "|".join(re.escape(v) for v in sorted(vectors)) +
            r")\s*\.\s*(?:emplace_back|push_back)\s*\(")
        for m in vec_pat.finditer(code):
            sites.append((
                m.start(),
                f"thread spawned into std::vector<std::thread> {m.group(1)}"))
    for pos, what in sites:
        end = _statement_end(code, pos)
        stmt = code[pos:end]
        if _SUPERVISED.search(stmt):
            continue  # entrypoint runs under the Supervisor
        first_line = lx.line_of(pos)
        last_line = lx.line_of(end - 1)
        annot = _comment_block_text(lx, first_line, last_line)
        waiver = _UNSUPERVISED_WAIVER.search(annot)
        if waiver:
            continue
        findings.append(Finding(
            PASS, "unsupervised-thread", rel, first_line,
            f"{what} does not run under the Supervisor and carries no "
            "// unsupervised-thread: <reason> waiver — one escaping "
            "exception here std::terminates the daemon"))


def run(root: pathlib.Path) -> list[Finding]:
    findings: list[Finding] = []
    files: list[pathlib.Path] = []
    for pattern in CPP_GLOBS:
        files.extend(sorted(root.glob(pattern)))
    for path in files:
        rel = path.relative_to(root).as_posix()
        if any(rel.startswith(d) for d in EXEMPT_DIRS):
            continue
        try:
            lx = cache.lexed(path)
        except (OSError, UnicodeDecodeError) as e:
            findings.append(Finding(PASS, "missing-file", rel, 1,
                                    f"cannot read: {e}"))
            continue
        infos = _scan_class_members(lx, rel, findings)
        fns = cache.functions(path, text=lx.text, lx=lx)
        # Header classes are often implemented in the sibling .cpp: merge
        # its class info (and thread-vector member names, for the
        # unsupervised-thread rule) when checking a .cpp's methods.
        sibling_vectors: set[str] = set()
        if rel.endswith(".cpp"):
            header = path.with_suffix(".h")
            if header.exists():
                hlx = cache.lexed(header)
                for name, inf in _scan_class_members(
                        hlx, rel, []).items():  # findings from .h scan only
                    infos.setdefault(name, inf)
                sibling_vectors = _thread_vector_names(hlx)
        _check_thread_entrypoints(lx, rel, sibling_vectors, findings)
        for fn in fns:
            if fn.cls and fn.cls in infos and infos[fn.cls].guarded:
                _check_guarded_use(lx, rel, fn, infos[fn.cls], findings)
            _check_sharded_use(lx, rel, fn, infos, findings)
            if _annotated_hot_path(lx, fn):
                _check_hot_path(lx, rel, fn, findings)
            if _annotated_event_loop(lx, fn):
                _check_event_loop(lx, rel, fn, findings)
            _check_span_coverage(lx, rel, fn, findings)
            _check_diagnose_spans(lx, rel, fn, findings)
        _check_signal_handlers(lx, rel, fns, findings)
    return findings
