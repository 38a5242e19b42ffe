"""README.md and docs/ name only paths that the tree holds.

A document that sends its reader to a file of this repository (a source,
a test, a script, a record) must not outlive that file. Checked: tokens
under the repository's top-level directories, and bare `*.py` / `*.md` /
capitalised `*.json` names in backticks (at the root, or a file's name
somewhere under those directories). A token may be a file, a
directory, or the stem of a `.h`/`.cpp` pair; `{a,b}` alternatives are
expanded. Skipped: globs, `<placeholders>`, and anything written under
another root (`/root/reference/hbt/src/...`, `dynolog/src/...`,
`dynolog/docs/...`: the reference project's tree).
"""

from __future__ import annotations

import functools
import pathlib
import re

import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent
DOCUMENTS = [REPO / "README.md", *sorted((REPO / "docs").glob("*.md"))]

_TOP = ("src", "dynolog_tpu", "tests", "scripts", "tools", "docs",
        "benchmarks", "perfbench", "examples")
# Not preceded by a path character: `hbt/src/x` and `dynolog/docs/x` are
# the reference's, not ours.
_PATH = re.compile(
    rf"(?<![\w/.\-])((?:{'|'.join(_TOP)})/[\w./{{}},*<>\-]*[\w}}*>])")
_BARE = re.compile(r"`([\w.\-]+\.(?:py|md)|[A-Z][\w.\-]*\.json)`")


def _alternatives(token: str) -> list[str]:
    m = re.search(r"\{([^{}]*)\}", token)
    if not m:
        return [token]
    return [
        path
        for alt in m.group(1).split(",")
        for path in _alternatives(token[: m.start()] + alt + token[m.end():])
    ]


def _exists(path: str) -> bool:
    target = REPO / path
    return target.exists() or any(target.parent.glob(target.name + ".*"))


@functools.cache
def _basenames() -> frozenset[str]:
    """File names under the top-level directories (not build/ or scratch):
    a bare `wire_schema.py` means the one under tools/dynolint/."""
    return frozenset(
        p.name for top in _TOP for p in (REPO / top).rglob("*.*"))


def missing_paths(text: str) -> list[str]:
    missing = []
    for token in _PATH.findall(text):
        if any(c in token for c in "*<>"):
            continue
        missing += [p for p in _alternatives(token) if not _exists(p)]
    missing += [
        name for name in _BARE.findall(text)
        if not (REPO / name).exists() and name not in _basenames()]
    return sorted(set(missing))


@pytest.mark.parametrize("document", DOCUMENTS, ids=lambda p: p.name)
def test_document_names_only_paths_in_the_tree(document):
    assert missing_paths(document.read_text()) == []


def test_the_check_sees_a_deleted_record():
    text = (
        "see benchmarks/gone_record.json, `GONE_r03.json` and `gone.py`;"
        " src/core/SinkWal, scripts/rpm/{dynolog_tpu.spec,make_rpm.sh},"
        " `chip_smoke.py`, /root/reference/hbt/src/mon/Monitor.h:22 stay.")
    assert missing_paths(text) == [
        "GONE_r03.json", "benchmarks/gone_record.json", "gone.py"]
