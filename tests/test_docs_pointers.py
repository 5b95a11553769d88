"""Every file the prose sends a reader to exists.

One case per document (README.md, the verify skill, each docs/*.md): the
repository paths it names are files of this checkout. A document that
points at a file a PR deleted fails here, in that PR.

What counts as a repository path: a name ending in .py, .md, .json or
.sh that has no directory part, or starts with `../` (a link written
from the document's own directory), or whose first directory is a
top-level directory of the repository or of the package
(`ops/flash_attention.py` is written from `horovod_tpu/`).

Where it is looked for: a `../` path from the document's directory
alone; any other from the root, from the document's directory
(markdown links) and from the package. A name without a directory that
a command runs (`python x.py`) is a file of the root, where commands
are run; in prose it may be any file of the tree, named without its
directory. Only files of this checkout count: a path that leaves it
through `..` is missing, whatever stands around the checkout.

Three kinds of name are somebody else's file, each by one rule:
  - the reference's own files: a path inside a parenthesis that opens
    with `ref:` (the citation style of SURVEY.md and the docs);
  - the user's program: what a launcher command line (`hvdrun`,
    `horovod_tpu.runner.launch`) runs after `python`;
  - what a run writes: a lower-case `.json` name without a directory
    (`trace.json`, `postmortem.json`); JSON files of the repository's
    root are written in capitals.
"""
import functools
import os
import pathlib
import re

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]
PACKAGE = ROOT / "horovod_tpu"
DOCUMENTS = [ROOT / "README.md",
             ROOT / ".claude" / "skills" / "verify" / "SKILL.md",
             *sorted((ROOT / "docs").glob("*.md"))]

# `x.json.gz` is no `.json` path; a trailing full stop ends a sentence.
PATH = re.compile(
    r"(?<![\w/.-])((?:[\w.-]+/)*[\w.-]+\.(?:py|md|json|sh))(?!\w|\.\w)")
LAUNCHED = re.compile(r"(?:hvdrun|runner\.launch)\b.*\bpython3?\s+$", re.S)
COMMAND = re.compile(r"\bpython3?\s+$")


def _directories(base: pathlib.Path) -> set:
    return {p.name for p in base.iterdir()
            if p.is_dir() and p.name[0] not in "._"}


@functools.cache
def _file_names() -> set:
    names = set(os.listdir(ROOT))
    for top in _directories(ROOT):
        for _, dirs, files in os.walk(ROOT / top):
            dirs[:] = [d for d in dirs if d[0] not in "._"]
            names.update(files)
    return names


def _cited_from_the_reference(text: str, start: int) -> bool:
    opened = text.rfind("(", 0, start)
    return (opened >= 0 and ")" not in text[opened:start]
            and text[opened + 1:start].lstrip().startswith("ref:"))


def _launched_by_the_user(text: str, start: int) -> bool:
    # One command, with its continuation lines.
    begin = start
    while True:
        begin = text.rfind("\n", 0, begin)
        if begin < 0 or not text[:begin].endswith("\\"):
            break
    return LAUNCHED.search(text[begin + 1:start]) is not None


def _is_file_of_the_checkout(path: str, bases) -> bool:
    for base in bases:
        # Lexically, so that a path that leaves the checkout and comes
        # back by the checkout's own name has left it.
        inside = os.path.normpath(base.relative_to(ROOT) / path)
        if not inside.startswith("..") and (ROOT / inside).is_file():
            return True
    return False


def missing_paths(text: str, directory: pathlib.Path) -> list:
    """(line, path) of every repository path in `text`, the text of a
    document in `directory`, that is no file of this checkout."""
    first_dirs = _directories(ROOT) | _directories(PACKAGE)
    names = _file_names()
    missing = []
    for found in PATH.finditer(text):
        path, start = found.group(1), found.start(1)
        bare = "/" not in path
        upward = path.startswith("../")
        if bare and path.endswith(".json") and path == path.lower():
            continue
        if not (bare or upward or path.split("/")[0] in first_dirs):
            continue
        if (_cited_from_the_reference(text, start)
                or _launched_by_the_user(text, start)):
            continue
        if upward:
            bases = (directory,)
        elif bare and COMMAND.search(text, 0, start):
            bases = (ROOT,)
        elif bare and path in names:
            continue
        else:
            bases = (ROOT, directory, PACKAGE)
        if not _is_file_of_the_checkout(path, bases):
            missing.append((text.count("\n", 0, start) + 1, path))
    return missing


@pytest.mark.parametrize(
    "document", DOCUMENTS, ids=lambda d: str(d.relative_to(ROOT)))
def test_every_repository_path_a_document_names_exists(document):
    assert missing_paths(document.read_text(), document.parent) == []


def test_the_rules_on_a_made_up_document():
    """The reader itself: what it reports and what each rule passes."""
    text = (
        "Run `python gone_tool.py`, then read [that](no_such_page.md) and\n"
        "`ops/no_such_kernel.py`; `scripts/perf_report.py` and\n"
        "`parallel/train.py:70` stay, as do `python chip_smoke.py`, a bare\n"
        "`perf_report.py` and [up](../benchmark/README.md).\n"
        "(ref: examples/pytorch_synthetic_benchmark.py:10-20, torch/x.py)\n"
        "    hvdrun -np 4 --flag \\\n        python train.py\n"
        "Save `trace.json`; unpack `docs/assets/shot.json.gz`; see\n"
        "`horovod/torch/optimizer.py` and `BENCHMARK.json`, not\n"
        "`NO_SUCH_RECORD.json`.\n"
        "A file of the tree is no file of the root: `python run.py`,\n"
        "`python3 perf_report.py`. Nor is what stands around the\n"
        "checkout a file of it: [out](../../outside.md),\n"
        f"[beside](../../{ROOT.name}/README.md), `docs/../../outside.md`.\n")
    assert missing_paths(text, ROOT / "docs") == [
        (1, "gone_tool.py"), (1, "no_such_page.md"),
        (2, "ops/no_such_kernel.py"), (10, "NO_SUCH_RECORD.json"),
        (11, "run.py"), (12, "perf_report.py"),
        (13, "../../outside.md"), (14, f"../../{ROOT.name}/README.md"),
        (14, "docs/../../outside.md")]
