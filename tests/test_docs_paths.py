"""The documents name files that exist.

Every repository path a document writes in code marks has to be in the
working tree: a deleted tool or a renamed module fails here until the
prose follows. A path the prose says was deleted is written without
code marks, and a pattern (``tools/bench_*.py``, ``<cell>.json``) is
not a path.
"""

import functools
import glob
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PACKAGE = "distributed_tensorflow_tpu"

DOCUMENTS = sorted(
    ["README.md", "benchmark/README.md", ".claude/skills/verify/SKILL.md",
     "tools/ci_fast.sh"]
    + [os.path.relpath(p, REPO)
       for p in glob.glob(os.path.join(REPO, "docs", "*.md"))])

#: directories whose mention makes a word a repository path
ROOT_DIRS = ("tools", PACKAGE, "benchmark", "tests", "examples", "docs",
             "native")

_CODE = re.compile(r"```.*?```|`[^`\n]+`", re.S)
_WORD = re.compile(r"[^\s,;()\"'=|]+")
_PATH = re.compile(r"[\w.-]+(?:/[\w.-]+)*/?")
_BARE = re.compile(r"[\w-]+\.(?:py|sh)")


def _subdirs(path):
    full = os.path.join(REPO, path)
    return {d for d in os.listdir(full)
            if os.path.isdir(os.path.join(full, d))
            and not d.startswith((".", "_"))}


def _words(document):
    text = open(os.path.join(REPO, document)).read()
    if not document.endswith(".md"):
        yield from _WORD.findall(text)  # a script is code from end to end
        return
    for span in _CODE.findall(text):
        yield from _WORD.findall(span.strip("`"))


def named_paths(document):
    """The repository paths ``document`` writes, as it writes them."""
    first = set(ROOT_DIRS) | _subdirs(PACKAGE)
    found = set()
    for word in _words(document):
        if any(c in word for c in "*<>{}$[]"):
            continue  # a pattern or a shell expansion, not a path
        # `serve/engine.py:73`, `tests/test_x.py::test_y`, a sentence's dot
        word = word.split(":")[0].rstrip(".")
        parts = word.rstrip("/").split("/")
        if len(parts) == 1:
            if _BARE.fullmatch(word):
                found.add(word)
        elif _PATH.fullmatch(word) and parts[0] in first:
            # `data/fsdp/model` lists mesh axes: below a package directory
            # only a name with a dot in it is a file
            if parts[0] in ROOT_DIRS or "." in parts[-1]:
                found.add(word)
    return sorted(found)


@functools.lru_cache(maxsize=None)
def _file_names():
    names = set()
    for top in ROOT_DIRS:
        for _, _, files in os.walk(os.path.join(REPO, top)):
            names.update(files)
    return names


def _find(document, path):
    for base in ("", PACKAGE, os.path.dirname(document)):
        full = os.path.join(REPO, base, path)
        if os.path.exists(full):
            return full
    return None


def exists(document, path):
    if "/" not in path.rstrip("/"):
        # a bare file name: at the root, or the name of some file below
        return (os.path.exists(os.path.join(REPO, path))
                or path in _file_names())
    if _find(document, path):
        return True
    # `parallel/cluster.configure_compile_cache`: a name inside a module
    module, _, name = path.rpartition(".")
    source = _find(document, module + ".py") if name.isidentifier() else None
    return bool(source) and bool(
        re.search(rf"\b{name}\b", open(source).read()))


@pytest.mark.parametrize("document", DOCUMENTS)
def test_document_names_files_that_exist(document):
    paths = named_paths(document)
    assert paths, f"{document}: the reader found no path at all"
    missing = [p for p in paths if not exists(document, p)]
    assert not missing, f"{document} names files that are not there: {missing}"


def test_reader_finds_paths_and_leaves_patterns(tmp_path):
    doc = tmp_path / "doc.md"
    doc.write_text(
        "Run `python tools/obs_check.py --x` and `tools/gone.py`; see "
        "`serve/engine.py:73`, `tests/test_serve.py::test_a`, "
        "`tools/bench_*.py`, `benchmark/limits/<cell>.json`, `nowhere.py`, "
        "`artifacts/out.json`, `data/fsdp/model`, `train/step.jit_train_step`, "
        "`train/step.no_such_name`. The former tools/deleted.py went.\n"
        "```\npython chip_smoke.py --rehearsal\n```\n")
    rel = os.path.relpath(str(doc), REPO)
    got = named_paths(rel)
    assert got == ["chip_smoke.py", "nowhere.py", "serve/engine.py",
                   "tests/test_serve.py", "tools/gone.py",
                   "tools/obs_check.py", "train/step.jit_train_step",
                   "train/step.no_such_name"]
    assert [p for p in got if not exists(rel, p)] == \
        ["nowhere.py", "tools/gone.py", "train/step.no_such_name"]
