"""The documents name files that exist and knobs that are read.

Every ``*.py`` / ``*.json`` / ``*.md`` path a document puts in backticks must
resolve against the repo root, ``evotorch_tpu/``, one of its packages or the
document's own directory, and no document names a variable of the deleted
bench scripts or of the deleted second sharded path. No jax here: text and
the file system only.
"""

import pathlib
import re

import pytest

ROOT = pathlib.Path(__file__).resolve().parent.parent

DOCUMENTS = [
    "README.md",
    "CLAUDE.md",
    ".claude/skills/verify/SKILL.md",
    "examples/README.md",
    *sorted(str(p.relative_to(ROOT)) for p in (ROOT / "docs").glob("*.md")),
]

#: spelled in halves, so that a search of the tree for either finds no reader
GONE = ("BENCH" + "_", "EVOTORCH_" + "SHARD_MAP")

#: not ours to resolve: upstream EvoTorch's counterpart of an example, and
#: the file ``EVOTORCH_TRACE`` is told to write
NOT_OURS = {"moo_parallel.py", "trace.json"}

_FENCE = re.compile(r"^```.*?^```", re.S | re.M)
_TICKED = re.compile(r"`([^`\n]+)`")
_PATH = re.compile(r"[\w./<>*{}$-]+\.(?:py|json|md)\b")


def _bases():
    package = ROOT / "evotorch_tpu"
    return [ROOT, package, *(p.parent for p in package.rglob("__init__.py"))]


def _named_paths(text):
    for ticked in _TICKED.findall(_FENCE.sub("", text)):
        for path in _PATH.findall(ticked):
            # a pattern is no file, and an absolute path lies outside the
            # checkout (``/root/TESTS_LAST_RUN.json`` exists only while a
            # session runs): neither is this tree's to keep true
            if not set(path) & set("<>*{}$") and not path.startswith("/"):
                yield path.removeprefix("./")


@pytest.mark.parametrize("document", DOCUMENTS)
def test_document_names_existing_files_and_live_knobs(document):
    text = (ROOT / document).read_text()
    for name in GONE:
        assert name not in text, f"{document} names {name}"
    bases = [*_bases(), (ROOT / document).parent]
    missing = sorted(
        {
            path
            for path in _named_paths(text)
            if pathlib.PurePath(path).name not in NOT_OURS
            and not any((base / path).exists() for base in bases)
        }
    )
    assert not missing, f"{document} names files that do not exist: {missing}"
