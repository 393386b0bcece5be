"""The guides at the root name only files that exist.

A guide that sends its reader to a file a later PR deleted is how the
README came to say "there is no benchmark on the chip yet" for 23 PRs.
History files (CHANGES.md, ROADMAP.md, PERF.md) name what has gone on
purpose and are not held to this.
"""

import functools
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
GUIDES = ("README.md", "SERVING.md", "TRAINING.md", "RL.md",
          "OBSERVABILITY.md", "DEVTOOLS.md", "COMPONENTS.md")

_SPAN = re.compile(r"`([^`\n]+)`")
# a word of a back-ticked span that names a file; what may follow the
# name is a test's name or line numbers (`tests/test_x.py::test_y`,
# `core/rpc.py:383`)
_FILE = re.compile(r"^([A-Za-z0-9_][A-Za-z0-9_./-]*\.(?:py|md|json))"
                   r"(?:::.*|:\d.*)?$")
# COMPONENTS.md's convention: "(ref `scripts.py:1383`, `updater.py`)"
# names the reference's files, not this repo's
_REF = re.compile(r"\bref:? `[^)|]*")


@functools.lru_cache(maxsize=None)
def _tree() -> tuple:
    files = []
    for top, dirs, names in os.walk(REPO):
        dirs[:] = [d for d in dirs if not d.startswith(".")
                   and d not in ("__pycache__", "chiprun_out")]
        files += [os.path.relpath(os.path.join(top, n), REPO)
                  for n in names]
    return tuple(files)


def named_files(text: str) -> set:
    """The files a guide's back-ticked spans name: `BENCHMARK.json`, the
    `benchmark/run.py` of a command line, `tests/test_x.py::test_y`. A
    bare lower-case `.json` is left out: those are files a command
    writes (`--trace out.json`, a debug dump's `alerts.json`); the
    repo's own records at the root are upper-case."""
    out = set()
    for span in _SPAN.finditer(_REF.sub("", text)):
        for word in span.group(1).split():
            m = _FILE.match(word.strip("(),;"))
            if m is None:
                continue
            name = m.group(1)
            stem, ext = os.path.splitext(name)
            if ext == ".json" and "/" not in name and stem != stem.upper():
                continue
            out.add(name)
    return out


@pytest.mark.parametrize("guide", GUIDES)
def test_a_guide_names_only_files_that_exist(guide):
    """A name is good from the root (`chip_smoke.py`,
    `benchmark/run.py`) or from inside a package, the way the guides
    write them (`serve/llm/engine.py`, `autoscaler.py`)."""
    with open(os.path.join(REPO, guide)) as f:
        names = named_files(f.read())
    assert names, f"{guide}: the pattern found no file name at all"
    tree = _tree()
    missing = sorted(
        n for n in names
        if not any(f == n or f.endswith("/" + n) for f in tree))
    assert not missing, (
        f"{guide} names files that are not in the tree: {missing}")
