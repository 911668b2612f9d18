"""What the documents and the comments point at is in the tree.

A reader cannot check a sentence that names a file, a script or a
record that is gone: `README.md` advertised a benchmark that never ran
on this machine for five PRs after its replacement landed, and comments
cited measurement rounds whose files were deleted long before.  Two
cheap checks keep that from coming back: every repo path a document
names exists, and no source file cites a vanished record."""
import functools
import glob
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

DOCS = ["README.md", "docs/dev-guide.md", "docs/tuning-guide.md",
        "COVERAGE.md", ".claude/skills/verify/SKILL.md"]

#: a back-ticked word is a repo path when it starts at one of the
#: tree's directories or is a top-level script / document / manifest
TREE_DIRS = ("scripts", "tests", "spark_rapids_tpu", "benchmark", "docs")
_REPO_PATH = re.compile(
    r"^(?:(?:%s)/[\w./*\-]+|[\w\-]+\.(?:py|sh|json|md))$"
    % "|".join(TREE_DIRS))
_BACKTICKED = re.compile(r"```.*?```|`[^`\n]+`", re.S)


def _repo_paths_named(text: str) -> set:
    out = set()
    for span in _BACKTICKED.findall(text):
        for word in span.strip("`").split():
            # `path:123`, `path::test_name`, `path,` -> path
            word = word.strip("`'\"()[]<>,;").split(":", 1)[0].rstrip(".,/")
            if _REPO_PATH.match(word):
                out.add(word)
    return out


@pytest.mark.parametrize("doc", DOCS)
def test_paths_named_in_docs_exist(doc):
    with open(os.path.join(REPO, doc)) as f:
        named = _repo_paths_named(f.read())
    assert named, f"{doc} names no repo path: the pattern is broken"
    # a bare `name.py` may be a module named in its package's context
    basenames = {os.path.basename(p) for p in _tree_files()}
    missing = sorted(p for p in named
                     if not glob.glob(os.path.join(REPO, p))
                     and p not in basenames)
    assert not missing, f"{doc} names paths that are not in the tree: " \
        f"{missing}"


@functools.lru_cache(maxsize=None)
def _tree_files() -> tuple:
    """The files of the tree's own directories and of its root.  Under
    these directories `.gitignore` covers byte code and built objects
    only, and what it covers is not a path of the tree."""
    paths = [n for n in os.listdir(REPO)
             if os.path.isfile(os.path.join(REPO, n))]
    for d in TREE_DIRS:
        for root, subdirs, names in os.walk(os.path.join(REPO, d)):
            subdirs[:] = [s for s in subdirs if s != "__pycache__"]
            paths += [os.path.relpath(os.path.join(root, n), REPO)
                      for n in names if not n.endswith((".pyc", ".so"))]
    return tuple(paths)


def test_no_source_cites_a_vanished_record():
    # built from pieces, so this file does not cite them itself
    gone = re.compile("|".join([
        "BENCH" + r"_r\d", "VERDICT" + r" r\d", "MULTICHIP" + r"_(?:r\d|LOCAL)",
        "multichip" + "_check", r"\bbench" + r"\.py\b", "bench" + "_diff"]))
    sources = [p for p in _tree_files()
               if p.startswith(("spark_rapids_tpu/", "tests/", "scripts/"))]
    assert len(sources) > 150, "the walk found too little to mean much"
    cited = []
    for path in sources:
        with open(os.path.join(REPO, path), errors="replace") as f:
            for n, line in enumerate(f, start=1):
                if gone.search(line):
                    cited.append(f"{path}:{n}: {line.strip()[:80]}")
    assert not cited, "a record that is not in the tree is cited:\n" \
        + "\n".join(cited)
