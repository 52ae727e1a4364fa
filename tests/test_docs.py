"""Docs that cannot rot (VERDICT r4 missing #4): every ```python block
in docs/tutorials + docs/faq executes, in file order, in one namespace
per file — the reference's tutorial-notebook CI pattern
(tests/nightly/test_tutorial) applied to the markdown itself."""
import glob
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

DOC_FILES = sorted(
    glob.glob(os.path.join(REPO, "docs", "tutorials", "*.md"))
    + glob.glob(os.path.join(REPO, "docs", "faq", "*.md")))

BLOCK_RE = re.compile(r"```python\n(.*?)```", re.S)


def _blocks(path):
    return BLOCK_RE.findall(open(path).read())


def test_docs_have_executable_blocks():
    """The tutorial set is real: most pages carry executable code."""
    assert len(DOC_FILES) >= 10, DOC_FILES
    with_code = [p for p in DOC_FILES if _blocks(p)]
    assert len(with_code) >= 8, with_code


@pytest.mark.parametrize(
    "path", DOC_FILES, ids=[os.path.relpath(p, REPO) for p in DOC_FILES])
def test_doc_blocks_execute(path):
    blocks = _blocks(path)
    if not blocks:
        pytest.skip("no python blocks")
    ns = {"__name__": "__doc_exec__"}
    for i, src in enumerate(blocks):
        try:
            exec(compile(src, f"{os.path.basename(path)}[block {i}]",
                         "exec"), ns)
        except Exception as e:  # noqa: BLE001 — point at the block
            raise AssertionError(
                f"{os.path.relpath(path, REPO)} block {i} failed: "
                f"{type(e).__name__}: {e}\n--- block ---\n{src}") from e


# -- every file a document names is there ------------------------------------
PATH_DOCS = sorted(
    [os.path.join(REPO, "README.md"),
     os.path.join(REPO, ".claude", "skills", "verify", "SKILL.md")]
    + glob.glob(os.path.join(REPO, "docs", "**", "*.md"), recursive=True))
SPAN_RE = re.compile(r"`([^`\n]+)`")
PATH_RE = re.compile(
    r"(?<![\w./<>{*:-])((?:[\w.-]+/)*[\w.-]+\.(?:py|md|json))(?![\w/])")
# a paragraph that opens so cites the reference project's tree, not this one
PARITY_RE = re.compile(r"^Parity (?:target|role):.*?(?:\n\s*\n|\Z)",
                       re.S | re.M)


@pytest.fixture(scope="module")
def tree_names():
    names = set()
    for _d, dirs, files in os.walk(REPO):
        # not what git ignores: a parent checkout unpacked under .scratch/
        # would lend its file names to the tree
        dirs[:] = [d for d in dirs if not d.startswith(".")
                   and d not in ("__pycache__", "chiprun_out")]
        names.update(files)
    return names


def _missing_paths(path, names):
    """Backticked repo-relative paths to .py / .md / .json files that the
    document at ``path`` names and the tree does not hold.  A path counts
    as repo-relative when its first component is at the root of the
    checkout or of the package (``gluon/trainer.py``), or beside the
    document; a bare file name has to be some file's name in the tree.
    Placeholders (``<cell>``, ``*``, ``{a,b}``), absolute paths and
    ``commit:path`` of a ``git show`` are not paths of this tree."""
    text = PARITY_RE.sub("", open(path).read())
    bases = (REPO, os.path.join(REPO, "mxnet_tpu"), os.path.dirname(path))
    missing = set()
    for span in SPAN_RE.findall(text):
        for tok in PATH_RE.findall(span):
            if "/" not in tok:
                found = tok in names
            else:
                first = tok.split("/", 1)[0]
                if not any(os.path.exists(os.path.join(b, first))
                           for b in bases):
                    continue
                found = any(os.path.exists(os.path.join(b, tok))
                            for b in bases)
            if not found:
                missing.add(tok)
    return sorted(missing)


@pytest.mark.parametrize(
    "path", PATH_DOCS, ids=[os.path.relpath(p, REPO) for p in PATH_DOCS])
def test_doc_names_only_files_that_exist(path, tree_names):
    assert _missing_paths(path, tree_names) == []
