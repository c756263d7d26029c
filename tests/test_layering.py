"""The package's box diagram as one table, and the documents' line citations.

**Layering.** One case per subpackage of ``beforeholiday_tpu/`` (and one for
the top-level ``__init__``): an AST walk over its files — function-level and
``TYPE_CHECKING`` imports included — finds every subpackage it imports, and
that set must lie inside its row of ``ALLOWED``. The rows are the graph as it
stood after PR 29; the test's job is that the graph only LOSES arrows from now
on (a new arrow is an edit to this table, in review). Two rules hold for every
row: nothing but ``testing`` itself imports ``beforeholiday_tpu.testing``
(models, fault injectors, drills and chip checks are for the tests, the
benchmark and the examples), and nothing in the package imports the repo
root's scripts. The known cycles are written down as they are, each marked
``# debt:`` (ROADMAP C, "the base-layer cycles").

**Citations.** One case per document: every ``path/file.py:NNN`` it cites is a
claim about the tree as it is, so the file must exist and have at least that
many lines. A weak check on purpose — no document is parsed for meaning — that
fails wherever a document cites a line of a deleted or shortened file.
"""

import ast
import os
import re

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_PKG = "beforeholiday_tpu"
_ROOT_SCRIPTS = frozenset({
    "__graft_entry__", "benchmark", "chip_smoke", "examples", "tests", "tools"})

ALLOWED = {
    "__init__": {"amp", "fp16_utils", "guard", "monitor", "ops", "optimizers",
                 "parallel", "remat", "rnn", "transformer", "utils"},
    "amp": {"monitor", "ops", "optimizers", "utils"},
    "contrib": {"ops", "optimizers", "parallel"},
    "elastic": {"guard", "monitor", "optimizers", "parallel", "utils"},
    "fp16_utils": {"amp", "contrib", "ops"},
    "guard": {
        "amp",      # debt: guard.step -> amp.scaler, lazily (amp -> ops -> guard)
        "ops",      # debt: ops <-> guard (guard.dispatch -> ops.quantized)
        "utils",
    },
    "infer": {"monitor", "ops", "remat"},
    "models": {"moe", "monitor", "ops", "parallel", "remat"},
    "moe": {"monitor", "ops", "parallel", "remat"},
    "monitor": {
        "guard",    # debt: monitor <-> guard (counters, flight: lazily)
        "utils",    # debt: utils <-> monitor
    },
    "ops": {
        "guard",    # debt: ops <-> guard
        "monitor",
        "remat",    # debt: ops <-> remat
    },
    "optimizers": {"monitor", "ops", "parallel", "remat"},
    "parallel": {"monitor", "ops"},
    "remat": {
        "monitor",
        "ops",      # debt: ops <-> remat
        "utils",
    },
    "rnn": set(),
    "testing": {"amp", "contrib", "elastic", "guard", "moe", "monitor", "ops",
                "optimizers", "parallel", "remat", "transformer", "utils"},
    "transformer": {"amp", "monitor", "ops", "parallel", "remat"},
    "utils": {
        "monitor",  # debt: utils <-> monitor (utils/__init__ re-exports spans)
        "parallel",  # debt: utils.logging -> parallel_state, lazily
    },
}


def _files_of(sub):
    if sub == "__init__":
        return [os.path.join(_REPO, _PKG, "__init__.py")]
    found = []
    for d, _, files in os.walk(os.path.join(_REPO, _PKG, sub)):
        found += [os.path.join(d, f) for f in files if f.endswith(".py")]
    return found


def _modules_imported(path):
    """Absolute dotted names of everything ``path`` imports, relative imports
    resolved against the file's own package."""
    package = os.path.relpath(os.path.dirname(path), _REPO).split(os.sep)
    with open(path) as f:
        tree = ast.parse(f.read())
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for alias in node.names:
                yield alias.name
        elif isinstance(node, ast.ImportFrom):
            base = package[:len(package) - node.level + 1] if node.level else []
            module = ".".join(base + ([node.module] if node.module else []))
            yield module
            for alias in node.names:     # ``from beforeholiday_tpu import ops``
                yield f"{module}.{alias.name}"


def _subpackages():
    return sorted(
        d for d in os.listdir(os.path.join(_REPO, _PKG))
        if os.path.isfile(os.path.join(_REPO, _PKG, d, "__init__.py")))


def test_the_table_has_a_row_for_every_subpackage():
    assert sorted(ALLOWED) == ["__init__"] + _subpackages()


@pytest.mark.parametrize("sub", sorted(ALLOWED))
def test_subpackage_imports_only_what_its_row_allows(sub):
    subs = set(_subpackages())
    imported, scripts = set(), set()
    for path in _files_of(sub):
        for name in _modules_imported(path):
            parts = name.split(".")
            if parts[0] == _PKG and len(parts) > 1 and parts[1] in subs:
                imported.add(parts[1])
            elif parts[0] in _ROOT_SCRIPTS:
                scripts.add(f"{os.path.relpath(path, _REPO)}: {name}")
    imported.discard(sub)
    assert not scripts, f"the package imports the repo root's scripts: {scripts}"
    assert imported <= ALLOWED[sub], (
        f"{sub} imports {sorted(imported - ALLOWED[sub])}: not in its row")
    if sub != "testing":
        assert "testing" not in ALLOWED[sub]


def test_nothing_imports_the_tuner():
    """``beforeholiday_tpu.tune`` went in PR 45: the entry points say their
    defaults in their signatures, and no file of the repo asks for the module."""
    assert not os.path.exists(os.path.join(_REPO, _PKG, "tune"))
    asking = []
    for top in (_PKG, "benchmark", "examples", "tools", "tests"):
        for d, _, files in os.walk(os.path.join(_REPO, top)):
            for f in files:
                if f.endswith(".py"):
                    path = os.path.join(d, f)
                    asking += [os.path.relpath(path, _REPO)
                               for name in _modules_imported(path)
                               if name.startswith(f"{_PKG}.tune")]
    assert not asking, f"still importing the tuner: {sorted(set(asking))}"


# --------------------------------------------------------------- citations

_DOCUMENTS = ("README.md", "PERF.md", "ROADMAP.md", ".claude/skills/verify/SKILL.md")
# `path/file.py:123`, `file.py:12-34`, `file.py:41,69,85,118-122`
_CITATION = re.compile(r"([\w./-]+\.py):(\d+(?:[-,]\d+)*)")
_SEARCH = ("", _PKG, "benchmark", "tests")
_OTHER_TREES = ("apex/",)           # the reference project's sources


def _resolve(cited):
    for base in _SEARCH:
        path = os.path.join(_REPO, base, cited)
        if os.path.isfile(path):
            return path
    return None


@pytest.mark.parametrize("document", _DOCUMENTS)
def test_every_cited_line_exists(document):
    with open(os.path.join(_REPO, document)) as f:
        text = f.read()
    wrong = []
    for cited, lines in _CITATION.findall(text):
        if cited.startswith(_OTHER_TREES):
            continue
        path = _resolve(cited)
        if path is None:
            wrong.append(f"{cited}: no such file")
            continue
        with open(path) as f:
            have = sum(1 for _ in f)
        last = max(int(n) for n in re.split("[-,]", lines))
        if last > have:
            wrong.append(f"{cited}:{lines}: the file has {have} lines")
    assert not wrong, f"{document} cites lines that are not there: {wrong}"
