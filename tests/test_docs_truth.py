"""The documents name files that are there, and the package knows no
harness: every path, script and module that ``README.md`` or a file of
``docs/`` sends a reader to resolves in the tree, and no module under
``hetu_tpu/`` imports or names a root-level script. ``PERF.md`` and
``ROADMAP.md`` are not cases: they cite history and ``chiprun_out/`` on
purpose."""
import ast
import glob
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOCS = ["README.md"] + sorted(
    os.path.relpath(p, REPO)
    for p in glob.glob(os.path.join(REPO, "docs", "*.md")))
TREES = ("hetu_tpu/", "benchmark/", "tests/", "bin/", "docs/", "examples/")

_BACKTICKED = re.compile(r"`([^`\n]+)`")
_SCRIPT = re.compile(r"\bpython3?\s+(?:-u\s+)?([\w./-]+\.py)\b")
_MODULE = re.compile(r"\bpython3?\s+-m\s+((?:hetu_tpu|benchmark)[\w.]*)")
_BARE_FILE = re.compile(r"^(\w+\.py)\b")


def _there(path):
    """A path as a document writes it: ``a/b.py:12``, ``a/b.py::test``,
    ``a/{b,c}.py`` and ``a/*.py`` all name files under the root."""
    path = re.split(r":|\s", path.rstrip(".,;)"), maxsplit=1)[0]
    m = re.search(r"\{([^}]*)\}", path)
    if m:
        return all(_there(path[:m.start()] + alt + path[m.end():])
                   for alt in m.group(1).split(","))
    return bool(glob.glob(os.path.join(REPO, path.rstrip("/"))))


@pytest.fixture(scope="module")
def basenames():
    """Every ``*.py`` file name at the root and under ``TREES``."""
    names = {f for f in os.listdir(REPO) if f.endswith(".py")}
    for top in TREES:
        for _, _, files in os.walk(os.path.join(REPO, top)):
            names.update(f for f in files if f.endswith(".py"))
    return names


def _module_there(name):
    rel = name.rstrip(".").replace(".", "/")
    return os.path.isfile(os.path.join(REPO, rel + ".py")) or \
        os.path.isfile(os.path.join(REPO, rel, "__main__.py"))


@pytest.mark.parametrize("doc", DOCS)
def test_a_document_names_only_files_that_are_there(doc, basenames):
    with open(os.path.join(REPO, doc)) as f:
        text = f.read()
    missing = []
    for token in _BACKTICKED.findall(text):
        if token.startswith(TREES):
            if not _there(token):
                missing.append(f"path `{token}`")
        else:
            # a bare file name (`executor.py:_build_step`) is shorthand
            # for a file somewhere in the tree
            m = _BARE_FILE.match(token)
            if m and m.group(1) not in basenames:
                missing.append(f"file `{token}`")
    for script in _SCRIPT.findall(text):
        if not os.path.isfile(os.path.join(REPO, script)):
            missing.append(f"command `python {script}`")
    for module in _MODULE.findall(text):
        if not _module_there(module):
            missing.append(f"command `python -m {module}`")
    assert not missing, f"{doc} names what is not in the tree: {missing}"


def test_nothing_in_the_package_imports_a_harness():
    """A root-level script (``chip_smoke.py``, ``__graft_entry__.py``)
    drives the package; the package neither imports one nor names one
    as its caller."""
    scripts = sorted(os.path.splitext(f)[0] for f in os.listdir(REPO)
                     if f.endswith(".py"))
    assert scripts, "no root-level script found: wrong root?"
    named = re.compile(
        "|".join(rf"\b{re.escape(s)}\.py\b" if not s.startswith("_")
                 else re.escape(s) for s in scripts))
    found = []
    for path in glob.glob(os.path.join(REPO, "hetu_tpu", "**", "*.py"),
                          recursive=True):
        with open(path) as f:
            src = f.read()
        rel = os.path.relpath(path, REPO)
        for node in ast.walk(ast.parse(src)):
            if isinstance(node, ast.Import):
                mods = [a.name for a in node.names]
            elif isinstance(node, ast.ImportFrom) and node.level == 0:
                mods = [node.module or ""]
            else:
                continue
            found += [f"{rel}:{node.lineno} imports {m}" for m in mods
                      if m.split(".")[0] in scripts]
        found += [f"{rel}:{src.count(chr(10), 0, m.start()) + 1} names "
                  f"{m.group(0)}" for m in named.finditer(src)]
    assert not found, found
