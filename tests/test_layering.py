"""Which way the arrows point inside ``shifu_tpu/``.

Static: every ``.py`` file of the package is parsed (``ast``, nothing is
imported, no JAX) and each import of a sibling top-level unit — at module
or function scope — becomes an edge ``unit -> unit``.  ``ALLOWED`` is the
drawing of the architecture, lowest layer first: a unit may import only
what its row names, and a row may name only units above it in the table.
An edge that points the other way is either removed or written into
``KNOWN_BACK_EDGES`` with the ROADMAP debt that cures it; an entry whose
edge is gone must be deleted, so that table can only shrink.
"""

import ast
import functools
import os
from collections import defaultdict

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG = os.path.join(REPO, "shifu_tpu")

# unit -> the sibling units it may import; lowest layer first
ALLOWED = {
    "config": set(),
    "compile_cache": set(),
    "ioutil": {"config"},
    "faults": {"config"},
    "obs": {"config", "ioutil", "faults"},
    "ops": {"config", "obs"},
    "data": {"config", "ioutil", "faults", "obs", "ops"},
    "models": {"config", "ioutil", "obs", "ops"},
    "parallel": {"config", "compile_cache", "ioutil", "faults", "obs",
                 "data", "models"},
    "eval": {"config", "ops", "data", "models", "parallel"},
    "export": {"config", "ioutil", "ops", "models"},
    "train": {"config", "compile_cache", "ioutil", "faults", "obs", "ops", "data",
              "models", "parallel"},
    "serve": {"config", "ioutil", "faults", "obs", "ops", "data", "models",
              "parallel", "eval", "train"},
    "refresh": {"config", "ioutil", "faults", "obs", "data", "eval",
                "train"},
    "pipeline": {"config", "ioutil", "faults", "obs", "ops", "data",
                 "models", "parallel", "eval", "export", "train", "serve",
                 "refresh"},
    "lint": {"ioutil"},
    "cli": {"config", "compile_cache", "obs", "models", "parallel",
            "pipeline", "serve", "lint"},
}

# (from, to) -> (a file that holds the edge, ROADMAP debt that cures it)
KNOWN_BACK_EDGES = {
    ("config", "train"): ("config/meta.py", "D13"),
    ("ioutil", "obs"): ("ioutil.py", "D13"),
    ("obs", "ops"): ("obs/quality.py", "D13"),
    ("obs", "eval"): ("obs/quality.py", "D13"),
    ("ops", "data"): ("ops/sensitivity.py", "D13"),
    ("ops", "models"): ("ops/sensitivity.py", "D13"),
    ("ops", "parallel"): ("ops/sensitivity.py", "D13"),
    ("parallel", "train"): ("parallel/elastic_demo.py", "D13"),
    ("train", "pipeline"): ("train/tower_trainer.py", "D13"),
    ("refresh", "pipeline"): ("refresh/retrain.py", "D13"),
}


def _py_files(top):
    for dirpath, dirnames, filenames in os.walk(top):
        dirnames[:] = [d for d in dirnames
                       if d != "__pycache__" and not d.startswith(".")]
        for name in filenames:
            if name.endswith(".py"):
                yield os.path.join(dirpath, name)


@functools.lru_cache(maxsize=None)
def _imported_modules(path, package=None):
    """Dotted names a file imports, relative ones resolved against
    ``package`` (the dotted package the file lives in).  ``from a import
    b`` gives both ``a`` and ``a.b``: ``b`` may be a module."""
    with open(path, encoding="utf-8") as f:
        tree = ast.parse(f.read(), path)
    out = []
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            out.extend(alias.name for alias in node.names)
        elif isinstance(node, ast.ImportFrom):
            if node.level:
                if package is None:
                    continue
                base = package.split(".")
                base = base[:len(base) - (node.level - 1)]
                mod = ".".join(base + ([node.module] if node.module else []))
            else:
                mod = node.module
            out.append(mod)
            out.extend(f"{mod}.{alias.name}" for alias in node.names)
    return tuple(out)


def _graph():
    """unit -> unit -> files (relative to the package) holding the edge."""
    edges = defaultdict(lambda: defaultdict(set))
    for path in _py_files(PKG):
        rel = os.path.relpath(path, PKG)
        parts = rel.split(os.sep)
        if parts == ["__init__.py"]:
            continue
        unit = parts[0][:-3] if len(parts) == 1 else parts[0]
        package = ".".join(["shifu_tpu"] + parts[:-1])
        for mod in _imported_modules(path, package):
            names = mod.split(".")
            if names[0] != "shifu_tpu" or len(names) < 2:
                continue
            if names[1] in ALLOWED and names[1] != unit:
                edges[unit][names[1]].add(rel.replace(os.sep, "/"))
    return edges


@pytest.fixture(scope="module")
def graph():
    return _graph()


def test_the_table_is_layered_and_complete():
    """A row names only rows above it, and every unit on disk has a row."""
    seen = set()
    for unit, allowed in ALLOWED.items():
        assert allowed <= seen, (unit, sorted(allowed - seen))
        seen.add(unit)
    on_disk = {n[:-3] if n.endswith(".py") else n for n in os.listdir(PKG)
               if n not in ("__init__.py", "__pycache__")
               and (n.endswith(".py") or os.path.isdir(os.path.join(PKG, n)))}
    assert on_disk == set(ALLOWED)
    for src, dst in KNOWN_BACK_EDGES:
        assert dst not in ALLOWED[src], (src, dst)


@pytest.mark.parametrize("unit", list(ALLOWED))
def test_unit_imports_only_the_layers_below_it(graph, unit):
    known = {dst for (src, dst) in KNOWN_BACK_EDGES if src == unit}
    extra = {dst: sorted(files) for dst, files in graph[unit].items()
             if dst not in ALLOWED[unit] and dst not in known}
    assert not extra, (
        f"shifu_tpu/{unit} imports {extra}: not in ALLOWED[{unit!r}] — "
        "move the code down, pass it in, or (last) name the debt in "
        "KNOWN_BACK_EDGES and ROADMAP")


def test_known_back_edges_still_exist(graph):
    """An entry whose edge was removed goes with it: the table shrinks."""
    gone = [(src, dst, file) for (src, dst), (file, _) in
            KNOWN_BACK_EDGES.items() if file not in graph[src][dst]]
    assert not gone, f"delete from KNOWN_BACK_EDGES (and ROADMAP): {gone}"


def test_one_benchmark_and_the_program_does_not_import_it():
    """Nothing under ``shifu_tpu/`` imports ``benchmark``; nothing in the
    checkout imports the pre-chip harness, the package's ``bench`` module
    that PR 29 removed."""
    tops = [PKG, os.path.join(REPO, "tests"), os.path.join(REPO, "examples")]
    files = [p for top in tops for p in _py_files(top)]
    files += [os.path.join(REPO, n) for n in os.listdir(REPO)
              if n.endswith(".py")]
    for path in files:
        rel = os.path.relpath(path, REPO)
        in_pkg = rel.startswith("shifu_tpu" + os.sep)
        package = (".".join(os.path.dirname(rel).split(os.sep))
                   if in_pkg else None)
        for mod in _imported_modules(path, package):
            assert not (in_pkg and mod.split(".")[0] == "benchmark"), rel
            assert mod.split(".")[:2] != ["shifu_tpu", "bench"], rel
    assert not os.path.exists(os.path.join(PKG, "bench.py"))
