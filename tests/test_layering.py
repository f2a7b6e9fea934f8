"""The package's import arrows point one way, and this file says which.

Every top-level package or module of ``katib_tpu`` is a node with a place in
``ORDER``; a node imports from its own tier and from the tiers before it,
module-level and function-level imports alike (a deferred import hides a
cycle, it does not remove one). ``parallel/mesh.py`` is a node of its own
below ``ops``: its importers (the kernels, the models, the runtime) say it is
a low helper, whatever package it lives in.

An edge that breaks the order is a line of ``EXCEPTIONS`` with the ROADMAP
debt that removes it (Queue D, D14). The table only shrinks: a new upward
import fails its node's case, and so does an entry that no longer matches
an import.
"""

import ast
import functools
import os

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PACKAGE = "katib_tpu"

# Lowest first. Imports within a tier are allowed.
ORDER = (
    ("utils", "config", "tracing", "telemetry"),
    ("api", "db", "native"),
    ("parallel.mesh",),
    ("ops",),
    ("analysis", "suggest", "earlystop"),
    ("compilesvc",),
    ("runtime",),
    ("models",),  # trial programs call the runtime's trial-facing API
    ("parallel",),  # the step builders, which import the models
    ("service",),
    ("controller",),
    ("client", "ui", "cli", "__init__"),  # __init__: the package's facade
)
TIER = {node: i for i, tier in enumerate(ORDER) for node in tier}

# (file under katib_tpu/, node it imports) -> the move that removes the edge.
EXCEPTIONS = {
    ("analysis/program.py", "controller"):
        "D14(a): resolve_entry_point moves out of controller/executor.py",
    ("runtime/population.py", "controller"):
        "D14(a): resolve_entry_point moves out of controller/executor.py",
    ("suggest/asha.py", "controller"):
        "D14(b): FidelityLadder and the bracket helpers move below suggest",
    ("suggest/bohb.py", "controller"):
        "D14(b): FidelityLadder and the bracket helpers move below suggest",
    ("api/validation.py", "controller"):
        "D14(c): PRIORITY_CLASSES and parse_condition move below api",
    ("api/validation.py", "runtime"):
        "D14(c): PERF_PREFIX moves below api with them",
    ("runtime/metrics.py", "service"):
        "D14(d): the remote observation stores are handed in by the caller",
    ("service/httpapi.py", "controller"):
        "D14(e): placement_table (a reader of lease files) moves below service",
    ("service/tenancy.py", "controller"):
        "D14(e): placement_table (a reader of lease files) moves below service",
    ("native/tailer.py", "runtime"):
        "D14(f): the line parsers of runtime/metrics.py move below native",
    ("utils/e2e_verify.py", "api"):
        "D14(g): e2e_verify is a client of the API and moves up to client/",
}

# What measures or tests the package is never imported by it.
OUTSIDE = ("bench", "benchmarks", "chip_smoke", "scripts", "tests")


def _node(parts):
    """The node that owns a module path given as parts below the package."""
    return "parallel.mesh" if parts[:2] == ["parallel", "mesh"] else parts[0]


def _node_of_file(rel):
    return _node(rel[: -len(".py")].split("/"))


def _imported(tree, package_parts):
    """Yield (line, absolute dotted parts) for every name a module that lives
    in the package ``package_parts`` imports. A ``from`` import yields the
    module and the name, so ``from . import mesh`` reads as the module it is."""
    for stmt in ast.walk(tree):
        if isinstance(stmt, ast.Import):
            for alias in stmt.names:
                yield stmt.lineno, alias.name.split(".")
        elif isinstance(stmt, ast.ImportFrom):
            module = stmt.module.split(".") if stmt.module else []
            if stmt.level:
                module = package_parts[: len(package_parts) - (stmt.level - 1)] + module
            for alias in stmt.names:
                yield stmt.lineno, module + [alias.name]


@functools.lru_cache(maxsize=None)
def _graph():
    """(edges, outside): edges maps a node to {(file, imported node): [lines]}
    over its siblings; outside lists "file:line name" for imports of OUTSIDE."""
    edges, outside = {}, []
    root = os.path.join(REPO, PACKAGE)
    for dirpath, _, filenames in os.walk(root):
        for filename in filenames:
            if not filename.endswith(".py"):
                continue
            path = os.path.join(dirpath, filename)
            rel = os.path.relpath(path, root).replace(os.sep, "/")
            src = _node_of_file(rel)
            mine = edges.setdefault(src, {})
            with open(path) as f:
                tree = ast.parse(f.read(), filename=path)
            for line, target in _imported(tree, [PACKAGE] + rel.split("/")[:-1]):
                if target[0] in OUTSIDE:
                    outside.append(f"{rel}:{line} {'.'.join(target)}")
                if target[0] != PACKAGE or len(target) < 2:
                    continue
                dst = _node(target[1:])
                if dst != src:
                    mine.setdefault((rel, dst), []).append(line)
    return edges, tuple(outside)


@pytest.mark.parametrize("node", sorted(TIER))
def test_a_node_imports_only_from_its_tier_and_below(node):
    edges, _ = _graph()
    assert node in edges, f"ORDER names {node}, which katib_tpu/ does not hold"
    upward = {
        edge: lines for edge, lines in edges[node].items()
        if TIER.get(edge[1], len(ORDER)) > TIER[node]
    }
    excused = {edge for edge in EXCEPTIONS if _node_of_file(edge[0]) == node}
    new = sorted(f"{f}:{lines[0]} imports {dst}" for (f, dst), lines in upward.items()
                 if (f, dst) not in excused)
    assert not new, (
        f"{node} (tier {TIER[node]}) imports from above itself: {new}. Move what is "
        "imported down, or hand it in from the caller; EXCEPTIONS takes no new line."
    )
    stale = sorted(excused - set(upward))
    assert not stale, f"EXCEPTIONS lists edges that are gone, delete them: {stale}"


def test_the_package_imports_nothing_that_measures_or_tests_it():
    edges, outside = _graph()
    assert not outside, f"katib_tpu imports from outside the package: {outside}"
    unplaced = sorted(set(edges) - set(TIER))
    assert not unplaced, f"new top-level nodes need a place in ORDER: {unplaced}"
    for edge, debt in EXCEPTIONS.items():
        assert debt.startswith("D14("), f"{edge} names no ROADMAP debt"
