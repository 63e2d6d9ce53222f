"""Back-compat anchors for the two original static screens (ISSUE 1/2).

The NameError scan and the hot-path allocation-idiom screen that used to
live here as ad-hoc test code are now first-class checkers in
:mod:`psana_ray_tpu.lint` (ISSUE 3) — registry, shared parse, central
allowlist with rot detection, CLI. ``tests/test_lint.py`` is the full
tier-1 driver; these two tests pin the MIGRATED screens by name so the
original invariants keep their own failure identity (a hot-path
regression fails here exactly as it did pre-framework, not just inside
an aggregate lint test).

A third screen is over the records, not the code: the files the
operator documents cite exist. A fourth is over the package's shape:
a box imports only from the boxes below it.
"""

from __future__ import annotations

import ast
import re

import pytest

from psana_ray_tpu.lint import run_lint
from psana_ray_tpu.lint.core import PACKAGE_DIR, REPO_ROOT


def _findings(checker: str):
    result = run_lint(checkers=[checker])
    return [f for f in result.findings if f.checker == checker]


def test_no_undefined_names():
    """The ISSUE 1 screen: latent NameErrors (deferred annotations,
    version-gated builtins like py3.10 ExceptionGroup) are tier-1."""
    found = _findings("undefined-name")
    assert not found, "\n".join(f.render() for f in found)


def test_hot_path_has_no_per_frame_allocation_idioms():
    """The ISSUE 2 screen: the zero-copy datapath must not regrow
    .tobytes()/.to_bytes(/raw .recv(/bytes(...) per-frame idioms."""
    found = _findings("hot-alloc")
    assert not found, "\n".join(f.render() for f in found)


# the one path the operator documents cite that is NOT this repo's: the
# reference project's own consumer, in MIGRATION.md's "psana-ray" column
_REFERENCE_REPO_FILES = {"examples/psana_consumer.py"}


@pytest.mark.parametrize("doc", ["README.md", "PARITY.md", "MIGRATION.md"])
def test_every_file_an_operator_document_cites_exists(doc):
    """The documents a new owner reads first send the reader to files
    (``tests/test_x.py``, ``models/fold.py``, ``benchmark/run.py``): each
    such path, as written or under the package, must exist — a deleted
    module or script must take its citations with it."""
    text = (REPO_ROOT / doc).read_text()
    cited = set(re.findall(r"`([\w.\-]+/[\w./\-]+\.(?:py|json|cpp|md|toml))(?:::?[\w:.\-\[\]]+)?`", text))
    assert cited, f"{doc} cites no file: the pattern has rotted"
    gone = sorted(
        p for p in cited - _REFERENCE_REPO_FILES
        if not (REPO_ROOT / p).exists() and not (PACKAGE_DIR / p).exists()
    )
    assert not gone, f"{doc} cites files that do not exist: {gone}"


# The package as boxes, top row first. A box imports only from rows BELOW
# its own: never from its own row, never upward. A name with ``.py`` is a
# top-level module; every top-level module stands in a row.
#
#           __init__.py        sfx.py          lint (reads the others
#                |                |                  as text, imports none)
#                v                v
#     producer.py   consumer.py   queue_server.py
#                        |
#                        v
#                     serving
#                        |
#              infeed    v    cluster
#                 |             |
#                 v   storage   v
#                        |
#          transport     v     models
#              |                 |
#   records.py v    parallel     v    sources
#       |              |
#   obs v     ops      v   checkpoint.py   cxi.py
#    |
#    v        utils        config.py
LAYERS = (
    ("__init__.py", "sfx.py", "lint"),
    ("producer.py", "consumer.py", "queue_server.py"),
    ("serving",),
    ("infeed", "cluster"),
    ("storage",),
    ("transport", "models"),
    ("records.py", "parallel", "sources"),
    ("obs", "ops", "checkpoint.py", "cxi.py"),
    ("utils", "config.py"),
)

# The arrows that still point up, each by the file that imports and the
# box it reaches, with what it is there for. ROADMAP lists them as a debt.
# A new one fails; one that has been repaired must leave this table.
UPWARD_IMPORTS = {
    ("utils/bufpool.py", "obs"): "the default pool registers its gauges with the metrics registry",
    ("utils/jaxenv.py", "obs"): "configure_compile_cache installs the listener of obs.jitwatch",
    ("utils/trace.py", "obs"): "phase() is obs' tracer and stage tags under a utils name",
    ("obs/collector.py", "transport"): "the collector scrapes its peers over a TcpQueueClient",
    ("obs/registry.py", "transport"): "a snapshot is tagged with transport.workers' worker id",
    ("records.py", "transport"): "a record puts itself back: recovery.return_to_queue and the transport's error types",
    ("transport/evloop.py", "storage"): "the server splices a SpilledRecord's payload and commits on delivery",
    ("transport/tcp.py", "storage"): "the replay client names the log's position and commit sentinels",
    ("transport/tcp.py", "cluster"): "the server holds the coordinator's GroupRegistry",
    ("transport/addressing.py", "cluster"): "open_queue dials cluster:// through ClusterClient",
    ("transport/workers.py", "cluster"): "a worker's ownership is cluster.hashring's rendezvous hash",
}

_ROW_OF = {box: row for row, boxes in enumerate(LAYERS) for box in boxes}


def _box_of(parts):
    """The box of a module path below the package: ``("transport",
    "tcp")`` -> ``transport``, ``("records",)`` -> ``records.py``."""
    if not parts:
        return "__init__.py"
    return parts[0] if (PACKAGE_DIR / parts[0]).is_dir() else f"{parts[0]}.py"


def _imports_of(path):
    """``(line, box)`` of every import of this package's own modules in
    ``path``, those inside functions too, each once."""
    here = ("psana_ray_tpu", *path.relative_to(PACKAGE_DIR).parts[:-1])
    found = set()
    for node in ast.walk(ast.parse(path.read_text())):
        if isinstance(node, ast.Import):
            names = [a.name for a in node.names]
        elif isinstance(node, ast.ImportFrom):
            base = ".".join(here[: len(here) - node.level + 1]) if node.level else ""
            module = ".".join(x for x in (base, node.module) if x)
            # ``from psana_ray_tpu import x`` / ``from . import x`` name modules
            names = [module] + [f"{module}.{a.name}" for a in node.names]
        else:
            continue
        for name in names:
            parts = tuple(name.split("."))
            if parts[0] == "psana_ray_tpu" and (
                len(parts) == 1 or (PACKAGE_DIR / parts[1]).is_dir()
                or (PACKAGE_DIR / f"{parts[1]}.py").exists()
            ):
                found.add((node.lineno, _box_of(parts[1:])))
    return sorted(found)


_BOXES = sorted(box for box in _ROW_OF if not box.endswith(".py")) + ["top-level modules"]


@pytest.mark.parametrize("box", _BOXES)
def test_a_package_imports_only_from_the_layers_below_it(box):
    """One case a sub-package, one for the top-level modules together.
    Read by ``ast``, so an import inside a function counts like one at
    the top of a file."""
    if box == "top-level modules":
        files = sorted(PACKAGE_DIR.glob("*.py"))
        assert {f.name for f in files} == {b for b in _ROW_OF if b.endswith(".py")}, (
            "a top-level module was added or removed: give it a row in LAYERS"
        )
    else:
        files = sorted((PACKAGE_DIR / box).rglob("*.py"))
        assert files, f"{box} holds no module: take it out of LAYERS"
    upward, owed = [], set()
    for path in files:
        rel = path.relative_to(PACKAGE_DIR).as_posix()
        mine = path.name if box == "top-level modules" else box
        for line, theirs in _imports_of(path):
            if theirs == mine or _ROW_OF[theirs] > _ROW_OF[mine]:
                continue
            if (rel, theirs) in UPWARD_IMPORTS:
                owed.add((rel, theirs))
            else:
                upward.append(f"{rel}:{line} imports {theirs}, which is not below {mine}")
    assert not upward, "\n".join(upward)
    listed = {
        key for key in UPWARD_IMPORTS
        if (key[0].split("/")[0] == box if "/" in key[0] else box == "top-level modules")
    }
    assert owed == listed, f"repaired, so take them out of UPWARD_IMPORTS: {sorted(listed - owed)}"
