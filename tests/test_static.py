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
operator documents cite exist.
"""

from __future__ import annotations

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
