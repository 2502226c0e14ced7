"""Shared set-up of the benchmark's own tests: the repository root and
``src`` on the path, and the tiny catalog fixture."""

from __future__ import annotations

import pytest

from chipbench_tiny import write_catalog


@pytest.fixture(scope="module")
def tiny_catalog(tmp_path_factory):
    from chipbench.catalog import Catalog

    return Catalog(write_catalog(tmp_path_factory.mktemp("tiny")))
