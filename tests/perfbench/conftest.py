"""The one place a test under ``tests/perfbench`` takes the committed table
from: ``table`` is the tree's manifest and then the tree's with a stand-in
cell appended (``tiny.with_stand_in``: one more configuration and backlog
cell, twelve per-layer entries of its own, a place in every shared list).
A test that reads ``BENCHMARK.json``'s tables asks for it and so runs twice;
what it holds has to hold of both, which is what "a later PR appends, and
edits no file that is there" means for the tests themselves."""

import pytest

from perfbench.manifest import Manifest

from . import tiny


@pytest.fixture(scope="session", params=["tree", "stand-in"])
def table(request, tmp_path_factory):
    root = tiny.REPO if request.param == "tree" else tiny.with_stand_in(tmp_path_factory.mktemp("table"))
    m = Manifest(root)
    m.validate()
    return m
