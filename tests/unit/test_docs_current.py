"""The documents name only files that exist.

Every back-quoted token of a document that is a path to a Python file (ends
in ``.py`` and holds a ``/``) or a record's name (``PERF.md``,
``BENCHMARK.json``, ``PERF_LEDGER.jsonl``), once a trailing ``:line`` or
``::name`` is cut, has to exist under the checkout's root, ``deepspeed_tpu/``,
``tests/``, ``tests/unit/`` or ``docs/``. This is what keeps a document from
citing a deleted script or record as evidence. ``MIGRATION.md`` maps the
reference's paths and ``PERF.md``, ``ROADMAP.md`` and ``CHANGES.md`` are
history: they are not cases.
"""

import glob
import os
import re

import pytest

REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
BASES = ("", "deepspeed_tpu", "tests", "tests/unit", "docs")
DOCUMENTS = ["README.md", ".claude/skills/verify/SKILL.md"] + sorted(
    os.path.relpath(p, REPO_ROOT)
    for p in glob.glob(os.path.join(REPO_ROOT, "docs", "*.md"))
)

_TOKEN = re.compile(r"`([^`\n]+)`")
_RECORD = re.compile(r"[A-Z][A-Za-z_0-9.]*\.(json|jsonl|md)")
_SUFFIX = re.compile(r"(::[\w.\[\]-]+|:\d+(-\d+)?)+$")


def named_files(text):
    """The back-quoted tokens of ``text`` that name a file, suffixes cut."""
    out = []
    for token in _TOKEN.findall(text):
        token = _SUFFIX.sub("", token.strip())
        if (token.endswith(".py") and "/" in token and " " not in token) \
                or _RECORD.fullmatch(token):
            out.append(token)
    return out


def test_named_files_reads_the_forms_the_documents_use():
    text = ("`docs/gen_config_reference.py`, `tests/unit/test_kv_heat.py::TestX`, "
            "`serving/scheduler.py:695`, `PERF.md`, `BENCH_pr9.json`, "
            "`python bench.py`, `kv_heat.jsonl`, `ops/attention.py::f[a-b]`")
    assert named_files(text) == [
        "docs/gen_config_reference.py", "tests/unit/test_kv_heat.py",
        "serving/scheduler.py", "PERF.md", "BENCH_pr9.json", "ops/attention.py",
    ]


@pytest.mark.parametrize("document", DOCUMENTS)
def test_document_names_only_files_that_exist(document):
    with open(os.path.join(REPO_ROOT, document), encoding="utf-8") as fh:
        tokens = named_files(fh.read())
    missing = sorted({
        t for t in tokens
        if not any(os.path.exists(os.path.join(REPO_ROOT, b, t)) for b in BASES)
    })
    assert not missing, f"{document} names files that are not in the tree: {missing}"
