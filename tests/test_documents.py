"""The documents name what exists: every path in ``README.md``'s module
table is in the repository, and nothing cites a section or helper of
``bench.py`` that ``bench.py`` does not define (ISSUE 28: the chip sections
went, and four comments and a README row still pointed at them).
``benchmarks/`` is outside this test's reach on purpose: its files change
only in ``benchmark`` PRs."""

import ast
import glob
import os
import re

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# ``bench.py:name``, and ``bench.py`` followed by a ``bench_*`` section name
# (``bench.py``'s ``bench_serve`` section; ``python bench.py bench_ops``).
# ``bench.py:66`` cites a line, not a name.
_CITATIONS = (re.compile(r"bench\.py:([A-Za-z_]\w*)"),
              re.compile(r"bench\.py\W*(?:s\W+)?(bench_\w+)"))


def _read(path: str) -> str:
    with open(path, encoding="utf-8") as fh:
        return fh.read()


def _cited_bench_names(text: str) -> set:
    return {m.group(1) for pattern in _CITATIONS
            for m in pattern.finditer(text)}


def _module_table_paths(readme: str) -> list:
    """Backticked entries of the first column of the table under
    ``## Layout``."""
    table = readme.split("## Layout", 1)[1].split("\n\n", 2)[1]
    rows = [r for r in table.splitlines() if r.startswith("| `")]
    return [p for r in rows for p in re.findall(r"`([^`]+)`",
                                                r.split("|")[1])]


def test_documents_name_what_exists():
    readme = _read(os.path.join(REPO, "README.md"))
    paths = _module_table_paths(readme)
    assert "bench.py" in paths and len(paths) >= 15, paths
    missing = [p for p in paths if not os.path.exists(os.path.join(REPO, p))]
    assert missing == [], missing

    defined = {node.name for node in
               ast.parse(_read(os.path.join(REPO, "bench.py"))).body
               if isinstance(node, (ast.FunctionDef, ast.ClassDef))}
    # the reader itself: it sees both forms, and a deleted name is not defined
    assert _cited_bench_names(
        "(bench.py:bench_lr), `bench.py`'s ``bench_serve`` section, "
        "bench.py:66") == {"bench_lr", "bench_serve"}
    assert "bench_serve" in defined and "bench_lr" not in defined

    files = ([os.path.join(REPO, "README.md"), os.path.join(REPO, "Makefile"),
              os.path.join(REPO, "chip_smoke.py")]
             + glob.glob(os.path.join(REPO, "docs", "*.md"))
             + glob.glob(os.path.join(REPO, "multiverso_tpu", "**", "*.py"),
                         recursive=True))
    stale = {os.path.relpath(f, REPO): sorted(names) for f in files
             if (names := _cited_bench_names(_read(f)) - defined)}
    assert stale == {}, stale
