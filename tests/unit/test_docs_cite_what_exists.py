"""A document that names a file of this tree names one that is there.

One case a document. Every backticked token that starts like a path of the
repo (a harness, a record, a document, a test, the package) and ends in
``.py``, ``.json`` or ``.md`` must be a file of the checkout; a glob must match
at least one. A deletion that leaves a citation behind fails here, in the
document's own case.
"""

import glob
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

DOCUMENTS = (["README.md", ".claude/skills/verify/SKILL.md"]
             + sorted(os.path.relpath(p, REPO) for p in
                      glob.glob(os.path.join(REPO, "docs", "*.md"))))

_CITES_A_FILE = re.compile(
    r"^(bench|benchmarks/|BENCH_|MULTICHIP_|docs/|tests/|chip_smoke"
    r"|deepspeed_tpu/)\S*\.(py|json|md)$")


def cited_files(text):
    """The words inside backticks in ``text`` that claim to be a file of the
    repo (``path::test`` and ``path: name`` cite ``path``; a command cites the
    script it runs)."""
    out = set()
    for span in re.findall(r"`([^`\n]+)`", text):
        for word in span.split():
            word = word.split("::")[0].rstrip(":,;")
            if _CITES_A_FILE.match(word):
                out.add(word)
    return sorted(out)


@pytest.mark.parametrize("document", DOCUMENTS)
def test_every_file_a_document_cites_is_in_the_tree(document):
    with open(os.path.join(REPO, document)) as f:
        cited = cited_files(f.read())
    dead = [c for c in cited if not glob.glob(os.path.join(REPO, c))]
    assert not dead, f"{document} cites files that are not in the tree: {dead}"


def test_the_reader_finds_what_it_is_for():
    text = ("run `python bench_gone.py --wq`, see `BENCH_*.json` and `docs/GONE.md`, held "
            "by `tests/unit/test_mesh.py::test_x` (`deepspeed_tpu/ops/moe/grouped_ffn.py: "
            "tile_rows`); `benchmarks.chipbench.run.main` and `--smoke` are no files")
    assert cited_files(text) == [
        "BENCH_*.json", "bench_gone.py", "deepspeed_tpu/ops/moe/grouped_ffn.py",
        "docs/GONE.md", "tests/unit/test_mesh.py"]
